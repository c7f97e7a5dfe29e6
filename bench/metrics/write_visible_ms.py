"""Mean time from a write's arrival at the serving tier to the dispatch of
the first micro-batch that answers on its version or a newer one, in ms,
over the window's writes that such a micro-batch followed.

Read from the driver's records of each micro-batch: its dispatch time and
version (``Served.t_dispatch``, ``Served.graph_version``) and the writes
acknowledged since the one before it (``ServiceReport.writes``), all on
the service's clock."""


def read(run):
    seen = []
    for call in run.calls:
        for w in call.get("writes", ()):
            first = next((c for c in run.calls
                          if c["t_dispatch"] >= w["t_ack"]
                          and c["graph_version"] >= w["graph_version"]), None)
            if first is not None:
                seen.append(first["t_dispatch"] - w["t_arrival"])
    if not seen:
        return None
    return 1e3 * sum(seen) / len(seen)
