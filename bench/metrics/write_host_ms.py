"""Host time of the program's ``serve.write`` span per write applied in
the traced window, in ms: the serving tier's write lane, from a due write
to its layout on the device (the engine's ``engine.delta.apply`` inside)."""

SPAN = "serve.write"


def read(run):
    writes = sum(len(c.get("writes", ())) for c in run.calls)
    if run.trace is None or not writes:
        return None
    host = run.trace.span_host_s.get(SPAN)
    if host is None:
        return None
    return 1e3 * host / writes
