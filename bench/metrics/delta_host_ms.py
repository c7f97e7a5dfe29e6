"""Host time of the program's ``engine.delta.apply`` span per refresh in
the traced window, in ms: from the ``DeltaQuery`` to the edge-delta layout
ready on the device."""

SPAN = "engine.delta.apply"


def read(run):
    if run.trace is None or not run.calls:
        return None
    host = run.trace.span_host_s.get(SPAN)
    if host is None:
        return None
    return 1e3 * host / len(run.calls)
