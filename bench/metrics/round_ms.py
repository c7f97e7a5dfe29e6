"""Device busy time of the traced window per ITA round run in it, in ms."""


def read(run):
    rounds = sum(c["iterations"] for c in run.calls)
    if run.trace is None or not rounds:
        return None
    return 1e3 * run.trace.busy_s / rounds
