"""Host time of the serve loop outside the engine, per micro-batch, in ms:
admission, queueing, batching and answer assembly."""


def read(run):
    if run.tier_s is None or not run.calls:
        return None
    return 1e3 * run.tier_s / len(run.calls)
