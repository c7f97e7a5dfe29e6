"""Mean ITA rounds per solve or micro-batch in the window (the engine's
``iterations`` counter)."""


def read(run):
    if not run.calls:
        return None
    return sum(c["iterations"] for c in run.calls) / len(run.calls)
