"""Share of the dense push's edge work whose source vertex was active, in %.

``ops`` is the engine's Formula-15 count (out-edges of the active vertices,
summed over rounds); the dense push walks all ``m`` edges every round.
"""


def read(run):
    if not run.calls or any(c["ops"] is None for c in run.calls):
        return None
    rounds = sum(c["iterations"] for c in run.calls)
    return 100.0 * sum(c["ops"] for c in run.calls) / (rounds * run.config["m"])
