"""A round's least bytes at the chip's HBM bandwidth, over its device time, in %."""
import roofline


def read(run):
    rounds = sum(c["iterations"] for c in run.calls)
    if run.trace is None or not rounds:
        return None
    seconds = run.trace.busy_s / rounds
    rows = run.calls[0]["rows"]
    least = roofline.round_bytes(run.config["n"], run.config["m"], rows,
                                 run.value_bytes)
    bw = roofline.peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (seconds * bw)
