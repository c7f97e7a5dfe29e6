"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Run on a TPU.  Inside a host span ``window`` it runs a jitted loop three
times under ``rank.solve`` spans, sleeps 0.2 s under a ``host.sleep`` span
with nothing on the device, and runs the loop once more.  It copies the
profiler's ``.xplane.pb`` to the path given and prints the trace's planes
and lines and what ``trace_reduce`` makes of it.
"""
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce  # noqa: E402

SPANS = ("rank.solve", "host.sleep")


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    if jax.devices()[0].platform != "tpu":
        print("no TPU: this records a device trace", file=sys.stderr)
        return 2

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(0, 200, lambda i, y: jnp.tanh(y @ y) * 0.5, x)

    x = jnp.ones((512, 512), jnp.float32)
    work(x).block_until_ready()
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("rank.solve"):
                work(x).block_until_ready()
        with TraceAnnotation("host.sleep"):
            time.sleep(0.2)
        with TraceAnnotation("rank.solve"):
            work(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    shutil.copyfile(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    pd = trace_reduce.load(out)
    for pl in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in pl.lines]
        print(f"plane {pl.name!r}: {lines}")
    print(trace_reduce.reduce_trace(pd, SPANS))
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
