"""The benchmark's own tests, run on the CPU: ``pytest bench/tests``.

They put ``bench/`` and the program's ``src/`` on the import path the way
``bench/run.py`` finds them, and hold JAX to the CPU.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

# a web-Google cut small enough for the CPU: 3,000 vertices, the published
# dangling share, the generator's shape
SMALL = dict(n=3000, m=20000, n_dangling=467,
             generator=dict(kind="powerlaw_web", dataset_seed=0, gamma_in=0.9,
                            gamma_out=0.7, oversample=1.5))
