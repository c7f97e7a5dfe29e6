"""Distributed == single-device equivalence, on an 8-device host mesh.

Each test runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 (the main pytest process must keep seeing 1 device — see
conftest).  Asserted:

  * ITA 1-D and 2-D shard_map solvers == the single-device reference pi;
  * the bf16-wire 2-D solver stays within its precision floor.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": "src",
       "JAX_PLATFORMS": "cpu"}


def run_py(body: str) -> dict:
    """Run a python snippet in a fresh 8-device process, parse last json line."""
    script = textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_ita_1d_matches_reference():
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core import power_method
        from repro.core.distributed import ita_distributed_1d
        g = web_graph(700, 5200, dangling_frac=0.2, seed=3)
        mesh = jax.make_mesh((8,), ("data",))
        pi_ref = power_method(g, tol=1e-14, max_iter=500).pi
        r = ita_distributed_1d(g, mesh, xi=1e-13)
        err = float(jnp.max(jnp.abs(r.pi - pi_ref)))
        print(json.dumps({"err": err, "iters": r.iterations}))
    """)
    assert out["err"] < 1e-10, out


def test_ita_2d_matches_reference():
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core import power_method
        from repro.core.distributed import ita_distributed_2d
        g = web_graph(900, 7000, dangling_frac=0.15, seed=4)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        pi_ref = power_method(g, tol=1e-14, max_iter=500).pi
        r = ita_distributed_2d(g, mesh, xi=1e-13)
        err = float(jnp.max(jnp.abs(r.pi - pi_ref)))
        print(json.dumps({"err": err, "iters": r.iterations}))
    """)
    assert out["err"] < 1e-10, out


@pytest.mark.slow
def test_ita_2d_compressed_bounded_error():
    """bf16-wire ITA with error feedback: half the ICI bytes for a bounded
    ~1e-3 relative precision floor (the bf16 mantissa), never divergence."""
    out = run_py("""
        import jax, json
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.graph import web_graph
        from repro.core import power_method
        from repro.core.distributed import ita_distributed_2d_compressed
        g = web_graph(900, 7000, dangling_frac=0.15, seed=4)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        pi_ref = power_method(g, tol=1e-14, max_iter=500).pi
        r = ita_distributed_2d_compressed(g, mesh, xi=1e-10)
        rel = float(jnp.max(jnp.abs(r.pi - pi_ref) / pi_ref))
        print(json.dumps({"rel": rel, "iters": r.iterations}))
    """)
    assert out["rel"] < 1e-2, out
