"""chip_smoke.py off the chip: its phases at a tiny size on the CPU, its
host reference, and its refusal to run anywhere but on a TPU.

The script itself only runs on a TPU; these tests call its phase
functions directly on a 3,502-vertex web-Google cut, so the code path the
chip takes is exercised on every test run.  The compile-cache helper the
script shares with the CLIs is checked in fresh processes, since it sets
process-wide JAX config.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from _mesh_env import needs_devices, run_py

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def g(smoke):
    return smoke.make_graph(scale=0.004)


def test_host_reference_matches_reference_pagerank(smoke, g):
    from repro.core import reference_pagerank

    ref, _ = smoke.host_pagerank(np.asarray(g.src), np.asarray(g.dst), g.n,
                                 np.full(g.n, 1.0 / g.n))
    # both stop at an l2 step of 1e-14; they differ only in summation order
    assert np.abs(ref - np.asarray(reference_pagerank(g))).sum() < 1e-12


def test_compare_topk_requires_the_reference_order(smoke):
    ref = np.array([0.5, 0.2, 0.2 + 1e-13, 0.1])
    assert smoke.compare_topk([0, 2, 1], ref[[0, 2, 1]], ref, 1e-9)
    # even a swap of two values 1e-13 apart fails, as does a real swap
    assert not smoke.compare_topk([0, 1, 2], ref[[0, 1, 2]], ref, 1e-9)
    assert not smoke.compare_topk([0, 3, 1], ref[[0, 3, 1]], ref, 1e-9)
    # and so does a score off by more than the bound
    assert not smoke.compare_topk([0, 2, 1], ref[[0, 2, 1]] + 1e-6, ref, 1e-9)


def test_ranking_phase_passes_its_reference_check(smoke, g, capsys):
    engine = smoke.run_ranking(g)
    assert engine.step_impl == "dense"
    assert "global ranking check: PASS" in capsys.readouterr().out


def test_serving_phase_serves_every_request_and_checks_answers(smoke, g,
                                                               capsys):
    from repro.core import EnginePlan, PageRankEngine

    smoke.run_serving(g, PageRankEngine(g, EnginePlan()), n_requests=32)
    out = capsys.readouterr().out
    assert "served/offered: 32/32" in out
    assert "serving check: PASS" in out


@needs_devices(4)
def test_mesh_phase_on_four_host_devices():
    out = run_py(f"""
        import importlib.util, io, json, contextlib
        import jax
        jax.config.update("jax_enable_x64", True)
        spec = importlib.util.spec_from_file_location("s", {SCRIPT!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            s.run_mesh(s.make_graph(scale=0.004))
        text = buf.getvalue()
        print(json.dumps({{"ok": "mesh check: PASS" in text,
                          "identical": "bit-identical=True" in text,
                          "spread": "graph on devices [0, 1, 2, 3]" in text}}))
    """)
    assert out == {"ok": True, "identical": True, "spread": True}, out


def _run(args, env_extra=None, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",)}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one", "four"])
def test_refuses_to_run_without_a_tpu(argv):
    r = _run([SCRIPT, *argv])
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("env_dir", [None, "custom"], ids=["unset", "set"])
def test_compile_cache_dir(env_dir, tmp_path):
    extra = {}
    if env_dir is not None:
        extra["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = _run(["-c", (
        "import json, jax\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "used = use_compile_cache()\n"
        "print(json.dumps([used, jax.config.jax_compilation_cache_dir]))")],
        env_extra=extra)
    assert r.returncode == 0, r.stderr
    used, config = json.loads(r.stdout.strip().splitlines()[-1])
    want = (os.path.join(ROOT, ".jax_cache") if env_dir is None
            else str(tmp_path / env_dir))
    assert used == config == want
