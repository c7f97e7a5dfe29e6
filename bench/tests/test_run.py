"""``bench/run.py`` and ``BENCHMARK.json`` off the chip.

The cells run here through ``harness.run`` at a 3,000-vertex cut on the
CPU, with the platform check pointed at the CPU: every phase of a run but
the chip.  Nothing here is a device measurement.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import harness
import roofline
import trace_reduce
from conftest import BENCH, ROOT, SMALL

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_the_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["source"])
        assert c["file"].startswith("bench/") and c["reduced"] == []
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in b["per_layer"]:
        assert _one_line(m["layer"]) and m["moves"] in {
            e["name"] for e in b["end_to_end"]}
        assert harness.reader(m["name"]) is not None
        layers.add(m["layer"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] == 1 and _one_line(w["why"])
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)
    assert {m["name"] for m in b["end_to_end"]} == {
        "rank_solve_s", "ppr_qps", "ppr_p95_ms", "setup_s"}


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_run_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "web-google.rank", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and r.stdout.strip() == ""


def test_run_fails_with_only_the_benchmark_in_the_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "web-google.rank", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_no_program_is_a_setup_error(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "path", [p for p in sys.path
                                      if not p.endswith("src")])
    for mod in [m for m in sys.modules if m == "repro" or
                m.startswith("repro.")]:
        monkeypatch.delitem(sys.modules, mod)
    with pytest.raises(harness.SetupError, match="not in this checkout"):
        harness.import_program()


@pytest.mark.parametrize("cell", ["web-google.rank", "web-google.ppr-zipf",
                                  "web-stanford.rank"])
def test_cell_runs_end_to_end_on_the_cpu(cell):
    result, checks = harness.run(cell, 2**35 + 11, 0.5, False,
                                 platform="cpu", config_override=SMALL)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {m["name"] for m in harness.load_cell(cell).end_to_end}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert checks["window_compiles"]["value"] == 0
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"


def test_same_seed_same_answers():
    a = harness.run("web-google.ppr-zipf", 77, 0.5, False, platform="cpu",
                    config_override=SMALL)[1]
    b = harness.run("web-google.ppr-zipf", 77, 0.5, False, platform="cpu",
                    config_override=SMALL)[1]
    assert a == b


@pytest.mark.parametrize("cell", ["web-google.rank", "web-google.ppr-zipf"])
def test_traced_run_reports_every_per_layer_metric(cell, monkeypatch):
    # the CPU trace has no device plane: stand one in with a busy time
    # of 0.9 of a 10 s window, so the readers' arithmetic can be checked
    summary = trace_reduce.TraceSummary(
        window_s=10.0, busy_s=9.0, devices=1, span_device_s={},
        span_host_s={}, device_ops=[["op", 9.0]], idle_gaps=[["gap", 1.0]])
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda *a: summary)
    monkeypatch.setattr(trace_reduce, "load", lambda path: None)
    # and a peaks row for the CPU, which the table does not hold
    monkeypatch.setitem(roofline.PEAKS, "cpu", dict(hbm_bytes_per_s=1e9))
    result, _ = harness.run(cell, 5, 0.5, True, platform="cpu",
                            config_override=SMALL)
    metrics = result["metrics"]
    want = {m["name"] for m in harness.load_cell(cell).per_layer}
    assert set(metrics) == want
    rounds = sum(result["window"]["rounds"])
    split = cell.split(".")[1].split("-")[0]
    rows = 16 if split == "ppr" else 1
    round_s = 9.0 / rounds
    assert metrics[f"round_ms.{split}"]["value"] == pytest.approx(1e3 * round_s)
    assert metrics[f"round_roofline.{split}"]["value"] == pytest.approx(
        100 * roofline.round_bytes(SMALL["n"], SMALL["m"], rows, 8)
        / (round_s * 1e9))
    assert metrics[f"idle_share.{split}"]["value"] == pytest.approx(10.0)
    assert metrics[f"rounds.{split}"]["value"] == pytest.approx(
        rounds / len(result["window"]["rounds"]))
    assert result["device"]["busy_s"] == 9.0
    assert result["breakdown"]["device_ops"] == [["op", 9.0]]
