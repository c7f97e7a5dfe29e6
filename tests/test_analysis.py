"""repro-lint contracts: each rule code has a firing and a clean fixture,
the CLI honors its exit-code/JSON contracts, and suppression/baseline
round-trip.

AST-rule fixtures are source *strings* (the rules never import analyzed
code, so nothing here executes); trace-rule fixtures are throwaway
backends registered into the live registry and removed in ``finally``.
Citation-looking tokens and suppression markers inside fixture strings are
assembled at runtime so the repo's own lint pass over this file stays
clean.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    STRICT_DIRS,
    BaselineError,
    Violation,
    load_baseline,
    run,
    write_baseline,
)
from repro.analysis.ast_rules import analyze_source
from repro.analysis.citations import doc_heading_tokens, resolve_citation
from repro.analysis.rules import RULES, Rule
from repro.analysis.suppress import line_suppressions
from repro.analysis.trace_rules import (
    analyze_backends,
    check_collective_schedule,
    platform_expresses_donation,
)
from repro.core.backends import (
    STEP_IMPL_CLASSES,
    STEP_IMPLS,
    BackendCapabilities,
    SolverBackend,
    declared_capabilities,
    register_step_impl,
)
from repro.roofline.hlo_costs import CollectiveOp, parse_collectives

ROOT = Path(__file__).resolve().parent.parent
LINT = ROOT / "tools" / "repro_lint.py"

# assembled, not literal, so this file's own lint pass sees no citation
MD = ".md"
MARKER = "# repro-lint" + ": disable="


def lint(path: str, src: str) -> list:
    return analyze_source(path, textwrap.dedent(src), ROOT)


def codes(violations) -> list:
    return [v.code for v in violations]


# ---------------------------------------------------------------------------
# rule registry / violation model
# ---------------------------------------------------------------------------
def test_registry_covers_both_layers_with_stable_codes():
    assert {c for c, r in RULES.items() if r.layer == "ast"} == {
        "RL001", "RL002", "RL003", "RL004", "RL005", "RL006"
    }
    assert {c for c, r in RULES.items() if r.layer == "trace"} == {
        "RL101", "RL102", "RL103", "RL104"
    }


def test_rule_code_layer_prefixes_enforced():
    with pytest.raises(ValueError):
        Rule(code="RL101", name="x", layer="ast", summary="s")
    with pytest.raises(ValueError):
        Rule(code="RL001", name="x", layer="trace", summary="s")


def test_violation_format_is_path_line_col_code():
    v = Violation("RL001", "src/x.py", 3, 7, "msg")
    assert v.format() == "src/x.py:3:7: RL001 msg"
    assert v.to_dict()["code"] == "RL001"


# ---------------------------------------------------------------------------
# RL001 wall-clock
# ---------------------------------------------------------------------------
def test_rl001_fires_on_time_time_outside_clock_seam():
    src = """
    import time

    def f():
        return time.time()
    """
    assert "RL001" in codes(lint("src/repro/launch/x.py", src))


def test_rl001_resolves_aliases_and_from_imports():
    src = """
    from time import sleep as zzz

    def f():
        zzz(1)
    """
    assert "RL001" in codes(lint("src/repro/launch/x.py", src))


def test_rl001_clean_in_clock_seam_and_for_perf_counter():
    src = """
    import time

    def f():
        return time.time()
    """
    assert codes(lint("src/repro/serve/clock.py", src)) == []
    ok = """
    import time

    def f():
        return time.perf_counter()
    """
    assert codes(lint("src/repro/launch/x.py", ok)) == []


def test_rl001_ignores_unrelated_attribute_named_time():
    src = """
    class C:
        def time(self):
            return 0

        def f(self):
            return self.time()
    """
    assert codes(lint("src/repro/launch/x.py", src)) == []


# ---------------------------------------------------------------------------
# RL002 seedless-rng
# ---------------------------------------------------------------------------
def test_rl002_fires_on_legacy_numpy_and_stdlib_random():
    src = """
    import random

    import numpy as np

    def f():
        random.seed(0)
        return np.random.rand(3) + random.random()
    """
    got = codes(lint("tests/x.py", src))
    assert got.count("RL002") == 3


def test_rl002_clean_for_seeded_generator():
    src = """
    import numpy as np

    def f(seed):
        return np.random.default_rng(seed).random(3)
    """
    assert codes(lint("tests/x.py", src)) == []


# ---------------------------------------------------------------------------
# RL003 hardcoded-prngkey
# ---------------------------------------------------------------------------
def test_rl003_fires_on_literal_key_in_src_only():
    src = """
    from jax import random

    def init():
        return random.PRNGKey(42)
    """
    assert "RL003" in codes(lint("src/repro/core/x.py", src))
    assert codes(lint("tests/x.py", src)) == []  # tests may pin keys


def test_rl003_clean_when_seed_is_threaded_in():
    src = """
    import jax

    def init(seed):
        return jax.random.PRNGKey(seed)
    """
    assert codes(lint("src/repro/core/x.py", src)) == []


# ---------------------------------------------------------------------------
# RL004 doc-citation
# ---------------------------------------------------------------------------
def test_rl004_fires_on_unresolvable_citation():
    bad_doc = f"# see NOPE{MD} §intro\n"
    assert "RL004" in codes(lint("src/repro/x.py", bad_doc))
    bad_sec = f"# see DESIGN{MD} §no-such-heading\n"
    assert "RL004" in codes(lint("src/repro/x.py", bad_sec))


def test_rl004_clean_for_real_heading():
    ok = f"# see DESIGN{MD} §4 for applicability\n"
    assert codes(lint("src/repro/x.py", ok)) == []


def test_citation_helpers_resolve_against_design_headings():
    tokens = doc_heading_tokens(ROOT / "docs" / "DESIGN.md")
    assert {"1", "2", "3", "4", "5"} <= set(tokens)
    ok, _ = resolve_citation(ROOT, "DESIGN" + MD, "4")
    assert ok
    ok, detail = resolve_citation(ROOT, "DESIGN" + MD, "99")
    assert not ok and "99" in detail


# ---------------------------------------------------------------------------
# RL005 kwargs-passthrough
# ---------------------------------------------------------------------------
def test_rl005_fires_on_untyped_splat_in_src():
    src = """
    def solve(g, **kwargs):
        return inner_solver(g, **kwargs)
    """
    assert "RL005" in codes(lint("src/repro/core/x.py", src))


def test_rl005_clean_for_typed_config_funnels_and_tests():
    ok = """
    def solve(g, **kwargs):
        cfg = make_config("ita", **kwargs)
        cfg2 = config_for("ita")(**kwargs)
        d = dict(**kwargs)
        return cfg, cfg2, d
    """
    assert codes(lint("src/repro/core/x.py", ok)) == []
    bad = """
    def solve(g, **kwargs):
        return inner_solver(g, **kwargs)
    """
    assert codes(lint("tests/x.py", bad)) == []  # src/ only


# ---------------------------------------------------------------------------
# RL006 capability-mismatch
# ---------------------------------------------------------------------------
def test_rl006_fires_on_real_push_batch_declared_unbatched():
    src = """
    class B(SolverBackend):
        capabilities_decl = BackendCapabilities(batched=False)

        def push_batch(self, g, ctx, W):
            return W
    """
    assert "RL006" in codes(lint("src/repro/core/x.py", src))


def test_rl006_fires_on_batched_declaration_over_stub():
    src = """
    @register_step_impl("x")
    class B:
        def capabilities(self):
            return BackendCapabilities(batched=True)

        def push_batch(self, g, ctx, W):
            raise NotImplementedError
    """
    assert "RL006" in codes(lint("src/repro/core/x.py", src))


def test_rl006_clean_for_consistent_declarations():
    ok = """
    class B(StepBackend):
        capabilities_decl = BackendCapabilities(batched=True)

        def push_batch(self, g, ctx, W):
            return W

    class C(StepBackend):
        capabilities_decl = BackendCapabilities(batched=False)

    class NotABackend:
        def push_batch(self, g, ctx, W):
            raise NotImplementedError
    """
    assert codes(lint("src/repro/core/x.py", ok)) == []


# ---------------------------------------------------------------------------
# trace layer fixtures
# ---------------------------------------------------------------------------
def _with_backend(name, cls, fn):
    register_step_impl(name)(cls)
    try:
        return fn()
    finally:
        del STEP_IMPLS[name]
        del STEP_IMPL_CLASSES[name]


def _backend_violations(name):
    viols, _ = analyze_backends(ROOT, mesh_checks=False)
    return [v for v in viols if name in v.message]


def test_rl101_fires_on_dtype_promotion_and_weak_type():
    class Promote(SolverBackend):
        capabilities_decl = BackendCapabilities(batch_parallel_mesh=False, donation=False)

        def push(self, g, ctx, w):
            return jnp.asarray(w, jnp.float32) * jnp.float32(1)

    class Weak(SolverBackend):
        capabilities_decl = BackendCapabilities(batch_parallel_mesh=False, donation=False)

        def push(self, g, ctx, w):
            return jnp.broadcast_to(jnp.asarray(0.0), w.shape)

    got = _with_backend("zz_promote", Promote, lambda: _backend_violations("zz_promote"))
    assert {"RL101"} == set(codes(got)) and "float32" in got[0].message
    got = _with_backend("zz_weak", Weak, lambda: _backend_violations("zz_weak"))
    weak = [v for v in got if v.code == "RL101" and "weak" in v.message]
    assert weak  # float64 rows stay f64 but come back weak-typed


def test_rl102_fires_when_declared_donation_cannot_alias():
    if not platform_expresses_donation():
        pytest.skip("platform lowering never records donation")

    class NoAlias(SolverBackend):
        capabilities_decl = BackendCapabilities(batch_parallel_mesh=False)

        def push(self, g, ctx, w):
            return w * 2.0

        def push_batch(self, g, ctx, W):
            return W[:, : W.shape[1] // 2]  # output cannot alias [B, n]

    got = _with_backend("zz_noalias", NoAlias, lambda: _backend_violations("zz_noalias"))
    assert "RL102" in codes(got)


def test_rl103_fires_on_host_sync_and_callbacks():
    class Sync(SolverBackend):
        capabilities_decl = BackendCapabilities(batch_parallel_mesh=False, donation=False)

        def push(self, g, ctx, w):
            return w * float(np.asarray(w)[0])

    class Callback(SolverBackend):
        capabilities_decl = BackendCapabilities(batch_parallel_mesh=False, donation=False)

        def push(self, g, ctx, w):
            spec = jax.ShapeDtypeStruct(w.shape, w.dtype)
            return jax.pure_callback(lambda x: x, spec, w, vmap_method="sequential")

    got = _with_backend("zz_sync", Sync, lambda: _backend_violations("zz_sync"))
    assert "RL103" in codes(got)
    got = _with_backend("zz_cb", Callback, lambda: _backend_violations("zz_cb"))
    assert any(v.code == "RL103" and "callback" in v.message for v in got)


def test_trace_layer_clean_on_shipped_registry():
    viols, notes = analyze_backends(ROOT, mesh_checks=False)
    assert viols == []
    assert any("frontier" in n for n in notes)  # host-driven skip is noted


def test_trace_violations_anchor_to_defining_file():
    class Bad(SolverBackend):
        capabilities_decl = BackendCapabilities(batch_parallel_mesh=False, donation=False)

        def push(self, g, ctx, w):
            return w.astype(jnp.float32)

    got = _with_backend("zz_anchor", Bad, lambda: _backend_violations("zz_anchor"))
    assert got and got[0].path.endswith("tests/test_analysis.py")
    assert got[0].line > 0


# ---------------------------------------------------------------------------
# RL104 collective schedule
# ---------------------------------------------------------------------------
def _coll(kind, nbytes, mult=1.0):
    return CollectiveOp(
        kind=kind,
        bytes_per_exec=float(nbytes),
        multiplier=mult,
        computation="body",
        op_name="c",
    )


def test_rl104_schedule_checker_fires_on_forbidden_collectives():
    # batch-parallel mesh: a bulk all-gather is the replicated anti-pattern
    assert check_collective_schedule([_coll("all-gather", 8192)], 2, 1)
    # non-scalar all-reduce is the naive replicated sum on any mesh
    assert check_collective_schedule([_coll("all-reduce", 65536)], 2, 2)
    # reduce-scatter is only licensed on C > 1 meshes
    assert check_collective_schedule([_coll("reduce-scatter", 8192)], 2, 1)
    assert check_collective_schedule([_coll("all-to-all", 4096)], 2, 2)


def test_rl104_schedule_checker_clean_on_contract_schedules():
    # (R, 1): scalar n_active psum only
    assert check_collective_schedule([_coll("all-reduce", 8, 40)], 2, 1) == []
    # (R, C): psum_scatter over "model" + the scalar psum
    sched = [_coll("reduce-scatter", 8192, 40), _coll("all-reduce", 8, 40)]
    assert check_collective_schedule(sched, 2, 2) == []


def test_rl104_parses_collectives_out_of_hlo_text():
    hlo = textwrap.dedent(
        """
        HloModule m

        ENTRY %main (p0: f64[8,128]) -> f64[16,128] {
          %p0 = f64[8,128] parameter(0)
          ROOT %ag = f64[16,128] all-gather(%p0), dimensions={0}
        }
        """
    )
    ops = parse_collectives(hlo)
    assert [op.kind for op in ops] == ["all-gather"]
    assert ops[0].bytes_per_exec == 8 * 128 * 8
    assert check_collective_schedule(ops, 2, 1)


# ---------------------------------------------------------------------------
# capability introspection without instantiation (core/backends)
# ---------------------------------------------------------------------------
def test_declared_capabilities_match_instance_capabilities():
    for name, inst in STEP_IMPLS.items():
        assert declared_capabilities(name) == inst.capabilities(), name
        assert declared_capabilities(type(inst)) == inst.capabilities()


def test_declared_capabilities_default_derives_from_jittable():
    class HostDriven(SolverBackend):
        jittable = False

    caps = declared_capabilities(HostDriven)
    assert not caps.jittable and not caps.donation and not caps.batch_parallel_mesh


# ---------------------------------------------------------------------------
# suppression
# ---------------------------------------------------------------------------
def test_line_suppressions_parse_codes_per_line():
    text = f"a = 1  {MARKER}RL001\nb = 2\nc = 3  {MARKER}RL002,RL004\n"
    got = line_suppressions(text)
    assert got == {1: {"RL001"}, 3: {"RL002", "RL004"}}


def test_suppression_round_trip_in_runner(tmp_path):
    pkg = tmp_path / "src"
    pkg.mkdir()
    (pkg / "app.py").write_text(
        "import time\n\n"
        f"t0 = time.time()  {MARKER}RL001\n"
        f"x = 1  {MARKER}RL002\n",
        encoding="utf-8",
    )
    report = run(tmp_path, ["src"], trace=False)
    assert report.ok() and report.suppressed == 1
    assert any("RL002" in n and "stale" in n for n in report.notes)


# ---------------------------------------------------------------------------
# baseline / ratchet
# ---------------------------------------------------------------------------
def test_baseline_write_load_round_trip(tmp_path):
    p = tmp_path / "baseline.txt"
    counts = {("src/repro/launch/x.py", "RL001"): 2, ("tests/y.py", "RL002"): 1}
    write_baseline(p, counts)
    assert load_baseline(p) == counts
    assert load_baseline(tmp_path / "missing.txt") == {}


def test_baseline_rejects_strict_dir_entries(tmp_path):
    p = tmp_path / "baseline.txt"
    for strict in STRICT_DIRS:
        with pytest.raises(BaselineError):
            write_baseline(p, {(strict + "x.py", "RL001"): 1})
    p.write_text("src/repro/core/x.py:RL001:1\n", encoding="utf-8")
    with pytest.raises(BaselineError):
        load_baseline(p)
    p.write_text("not a baseline line\n", encoding="utf-8")
    with pytest.raises(BaselineError):
        load_baseline(p)


def test_baseline_budget_absorbs_then_fails_and_reports_progress(tmp_path):
    (tmp_path / "src").mkdir()
    bad = tmp_path / "src" / "app.py"
    bad.write_text("import time\nt0 = time.time()\n", encoding="utf-8")
    base = tmp_path / "baseline.txt"
    write_baseline(base, {("src/app.py", "RL001"): 2})
    report = run(tmp_path, ["src"], trace=False, baseline_path=base)
    assert report.ok() and report.baselined == 1
    assert any(p == ("src/app.py", "RL001", 2, 1) for p in report.progress)
    bad.write_text(
        "import time\nt0 = time.time()\nt1 = time.time()\nt2 = time.time()\n",
        encoding="utf-8",
    )
    report = run(tmp_path, ["src"], trace=False, baseline_path=base)
    assert not report.ok() and len(report.violations) == 1  # 2 absorbed, 1 over


# ---------------------------------------------------------------------------
# CLI contracts
# ---------------------------------------------------------------------------
def _cli(*args, root=None):
    cmd = [sys.executable, str(LINT), "--no-trace"]
    if root is not None:
        cmd += ["--root", str(root)]
    cmd += list(args)
    return subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)


def _fixture_tree(tmp_path, body):
    (tmp_path / "src").mkdir()
    (tmp_path / "tools").mkdir()
    (tmp_path / "src" / "app.py").write_text(body, encoding="utf-8")
    return tmp_path


def test_cli_list_rules_names_every_code():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for code in RULES:
        assert code in proc.stdout


def test_cli_exit_codes_clean_dirty_config_error(tmp_path):
    root = _fixture_tree(tmp_path, "import time\nt0 = time.time()\n")
    dirty = _cli("src", root=root)
    assert dirty.returncode == 1 and "RL001" in dirty.stdout
    (root / "src" / "app.py").write_text("x = 1\n", encoding="utf-8")
    assert _cli("src", root=root).returncode == 0
    assert _cli("src/missing_dir", root=root).returncode == 2
    (root / "tools" / "repro_lint_baseline.txt").write_text("garbage\n")
    assert _cli("src", root=root).returncode == 2


def test_cli_json_contract(tmp_path):
    root = _fixture_tree(tmp_path, "import time\nt0 = time.time()\n")
    proc = _cli("--json", "src", root=root)
    assert proc.returncode == 1
    rep = json.loads(proc.stdout)
    assert rep["version"] == 1 and rep["ok"] is False
    assert rep["files_checked"] == 1
    [v] = rep["violations"]
    assert v["code"] == "RL001" and v["path"] == "src/app.py" and v["line"] == 2
    assert rep["summary"]["by_code"] == {"RL001": 1}
    (root / "src" / "app.py").write_text("x = 1\n", encoding="utf-8")
    clean = _cli("--json", "src", root=root)
    assert clean.returncode == 0 and json.loads(clean.stdout)["ok"] is True


def test_cli_update_baseline_ratchets(tmp_path):
    root = _fixture_tree(tmp_path, "import time\nt0 = time.time()\n")
    assert _cli("--update-baseline", "src", root=root).returncode == 0
    base = root / "tools" / "repro_lint_baseline.txt"
    assert "src/app.py:RL001:1" in base.read_text()
    ok = _cli("src", root=root)
    assert ok.returncode == 0 and "1 baselined" in ok.stdout


def test_cli_update_baseline_refuses_strict_dirs(tmp_path):
    root = _fixture_tree(tmp_path, "x = 1\n")
    core = root / "src" / "repro" / "core"
    core.mkdir(parents=True)
    (core / "bad.py").write_text("import time\nt0 = time.time()\n")
    proc = _cli("--update-baseline", "src", root=root)
    assert proc.returncode == 2 and "zero-baseline" in proc.stderr


def test_repo_is_lint_clean_ast_layer():
    """The committed tree passes its own AST gate with an empty baseline."""
    report = run(
        ROOT,
        ["src", "tests"],
        trace=False,
        baseline_path=ROOT / "tools" / "repro_lint_baseline.txt",
    )
    assert report.ok(), [v.format() for v in report.violations]
    assert report.baselined == 0  # the shipped baseline stays empty
