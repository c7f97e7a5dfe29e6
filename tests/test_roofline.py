"""Roofline infrastructure: HLO cost parser (loop multipliers, dot flops,
slice-aware bytes, collectives) against hand-written HLO snippets, an
end-to-end check on a real compiled module, and the measured-cost contract:
samples from ``measure_step`` / ``measure_sharded_step`` must agree with
the analytic per-step byte model — in particular the per-round collective
bytes of the vertex-sharded schedules against the table in
docs/SHARDING.md ("`psum_scatter` over model: `(B/R)·(n/C)·d` sent per
device" for both the dense and sharded-ELL rows)."""

import math

import jax
import jax.numpy as jnp
import pytest
from _mesh_env import MESH, needs_devices, run_py

from repro.roofline.analysis import analyze_compiled, parse_shape_bytes
from repro.roofline.hlo_costs import parse_hlo_costs
from repro.roofline.hw import HW, TPUv5e, spec_for_device_kind
from repro.roofline.planner_costs import measure_step, roofline_seconds

SIMPLE_HLO = """
HloModule test, is_scheduled=true

ENTRY %main.1 (a: f32[128,256], b: f32[256,512]) -> f32[128,512] {
  %a = f32[128,256]{1,0} parameter(0)
  %b = f32[256,512]{1,0} parameter(1)
  ROOT %dot.1 = f32[128,512]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

LOOP_HLO = """
HloModule test, is_scheduled=true

%body.1 (arg: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %arg = (s32[], f32[64,64]) parameter(0)
  %iv = s32[] get-tuple-element(%arg), index=0
  %x = f32[64,64]{1,0} get-tuple-element(%arg), index=1
  %dot.2 = f32[64,64]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,64]{1,0} all-reduce(%dot.2), replica_groups={}, to_apply=%add.1
  ROOT %tuple.9 = (s32[], f32[64,64]) tuple(%iv, %ar)
}

%cond.1 (arg2: (s32[], f32[64,64])) -> pred[] {
  %arg2 = (s32[], f32[64,64]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

%add.1 (p0: f32[], p1: f32[]) -> f32[] {
  %p0 = f32[] parameter(0)
  %p1 = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%p0, %p1)
}

ENTRY %main.2 (x0: f32[64,64]) -> (s32[], f32[64,64]) {
  %x0 = f32[64,64]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[64,64]) tuple(%c0, %x0)
  ROOT %w = (s32[], f32[64,64]) while(%t0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"12"}}
}
"""


class TestShapeParsing:
    def test_basic_bytes(self):
        assert parse_shape_bytes("f32[128,256]") == 128 * 256 * 4
        assert parse_shape_bytes("bf16[10]") == 20
        assert parse_shape_bytes("(f32[4,4], s32[2])") == 64 + 8
        assert parse_shape_bytes("pred[8]") == 8

    def test_scalar_and_empty(self):
        assert parse_shape_bytes("f32[]") == 4
        assert parse_shape_bytes("token[]") == 0


class TestHloCosts:
    def test_simple_dot_flops(self):
        c = parse_hlo_costs(SIMPLE_HLO)
        assert c.flops == 2 * 128 * 512 * 256
        assert c.collective_bytes == 0

    def test_loop_multiplier_applies(self):
        c = parse_hlo_costs(LOOP_HLO)
        # dot inside a while body with known_trip_count=12
        assert c.flops == 12 * 2 * 64 * 64 * 64, c.loop_multipliers
        # the all-reduce is also x12
        assert c.collective_bytes == 12 * 64 * 64 * 4
        assert c.collective_by_kind["all-reduce"] == 12 * 64 * 64 * 4

    def test_real_compiled_module(self):
        """End-to-end: scanned matmuls must count once per layer."""
        L, D = 7, 32

        def f(ws, x):
            def body(x, w):
                return jnp.tanh(x @ w), jnp.zeros((), x.dtype)

            x, _ = jax.lax.scan(body, x, ws)
            return x

        ws = jnp.zeros((L, D, D), jnp.float32)
        x = jnp.zeros((8, D), jnp.float32)
        hlo = jax.jit(f).lower(ws, x).compile().as_text()
        c = parse_hlo_costs(hlo)
        expect = L * 2 * 8 * D * D
        assert abs(c.flops - expect) / expect < 0.01, (c.flops, expect)

    def test_analyze_compiled_terms(self):
        rep = analyze_compiled("t", "m", 4, {}, SIMPLE_HLO, model_flops=4 * 2 * 128 * 512 * 256)
        assert rep.compute_s == pytest.approx(2 * 128 * 512 * 256 / HW.peak_bf16_flops)
        assert rep.useful_ratio == pytest.approx(1.0)
        assert rep.dominant in ("compute", "memory", "collective")


# ---------------------------------------------------------------------------
# Measured-sample contract: single device
# ---------------------------------------------------------------------------
class TestMeasuredSamples:
    @pytest.fixture(scope="class")
    def g(self):
        from repro.graph import web_graph

        return web_graph(400, 3200, dangling_frac=0.25, seed=17)

    def test_dense_bytes_match_analytic_band(self, g):
        """One dense push streams the edge list and the vertex vectors,
        (m reads + m index reads + n write + n operand read) x d
        ~ 2(m + n)·d, then sums runs with a segmented scan: ceil(log2 m)
        passes, each reading the values and run flags twice (plain and
        shifted) and writing both, m·(3d + 3) bytes.  cost_analysis sees
        the XLA realisation (fused gathers, scratch) — hold it to a
        stated factor-2 band of the analytic figure, both directions."""
        s = measure_step("dense", g, dtype="float64")
        passes = math.ceil(math.log2(g.m))
        analytic = 2 * (g.m + g.n) * 8 + passes * g.m * (3 * 8 + 3)
        assert analytic / 2 <= s.bytes_accessed <= analytic * 2, (
            s.bytes_accessed,
            analytic,
        )

    def test_ell_bytes_cover_streamed_slots(self, g):
        """The bucketed-ELL kernel streams every padded slot at least
        once — its measured bytes must not undercut the real edge set."""
        s = measure_step("ell", g, dtype="float64")
        assert s.bytes_accessed >= g.m * 8

    @pytest.mark.parametrize("backend", ["dense", "ell", "frontier"])
    def test_seconds_are_roofline_priced(self, g, backend):
        s = measure_step(backend, g, dtype="float64")
        assert s.seconds == pytest.approx(
            roofline_seconds(s.flops, s.bytes_accessed, s.collective_bytes, s.platform)
        )
        assert s.n == g.n and s.m == g.m and s.op == "push"

    def test_frontier_batch_scales_linearly(self, g):
        """The host-driven backend's batch is B sequential pushes — its
        sample must charge exactly B x the single-row lowering."""
        one = measure_step("frontier", g, batch=1)
        three = measure_step("frontier", g, batch=3)
        assert three.flops == pytest.approx(3 * one.flops)
        assert three.bytes_accessed == pytest.approx(3 * one.bytes_accessed)
        assert three.op == "push_batch"

    def test_push_batch_sample_labels(self, g):
        s = measure_step("dense", g, batch=4)
        assert s.op == "push_batch" and s.batch == 4


# ---------------------------------------------------------------------------
# Measured-sample contract: vertex-sharded collectives vs docs/SHARDING.md
# ---------------------------------------------------------------------------
# the matrix cell's (R, C) when it is vertex-sharded, else the minimal one
SHARD_MESH = MESH if MESH[1] > 1 else (2, 2)

_SHARDED_BODY = """
    import json

    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.core.distributed import (
        _batch_2d_operands_cached, _ell_cols_operands_cached, resolve_mesh)
    from repro.graph import web_graph
    from repro.roofline.planner_costs import measure_sharded_step

    R, C = {mesh}
    g = web_graph(300, 2400, dangling_frac=0.2, seed=5)
    mesh = resolve_mesh((R, C))
    out = dict(R=R, C=C)
    part, _ = _batch_2d_operands_cached(g, mesh, C, "float64", "model")
    ellc, _ = _ell_cols_operands_cached(
        g, mesh, C, "float64", "model", (8, 32, 128), 8)
    out["n_pad"] = dict(dense=int(part.n_pad), ell=int(ellc.n_pad))
    for backend in ("dense", "ell"):
        s = measure_sharded_step(backend, g, (R, C), batch=8)
        out[backend] = dict(
            coll=float(s.collective_bytes), B_pad=int(s.batch),
            mesh=list(s.mesh), op=s.op)
    print(json.dumps(out))
"""


@needs_devices(SHARD_MESH[0] * SHARD_MESH[1])
def test_sharded_collective_bytes_match_sharding_table():
    """docs/SHARDING.md, (R, C) rows: `psum_scatter` over model sends
    `(B/R)·(n/C)·d` per device, for BOTH the dense and sharded-ELL
    schedules.  The parsed reduce-scatter operand is the full per-device
    [B/R, n_pad] block — C x the per-device sent figure — plus one 4-byte
    s32 all-reduce (the n_active psum).  Hold each backend to 5% of its
    analytic figure, and the two schedules to the same collective model."""
    out = run_py(_SHARDED_BODY.format(mesh=tuple(SHARD_MESH)))
    R, C = out["R"], out["C"]
    d = 8  # float64
    for backend in ("dense", "ell"):
        got = out[backend]
        assert got["op"] == "sharded-round"
        assert got["mesh"] == [R, C]
        n_pad = out["n_pad"][backend]
        per_device_sent = (got["B_pad"] // R) * (n_pad // C) * d
        expect = C * per_device_sent  # + one 4-byte all-reduce, inside 5%
        assert abs(got["coll"] - expect) / expect < 0.05, (backend, got, expect)


@needs_devices(2)
def test_batch_only_mesh_has_no_vertex_collective():
    """(R, 1) rows of the table: the vertex axis is whole, so no
    psum_scatter — only the scalar n_active all-reduce may remain."""
    out = run_py(_SHARDED_BODY.format(mesh=(2, 1)))
    for backend in ("dense", "ell"):
        assert out[backend]["coll"] <= 64, out[backend]


def test_specs_are_keyed_by_device_kind():
    assert spec_for_device_kind("TPU v5 lite") is TPUv5e
    assert TPUv5e.peak_bf16_flops == 197e12 and TPUv5e.hbm_bandwidth == 819e9
    assert spec_for_device_kind(jax.devices()[0].device_kind) is not None
    for unknown in ("tpu", "TPU v6 lite", "gpu"):
        with pytest.raises(KeyError, match="no hardware spec"):
            spec_for_device_kind(unknown)
