"""ITA's 2-D edge partition lifted to learned message passing (shard_map).

This is the §Perf hillclimb for the graphcast × ogb_products cell — and the
clearest "beyond-paper" payoff of the paper's own layout: the block-cyclic
(dst-row × src-column) partition built for ITA (graph/partition.py) carries
over UNCHANGED to interaction-network GNNs; only the per-edge scalar
`c·h/deg` becomes a learned MLP message.

Layouts per device (i, j) on the (data=R, model=C) grid:
    h_row  [nr, d]   — node state for dst row-block i   (replicated over j)
    h_col  [nc, d]   — node state for src col-block j   (replicated over i,
                        block-cyclic permuted — partition_2d.perm)
    e      [e_blk,d] — edge state for edge block (i, j)
    src/dst local indices into h_col / h_row (sentinel-padded)

One interaction layer:
    e'        = e + MLP([e, h_col[src], h_row[dst]])          (local)
    agg_i     = segment_sum(e', dst, nr)                      (local)
    agg_sub   = psum_scatter(agg_i, 'model')                  [sub, d]
    h_sub'    = h_sub + MLP([h_sub, agg_sub])                 (local)
    h_row'    = all_gather(h_sub', 'model')                   [nr, d]
    h_col'    = all_gather(h_sub', 'data')                    [nc, d]

Per-layer collective volume per device: d·(nr + nr + nc)·4 bytes — NO
all-to-all, no replicated [n, d] feature matrix, no GSPMD scatter
pessimisation (the baseline auto-sharded version gathers 5 GB of f32 per
layer in the backward and lands at 69 GB/device; see EXPERIMENTS.md §Perf).

The node-MLP compute is split over columns (each column owns the n/(R·C)
sub-chunk of its row block) — the same psum_scatter/all_gather trick that
makes the 2-D ITA reassembly work, so nothing is computed redundantly.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..layers import mlp
from .graphcast import GraphCastConfig

__all__ = ["gc2d_loss", "gc2d_input_specs", "build_gc2d_job", "gc2d_prepare"]


def _mlp_local(p, x, act=jax.nn.silu):
    return mlp(p, x, act=act)


def gc2d_forward_local(params, cfg: GraphCastConfig, geom: dict,
                       nodes_row, nodes_sub, pos_col, pos_row,
                       src_loc, dst_loc, row_axis="data", col_axis="model"):
    """Per-device body (runs under shard_map).  Shapes are LOCAL."""
    nr, nc, sub = geom["nr"], geom["nc"], geom["sub"]

    # ---- encoders ----------------------------------------------------
    # node encoder on this column's sub-chunks only (no redundancy), then
    # broadcast into both layouts via the two gathers.
    h_sub = _mlp_local(params["node_enc"], nodes_sub)              # [sub, d]
    h_row = jax.lax.all_gather(h_sub, col_axis, axis=0, tiled=True)   # [nr, d]
    h_col = jax.lax.all_gather(h_sub, row_axis, axis=0, tiled=True)   # [nc, d]

    rel = pos_col[src_loc] - pos_row[dst_loc]                       # [e, 3]
    norm = jnp.linalg.norm(rel, axis=-1, keepdims=True)
    e = _mlp_local(params["edge_enc"], jnp.concatenate([rel, norm], -1))
    emask = (src_loc < nc)[:, None]
    # optional mixed precision: the edge state is the HBM hog (62M x 512);
    # bf16 halves it while node state / reductions stay f32.
    e_dtype = geom.get("edge_dtype", jnp.float32)
    e = jnp.where(emask, e, 0).astype(e_dtype)

    # ---- processor ----------------------------------------------------
    # carry only (h_sub [sub,d], e [e_blk,d]); the row/col views are
    # re-gathered inside each layer, so per-layer remat saves are
    # (sub + e_blk)·d instead of (nr + nc + sub + e_blk)·d — the gathers
    # are cheap (collective term is 20x under budget after this layout)
    # while the carry dominates HBM.  Layers additionally scan in groups
    # of `remat_g` with an outer checkpoint: persistent saves drop another
    # L/remat_g x (same segmented-remat trick as the LM stack).
    def layer(carry, blk):
        h_sub, e = carry
        h_row = jax.lax.all_gather(h_sub, col_axis, axis=0, tiled=True)
        h_col = jax.lax.all_gather(h_sub, row_axis, axis=0, tiled=True)
        e_in = jnp.concatenate([e, h_col[src_loc].astype(e_dtype),
                                h_row[dst_loc].astype(e_dtype)], axis=-1)
        e = e + jnp.where(emask, _mlp_local(blk["edge_mlp"], e_in), 0).astype(e_dtype)
        agg = jax.ops.segment_sum(e.astype(jnp.float32), dst_loc,
                                  num_segments=nr + 1)[:nr]
        agg_sub = jax.lax.psum_scatter(agg, col_axis, scatter_dimension=0,
                                       tiled=True)                  # [sub, d]
        h_sub = h_sub + _mlp_local(blk["node_mlp"],
                                   jnp.concatenate([h_sub, agg_sub], -1))
        return (h_sub, e), jnp.zeros((), h_sub.dtype)

    L = cfg.n_layers
    remat_g = geom.get("remat_g", 4)
    if L % remat_g == 0 and remat_g > 1:
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(L // remat_g, remat_g, *a.shape[1:]),
            params["blocks"])

        def group(carry, blkg):
            return jax.lax.scan(jax.checkpoint(layer), carry, blkg)

        (h_sub, e), _ = jax.lax.scan(jax.checkpoint(group), (h_sub, e), grouped)
    else:
        (h_sub, e), _ = jax.lax.scan(jax.checkpoint(layer), (h_sub, e),
                                     params["blocks"])

    # ---- decoder (on sub-chunks; classification head) -----------------
    return _mlp_local(params["decoder"], h_sub)                     # [sub, n_out]


def gc2d_loss(params, cfg: GraphCastConfig, geom: dict, mesh: Mesh, batch: dict):
    """Masked node-classification CE over the 2-D layout (global view)."""
    row_axis, col_axis = geom["row_axis"], geom["col_axis"]
    sub_spec = P((row_axis, col_axis) if isinstance(row_axis, str) else
                 (*row_axis, col_axis))
    # inputs arrive already laid out (see gc2d_input_specs)
    col_spec = P(col_axis)
    row_spec = P(row_axis)
    edge_spec = P(row_axis, col_axis, None)

    def local(nodes_sub, pos_col, pos_row, src_loc, dst_loc, targets_sub,
              tmask_sub):
        logits = gc2d_forward_local(
            params, cfg, geom, None, nodes_sub, pos_col, pos_row,
            src_loc[0, 0], dst_loc[0, 0],
            row_axis=row_axis, col_axis=col_axis)
        tm = tmask_sub.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   targets_sub[:, None], axis=-1)[..., 0]
        loss_sum = jnp.sum((logz - gold) * tm)
        cnt = jnp.sum(tm)
        axes = tuple(mesh.axis_names)
        loss_sum = jax.lax.psum(loss_sum, axes)
        cnt = jax.lax.psum(cnt, axes)
        return loss_sum / jnp.maximum(cnt, 1.0)

    sm = shard_map(
        local, mesh=mesh,
        in_specs=(sub_spec, col_spec, row_spec, edge_spec, edge_spec,
                  sub_spec, sub_spec),
        out_specs=P(),
        check_vma=False,
    )
    loss = sm(batch["nodes_sub"], batch["pos_col"], batch["pos_row"],
              batch["src"], batch["dst"], batch["targets_sub"],
              batch["tmask_sub"])
    return loss, {"ce": loss}


# ---------------------------------------------------------------------------
# dry-run job + host-side data prep
# ---------------------------------------------------------------------------
def gc2d_geometry(n: int, m: int, mesh: Mesh, *, pad_factor: float = 1.1):
    """pad_factor sizes the per-device edge block over the uniform mean.
    1.1 suffices for near-uniform dst distributions (the ogb_products
    stand-in); heavy-tailed real crawls want degree-aware block balancing
    (the 2-D layout admits it — rows are just vertex ranges) or a larger
    factor."""
    row_axis: object = "data"
    col_axis = "model"
    R, C = mesh.shape["data"], mesh.shape["model"]
    if "pod" in mesh.axis_names:
        row_axis = ("pod", "data")
        R = mesh.shape["pod"] * mesh.shape["data"]
    n_pad = ((n + R * C - 1) // (R * C)) * (R * C)
    e_pad = ((int(m / (R * C) * pad_factor) + 8 + 7) // 8) * 8
    return dict(R=R, C=C, nr=n_pad // R, nc=n_pad // C, sub=n_pad // (R * C),
                n_pad=n_pad, e_pad=e_pad, row_axis=row_axis, col_axis=col_axis)


def gc2d_input_specs(meta: dict, geom: dict, d_feat: int):
    R, C, e_pad = geom["R"], geom["C"], geom["e_pad"]
    return {
        "nodes_sub": jax.ShapeDtypeStruct((geom["n_pad"], d_feat), jnp.float32),
        "pos_col": jax.ShapeDtypeStruct((geom["n_pad"], 3), jnp.float32),
        "pos_row": jax.ShapeDtypeStruct((geom["n_pad"], 3), jnp.float32),
        "src": jax.ShapeDtypeStruct((R, C, e_pad), jnp.int32),
        "dst": jax.ShapeDtypeStruct((R, C, e_pad), jnp.int32),
        "targets_sub": jax.ShapeDtypeStruct((geom["n_pad"],), jnp.int32),
        "tmask_sub": jax.ShapeDtypeStruct((geom["n_pad"],), jnp.bool_),
    }


def gc2d_prepare(g, features, labels, label_mask, pos, mesh: Mesh):
    """Host-side layout builder from a real Graph (tests + examples)."""
    from ...graph.partition import partition_2d

    geom = gc2d_geometry(g.n, g.m, mesh)
    R, C = geom["R"], geom["C"]
    part = partition_2d(g, R, C, pad_factor=1.3)
    assert part.nr == geom["nr"]
    # real graphs are skewed: size local buffers from the actual partition
    geom = {**geom, "e_pad": part.e_pad}
    e_pad = geom["e_pad"]

    def pad_edges(a, fill):
        out = np.full((R, C, e_pad), fill, np.int32)
        out[:, :, : a.shape[2]] = a
        return out

    def to_col(x, fill=0.0):
        out = np.full((geom["n_pad"], *x.shape[1:]), fill, x.dtype)
        out[part.perm[: g.n]] = x
        return out

    def to_row(x, fill=0.0):
        out = np.full((geom["n_pad"], *x.shape[1:]), fill, x.dtype)
        out[: g.n] = x
        return out

    batch = {
        # sub-chunk arrays live in NATURAL order: sharded P((row, col)),
        # device (i, j) receives flat chunk i·C + j == natural sub-chunk
        # (i, j).  all_gather over 'model' then rebuilds row block i, and
        # all_gather over 'data' rebuilds column block j in exactly the
        # block-cyclic order of partition_2d.perm — same identity that
        # makes the ITA 2-D reassembly exact (core/distributed.py).
        "nodes_sub": jnp.asarray(to_row(features)),
        "pos_col": jnp.asarray(to_col(pos)),
        "pos_row": jnp.asarray(to_row(pos)),
        "src": jnp.asarray(pad_edges(part.src_local, geom["nc"])),
        "dst": jnp.asarray(pad_edges(part.dst_local, geom["nr"])),
        "targets_sub": jnp.asarray(to_row(labels.astype(np.int32))),
        "tmask_sub": jnp.asarray(to_row(label_mask, fill=False)),
    }
    return geom, batch, part


def build_gc2d_job(mesh: Mesh, *, n: int, m: int, d_feat: int, n_classes: int,
                   **geom_overrides):
    """LoweringJob for the hillclimbed graphcast × ogb_products cell."""
    from ...configs import get_config
    from ...launch.steps import KEY, LoweringJob, _replicated
    from ...train.optimizer import AdamWConfig, adamw_init, adamw_update
    from .graphcast import graphcast_init

    cfg = get_config("graphcast")
    geom = {**gc2d_geometry(n, m, mesh), **geom_overrides}
    params_s = jax.eval_shape(
        lambda k: graphcast_init(k, cfg, d_feat, 4, n_classes), KEY)
    opt_cfg = AdamWConfig()
    opt_s = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_s)
    batch_s = gc2d_input_specs({}, geom, d_feat)

    def train_step(params, opt_state, batch):
        (loss, m_), grads = jax.value_and_grad(
            lambda p: gc2d_loss(p, cfg, geom, mesh, batch), has_aux=True)(params)
        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        return params, opt_state, {"loss": loss, **om}

    row_axis, col_axis = geom["row_axis"], geom["col_axis"]
    sub_axes = ((row_axis, col_axis) if isinstance(row_axis, str)
                else (*row_axis, col_axis))
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    batch_sh = {
        "nodes_sub": ns(sub_axes, None),
        "pos_col": ns(col_axis, None),
        "pos_row": ns(row_axis, None),
        "src": ns(row_axis, col_axis, None),
        "dst": ns(row_axis, col_axis, None),
        "targets_sub": ns(sub_axes),
        "tmask_sub": ns(sub_axes),
    }
    return LoweringJob(
        name="graphcast:ogb_products:ita2d",
        step_fn=train_step,
        args=(params_s, opt_s, batch_s),
        in_shardings=(_replicated(params_s, mesh), _replicated(opt_s, mesh),
                      batch_sh),
        rules=None,
        donate_argnums=(0, 1),
        static_meta=geom,
    )
