"""Production meshes and per-family logical-axis rule sets.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because
the dry-run forces 512 host devices while tests/benches must see 1.

Mesh geometry:
  single-pod : (data=16, model=16)            — 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     — 512 chips

Logical-axis conventions (DESIGN.md §5):
  batch    -> (pod, data)   activations' batch dim; grad all-reduce crosses pods
  fsdp     -> data          parameter/optimizer-state sharding (intra-pod)
  seq      -> model         sequence-parallel residual stream
  heads/ffn/vocab/experts -> model   tensor/expert parallel
  kv_seq   -> model         decode KV for MQA/GQA<model_size
  nodes/edges -> (pod, data) graph partition (dst-block aligned)
"""
from __future__ import annotations


import jax
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from .sharding import AxisRules

__all__ = ["make_production_mesh", "make_smoke_mesh", "lm_axis_rules",
           "gnn_axis_rules", "recsys_axis_rules", "lm_param_rules",
           "recsys_param_rules", "batch_axes"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Tiny mesh for the in-suite distributed tests (8 host devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# activation (logical-axis) rules per family
# ---------------------------------------------------------------------------
def lm_axis_rules(mesh: Mesh, cfg=None, *, decode: bool = False) -> AxisRules:
    model_size = mesh.shape["model"]
    kv_on_heads = (cfg is not None and cfg.n_kv_heads % model_size == 0
                   and cfg.n_kv_heads >= model_size)
    return AxisRules(mesh, {
        "batch": batch_axes(mesh),
        "seq": "model",          # sequence-parallel residuals
        "seq_q": None,
        "embed": None,
        "heads": "model",
        "kv_heads": "model" if kv_on_heads else None,
        "kv_seq": None if kv_on_heads else "model",
        "ffn": "model",
        "vocab": "model",
        "experts": "model",
    })


def gnn_axis_rules(mesh: Mesh, cfg=None) -> AxisRules:
    # Two regimes by hidden width:
    #  * wide (graphcast, d>=256): graph dims on the batch axes, features on
    #    model (TP on the per-edge MLPs) — keeps the h[src] gather at
    #    n_nodes x d/16 per device instead of replicating [n_nodes, d]
    #    (5 GB f32 at graphcast x ogb_products);
    #  * narrow (gin/schnet/mgn, d<256): a 16-wide feature shard of d=64-128
    #    is below GSPMD's useful granularity (it silently drops it on loop
    #    carries) — spend every axis on the graph dims instead.
    d_hidden = getattr(cfg, "d_hidden", 0) if cfg is not None else 0
    if d_hidden >= 256:
        return AxisRules(mesh, {
            "batch": batch_axes(mesh),
            "nodes": batch_axes(mesh),
            "edges": batch_axes(mesh),
            "embed": "model",
        })
    all_axes = tuple(mesh.axis_names)
    return AxisRules(mesh, {
        "batch": all_axes,
        "nodes": all_axes,
        "edges": all_axes,
        "embed": None,
    })


def recsys_axis_rules(mesh: Mesh) -> AxisRules:
    return AxisRules(mesh, {
        "batch": batch_axes(mesh),
        "vocab_rows": "model",
        "embed": None,
    })


# ---------------------------------------------------------------------------
# parameter-sharding rules (path-regex -> PartitionSpec), FSDP="data", TP="model"
# ---------------------------------------------------------------------------
def lm_param_rules(mesh: Mesh) -> list:
    return [
        # attention projections (stacked [L, d, H*dh] / [L, H*dh, d])
        (r"attn/(q|k|v)/w$", P(None, "data", "model")),
        (r"attn/(q|k|v)/b$", P(None, "model")),
        (r"attn/o/w$", P(None, "model", "data")),
        # MoE expert stacks [L, E, d, f]: storage shards on (d, f) — E stays
        # unsharded so any expert count works (granite-moe's 40 doesn't
        # divide the 16-wide model axis); the shard_map EP layer re-lays-out
        # (and pads) E -> model at its boundary per layer.
        (r"ffn/w_(gate|up)$", P(None, None, "data", "model")),
        (r"ffn/w_down$", P(None, None, "model", "data")),
        (r"ffn/router/w$", P(None, "data", None)),
        # dense FFN [L, d, f] / [L, f, d]
        (r"ffn/w_(gate|up)/w$", P(None, "data", "model")),
        (r"ffn/w_down/w$", P(None, "model", "data")),
        # embeddings / head
        (r"embed/w$", P("model", "data")),
        (r"lm_head/w$", P("data", "model")),
        # norms and everything else: replicated
    ]


def recsys_param_rules(mesh: Mesh) -> list:
    return [
        (r"embed/w$", P("model", None)),     # row-sharded table (the model)
        (r"linear/w$", P("model", None)),
        # CIN / MLP dense parts are < 1M params: replicate
    ]


def gnn_param_rules(mesh: Mesh) -> list:
    return []  # all GNN params replicate (≤ tens of M); activations shard
