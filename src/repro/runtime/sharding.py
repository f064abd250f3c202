"""Partition-spec policies per architecture family (DESIGN.md §4).

Mesh axes: single-pod ``('data','model')`` = (16,16); multi-pod
``('pod','data','model')`` = (2,16,16).

* **LM** — 2D FSDP×TP: weight matrices shard their d_model-side over
  ``data`` (ZeRO-3; all-gathered at use, reduce-scattered on grads — XLA
  SPMD inserts the collectives) and their head/ffn-side over ``model``
  (Megatron TP). Across pods params are *replicated* (pure DP): no param
  collective ever crosses the slow pod axis. MoE experts shard over
  ``model`` (EP).
* **GNN** — edge-parallel: edge arrays shard over every mesh axis, node
  state is replicated; ``segment_sum`` lowers to local partial sums +
  all-reduce. (The §Perf pass revisits this with node-sharded aggregation.)
* **RecSys** — vocab-parallel embedding: table rows shard over ``model``;
  lookups mask + psum inside a ``shard_map`` (see ``make_vp_take``);
  everything else is data-parallel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication (vma) checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def dp_axes(mesh: Mesh):
    """Axes carrying the batch (data-parallel) dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def all_axes(mesh: Mesh):
    return tuple(mesh.axis_names)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


# ----------------------------------------------------------------------
# LM family
# ----------------------------------------------------------------------

def lm_param_spec_tree(params_tree, mesh: Mesh):
    """PartitionSpec pytree matching the transformer param layout.

    Stacked layer params carry a leading L axis (never sharded: it is the
    scan dimension).
    """

    def spec_for(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        name = keys[-1]
        in_layer = "layers" in keys
        nd = len(leaf.shape)
        if name == "embed":
            return P(None, "model")
        if name == "head":
            return P(None, "model")
        if name in ("ln_f",):
            return P(None)
        if in_layer:
            if name in ("ln1", "ln2"):
                return P(None, None)
            if name in ("wq", "wk", "wv"):
                return P(None, "data", "model")
            if name == "wo" and nd == 3 and "moe" not in keys and "ffn" not in keys:
                return P(None, "model", "data")
            if name in ("bq", "bk", "bv"):
                return P(None, "model")
            if "ffn" in keys:
                if name in ("wi", "wg"):
                    return P(None, "data", "model")
                if name == "wo":
                    return P(None, "model", "data")
            if "moe" in keys:
                model_size = mesh.shape["model"]
                if name == "router":
                    return P(None, "data", None)
                # EP when the expert count divides the model axis (dbrx:
                # 16 % 16); otherwise shard *inside* each expert (expert-TP,
                # qwen2-moe: 60 experts do not divide 16).
                if name in ("wi", "wg"):                     # (L, E, d, f)
                    if leaf.shape[1] % model_size == 0:
                        return P(None, "model", "data", None)
                    return P(None, None, "data", "model")
                if name == "wo":                              # (L, E, f, d)
                    if leaf.shape[1] % model_size == 0:
                        return P(None, "model", None, "data")
                    return P(None, None, "model", "data")
                if name in ("shared_wi", "shared_wg"):        # (L, S, d, f)
                    return P(None, None, "data", "model")
                if name == "shared_wo":                       # (L, S, f, d)
                    return P(None, None, "model", "data")
        raise ValueError(f"no sharding rule for param path {keys} shape {leaf.shape}")

    return jax.tree_util.tree_map_with_path(spec_for, params_tree)


def lm_opt_spec_tree(param_specs):
    """Adam moments share the param sharding; step is replicated."""
    return {"mu": param_specs, "nu": param_specs, "step": P()}


def lm_batch_specs(mesh: Mesh):
    dp = dp_axes(mesh)
    return {"tokens": P(dp, None), "labels": P(dp, None)}


def lm_cache_spec(mesh: Mesh, n_kv: int):
    dp = dp_axes(mesh)
    # (L, B, T, Hkv, dh): batch over DP; kv heads over model only when they
    # divide the axis (pjit input shardings require exact divisibility) —
    # glm4 (kv=2) / dbrx (kv=8) replicate heads across TP.
    head = "model" if n_kv % mesh.shape["model"] == 0 else None
    spec = P(None, dp, None, head, None)
    return {"k": spec, "v": spec}


# ----------------------------------------------------------------------
# GNN family
# ----------------------------------------------------------------------

_GNN_EDGE_KEYS = ("src", "dst", "edge_feat", "edge_mask")
_GNN_NODE_KEYS = ("node_feat", "pos", "target", "labels", "seed_mask",
                  "graph_id", "force_target")


def gnn_batch_specs(batch_tree, mesh: Mesh):
    ax = all_axes(mesh)

    def spec_for(path, leaf):
        name = path[-1].key
        nd = len(leaf.shape)
        if name in _GNN_EDGE_KEYS:
            return P(ax, *([None] * (nd - 1)))    # edge-parallel over all axes
        if name in _GNN_NODE_KEYS or name == "energy_target":
            return P(*([None] * nd))              # replicated node state
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec_for, batch_tree)


def gnn_param_specs(params_tree):
    return jax.tree.map(lambda _: P(), params_tree)


# ----------------------------------------------------------------------
# RecSys family
# ----------------------------------------------------------------------

def mind_param_specs(params_tree):
    return {"item_embed": P("model", None), "S": P()}


def mind_batch_specs(batch_tree, mesh: Mesh, retrieval: bool = False):
    dp = dp_axes(mesh)

    def spec_for(path, leaf):
        name = path[-1].key
        nd = len(leaf.shape)
        if retrieval and name == "cand_ids":       # (C,) candidate slab
            return P(dp)                           # dp divides 10^6; 'model' serves the table
        if retrieval:                              # (1, H) user history
            return P(*([None] * nd))
        return P(dp, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, batch_tree)


def make_vp_take(mesh: Mesh, table_axis: str = "model", leading=None):
    """Vocab-parallel EmbeddingBag gather: local take + mask + psum.

    Returns ``take_fn(table, ids) -> (*ids.shape, d)`` usable inside jit:
    the table is row-sharded over ``table_axis``; each shard gathers the
    rows it owns and the partial embeddings are psum'd over the axis.
    ``leading`` shards the first id dimension (typically the DP batch);
    remaining id dims are replicated. Rank-generic: specs are derived from
    ``ids.ndim`` at trace time, so one take_fn serves (B,), (B,H), (B,C).
    """

    def local(table_shard, ids):
        vl = table_shard.shape[0]
        lo = jax.lax.axis_index(table_axis) * vl
        loc = ids - lo
        ok = (loc >= 0) & (loc < vl)
        emb = jnp.take(table_shard, jnp.clip(loc, 0, vl - 1), axis=0)
        emb = jnp.where(ok[..., None], emb, 0.0)
        return jax.lax.psum(emb, table_axis)

    def take_fn(table, ids):
        ids_spec = P(leading, *([None] * (ids.ndim - 1)))
        out_spec = P(leading, *([None] * ids.ndim))
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(table_axis, None), ids_spec),
            out_specs=out_spec,
        )(table, ids)

    return take_fn
