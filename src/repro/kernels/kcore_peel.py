"""Pallas kernel: fused degree-count + threshold for one k-core peel round.

TPU adaptation of the peeling inner loop (DESIGN.md §3). Scatter-add is the
CPU idiom; the TPU-native formulation turns the degree histogram into a
*one-hot compare + row reduction* over (edge-block x vertex-block) tiles —
dense VPU work with an MXU-shaped inner product, no atomics, deterministic.

Grid: (n_edge_blocks, n_vertex_blocks). Each step loads an edge block
(src, dst, alive int32) and accumulates the partial histogram of its
endpoints against the vertex-id range of the current vertex block:

    part[j] = sum_i alive[i] * ([src_i == base+j] + [dst_i == base+j])

The output block (per vertex-block) is revisited across edge blocks
(accumulation across the first grid dim), initialized at edge-block 0.
A second tiny kernel applies the k-threshold + edge mask update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import interpret_mode
from .contracts import ANY_INT, ArraySpec, INT_OR_BOOL, kernel_contract

DEFAULT_EDGE_BLOCK = 1024
DEFAULT_VERT_BLOCK = 512


def _degree_kernel(src_ref, dst_ref, alive_ref, out_ref):
    eb = pl.program_id(0)
    vb = pl.program_id(1)
    base = vb * out_ref.shape[0]
    src = src_ref[...]
    dst = dst_ref[...]
    alive = alive_ref[...]
    vids = base + jax.lax.broadcasted_iota(jnp.int32, (src.shape[0], out_ref.shape[0]), 1)
    hit = (src[:, None] == vids).astype(jnp.int32) + (dst[:, None] == vids).astype(jnp.int32)
    part = jnp.sum(hit * alive[:, None], axis=0)

    @pl.when(eb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part


@kernel_contract(
    in_specs={
        "src": ArraySpec(("E",), ANY_INT),
        "dst": ArraySpec(("E",), ANY_INT),
        "alive": ArraySpec(("E",), INT_OR_BOOL),
    },
    out_specs=ArraySpec(("n",), ("int32",)),
    # per step: three edge blocks + the vertex-block output, i32
    vmem_bound=lambda a: 4 * (3 * a["edge_block"] + a["vert_block"]),
)
def degree_count(src, dst, alive, n: int, *,
                 edge_block: int = DEFAULT_EDGE_BLOCK,
                 vert_block: int = DEFAULT_VERT_BLOCK,
                 interpret: bool | None = None) -> jnp.ndarray:
    """int32[n] alive-edge degrees. Pads edges/vertices to block multiples."""
    m = src.shape[0]
    mp = int(np.ceil(max(m, 1) / edge_block)) * edge_block
    np_ = int(np.ceil(max(n, 1) / vert_block)) * vert_block
    pad_e = mp - m
    src_p = jnp.pad(src.astype(jnp.int32), (0, pad_e), constant_values=-1)
    dst_p = jnp.pad(dst.astype(jnp.int32), (0, pad_e), constant_values=-1)
    alive_p = jnp.pad(alive.astype(jnp.int32), (0, pad_e))
    grid = (mp // edge_block, np_ // vert_block)
    out = pl.pallas_call(
        _degree_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((edge_block,), lambda e, v: (e,)),
            pl.BlockSpec((edge_block,), lambda e, v: (e,)),
            pl.BlockSpec((edge_block,), lambda e, v: (e,)),
        ],
        out_specs=pl.BlockSpec((vert_block,), lambda e, v: (v,)),
        out_shape=jax.ShapeDtypeStruct((np_,), jnp.int32),
        interpret=interpret_mode(interpret),
    )(src_p, dst_p, alive_p)
    return out[:n]


def _threshold_kernel(src_ref, dst_ref, alive_ref, deg_ref, k_ref, out_ref):
    src = src_ref[...]
    dst = dst_ref[...]
    k = k_ref[0]
    deg = deg_ref[...]        # full degree vector in VMEM
    ok_s = deg[src] >= k
    ok_d = deg[dst] >= k
    out_ref[...] = (alive_ref[...] > 0) & ok_s & ok_d


def _peel_vmem(a: dict) -> int:
    # the threshold kernel holds the WHOLE padded degree vector in VMEM
    # (deg.shape BlockSpec) — the dominant term for large n
    n_pad = (int(np.ceil(max(a["n"], 1) / DEFAULT_VERT_BLOCK))
             * DEFAULT_VERT_BLOCK)
    return 4 * (3 * a["edge_block"] + n_pad + 1) + a["edge_block"]


@kernel_contract(
    in_specs={
        "src": ArraySpec(("E",), ANY_INT),
        "dst": ArraySpec(("E",), ANY_INT),
        "alive": ArraySpec(("E",), INT_OR_BOOL),
    },
    out_specs=ArraySpec(("E",), ("bool",)),
    vmem_bound=_peel_vmem,
)
def peel_round(src, dst, alive, n: int, k: int, *,
               edge_block: int = DEFAULT_EDGE_BLOCK,
               interpret: bool | None = None):
    """One fused peel round; returns the new alive mask (bool[m])."""
    deg = degree_count(src, dst, alive, n, interpret=interpret)
    m = src.shape[0]
    mp = int(np.ceil(max(m, 1) / edge_block)) * edge_block
    pad_e = mp - m
    src_p = jnp.pad(src.astype(jnp.int32), (0, pad_e))
    dst_p = jnp.pad(dst.astype(jnp.int32), (0, pad_e))
    alive_p = jnp.pad(alive.astype(jnp.int32), (0, pad_e))
    out = pl.pallas_call(
        _threshold_kernel,
        grid=(mp // edge_block,),
        in_specs=[
            pl.BlockSpec((edge_block,), lambda e: (e,)),
            pl.BlockSpec((edge_block,), lambda e: (e,)),
            pl.BlockSpec((edge_block,), lambda e: (e,)),
            pl.BlockSpec(deg.shape, lambda e: (0,)),      # whole degree vector
            pl.BlockSpec((1,), lambda e: (0,)),
        ],
        out_specs=pl.BlockSpec((edge_block,), lambda e: (e,)),
        out_shape=jax.ShapeDtypeStruct((mp,), jnp.bool_),
        interpret=interpret_mode(interpret),
    )(src_p, dst_p, alive_p, deg, jnp.array([k], jnp.int32))
    return out[:m]
