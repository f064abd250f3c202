"""Pallas kernels: blocked MXU matmul and segment-sum as one-hot GEMM.

``matmul`` — the classic tiled GEMM: grid (M/bm, N/bn, K/bk), A/B tiles in
VMEM, f32 accumulation in the revisited output tile (MXU shapes: tiles are
multiples of 128).

``segment_sum`` — the GNN/EmbeddingBag scatter-reduce, TPU-style: instead of
atomics, each edge block builds the one-hot matrix of its segment ids
against the current segment block and contracts it with the value rows on
the MXU:

    out[s, :] += sum_i [ids_i == s] * vals[i, :]    (bs x bm @ bm x d)

Grid (m/bm, S/bs); the output tile is revisited across edge blocks.
This is the fused gather->GEMM->scatter pattern of GE-SpMM/FusedMM mapped
onto the systolic array (kernel_taxonomy §GNN).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import interpret_mode
from .contracts import ANY_FLOAT, ANY_INT, ArraySpec, kernel_contract


def _matmul_kernel(a_ref, b_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)


@kernel_contract(
    in_specs={
        "a": ArraySpec(("M", "K"), ANY_FLOAT),
        "b": ArraySpec(("K", "N"), ANY_FLOAT),
    },
    out_specs=ArraySpec(("M", "N"), ("float32",)),
    # per step: A tile + B tile + f32 accumulator tile
    vmem_bound=lambda v: 4 * (v["bm"] * v["bk"] + v["bk"] * v["bn"]
                              + v["bm"] * v["bn"]),
)
def matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
           interpret: bool | None = None):
    """f32[M, N] = a @ b with (bm, bn, bk) VMEM tiles; pads to multiples."""
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"inner dims disagree: a is (?, {K}), b is ({K2}, ?)")
    Mp, Kp, Np = (int(np.ceil(M / bm)) * bm, int(np.ceil(K / bk)) * bk,
                  int(np.ceil(N / bn)) * bn)
    a_p = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    b_p = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        interpret=interpret_mode(interpret),
    )(a_p, b_p)
    return out[:M, :N]


def _segsum_kernel(ids_ref, vals_ref, out_ref):
    eb = pl.program_id(0)
    sb = pl.program_id(1)
    bs = out_ref.shape[0]
    base = sb * bs
    ids = ids_ref[...]
    vals = vals_ref[...]
    seg = base + jax.lax.broadcasted_iota(jnp.int32, (bs, ids.shape[0]), 0)
    onehot = (seg == ids[None, :]).astype(vals.dtype)       # (bs, bm)

    @pl.when(eb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(onehot, vals, preferred_element_type=jnp.float32)


@kernel_contract(
    in_specs={
        "vals": ArraySpec(("E", "D"), ANY_FLOAT),
        "ids": ArraySpec(("E",), ANY_INT),
    },
    out_specs=ArraySpec(("num_segments", "D"), ("float32",)),
    # per step: id block + value rows + f32 output tile (d = row width)
    vmem_bound=lambda a: 4 * (a["bm"] + (a["bm"] + a["bs"])
                              * a["vals"].shape[1]),
)
def segment_sum(vals, ids, num_segments: int, *, bm: int = 512, bs: int = 256,
                interpret: bool | None = None):
    """f32[num_segments, d] scatter-add of rows by id, via one-hot GEMM."""
    m, d = vals.shape
    mp = int(np.ceil(max(m, 1) / bm)) * bm
    sp = int(np.ceil(max(num_segments, 1) / bs)) * bs
    vals_p = jnp.pad(vals.astype(jnp.float32), ((0, mp - m), (0, 0)))
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, mp - m), constant_values=-1)
    out = pl.pallas_call(
        _segsum_kernel,
        grid=(mp // bm, sp // bs),
        in_specs=[
            pl.BlockSpec((bm,), lambda e, s: (e,)),
            pl.BlockSpec((bm, d), lambda e, s: (e, 0)),
        ],
        out_specs=pl.BlockSpec((bs, d), lambda e, s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, d), jnp.float32),
        interpret=interpret_mode(interpret),
    )(ids_p, vals_p)
    return out[:num_segments]


def embedding_bag(table, ids, weights=None):
    """(bags, k) -> (bags, d): gather + weighted within-bag sum.

    The gather stays an XLA gather (TPUs do this well); the bag reduction is
    a tiny einsum. Provided for API parity with the torch EmbeddingBag and
    reused by the recsys path; the heavy lifting for *scatter* bags goes
    through :func:`segment_sum`.
    """
    emb = jnp.take(table, ids, axis=0)
    if weights is not None:
        emb = emb * weights[..., None]
    return emb.sum(axis=1)
