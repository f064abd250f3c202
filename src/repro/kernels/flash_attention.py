"""Pallas kernel: blockwise online-softmax attention (FlashAttention-style).

Grid (batch*heads, q_blocks, kv_blocks); the kv dimension is the innermost
(fastest-varying) grid axis, so the output tile and the running max / sum
statistics are revisited and carried across kv steps in VMEM:

    m_new = max(m, rowmax(S));  alpha = exp(m - m_new)
    l     = alpha * l + rowsum(exp(S - m_new))
    acc   = alpha * acc + exp(S - m_new) @ V

The unnormalized accumulator is divided by l at the final kv step. Causal
masking skips whole kv blocks above the diagonal (`pl.when` guard) and
applies the triangular mask inside the diagonal block; kv padding past the
true sequence length is always masked.

Running stats are *revisited outputs* (block constant along the kv axis)
rather than scratch, for interpret-mode portability. VMEM per step: q tile
(bq, dh) + k/v tiles (bk, dh) + stats — MXU-aligned for 128-multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import interpret_mode
from .contracts import ANY_FLOAT, ArraySpec, kernel_contract

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, causal: bool, scale: float, blocks_kv: int, t_real: int):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    if causal:
        run = kv_idx * bk <= (q_idx + 1) * bq - 1   # below/at the diagonal
    else:
        run = kv_idx * bk < t_real                  # any real keys in block

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < t_real
        if causal:
            qpos = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[0] = alpha * l_ref[0] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[0] = alpha * acc_ref[0] + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(kv_idx == blocks_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[0] / jnp.maximum(l_ref[0], 1e-30)).astype(o_ref.dtype)


def _flash_vmem(a: dict) -> int:
    # per step (upper bound with the declared bq/bk — the wrapper may
    # shrink them for short sequences): q tile + k/v tiles + o/acc tiles
    # + (bq, 1) running stats, f32 bound per element
    dh = a["q"].shape[3]
    return 4 * (3 * a["bq"] * dh + 2 * a["bk"] * dh + 2 * a["bq"])


@kernel_contract(
    in_specs={
        "q": ArraySpec(("B", "S", "H", "dh"), ANY_FLOAT),
        "k": ArraySpec(("B", "T", "H", "dh"), ANY_FLOAT),
        "v": ArraySpec(("B", "T", "H", "dh"), ANY_FLOAT),
    },
    out_specs=ArraySpec(("B", "S", "H", "dh"), ANY_FLOAT),
    vmem_bound=_flash_vmem,
)
def flash_attention(q, k, v, *, causal: bool = False, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """(B, S, H, dh) attention with KV (B, T, H, dh); H == kv-head count
    (expand GQA before calling). Returns (B, S, H, dh) in q.dtype."""
    B, S, H, dh = q.shape
    T = k.shape[1]
    bq = min(bq, int(np.ceil(S / 8)) * 8 if S < bq else bq)
    bk = min(bk, int(np.ceil(T / 8)) * 8 if T < bk else bk)
    Sp = int(np.ceil(S / bq)) * bq
    Tp = int(np.ceil(T / bk)) * bk
    qf = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    qf = qf.transpose(0, 2, 1, 3).reshape(B * H, Sp, dh)
    kf = kf.transpose(0, 2, 1, 3).reshape(B * H, Tp, dh)
    vf = vf.transpose(0, 2, 1, 3).reshape(B * H, Tp, dh)
    blocks_kv = Tp // bk
    kernel = functools.partial(_flash_kernel, causal=causal,
                               scale=1.0 / float(np.sqrt(dh)),
                               blocks_kv=blocks_kv, t_real=T)
    # bq/bk shrink via min() and dh is a model dim (<=256); the static
    # worst case (2048^2 tiles) is unreachable, and the armed witness
    # checks the real-tree bound at call time
    # repro: ignore[pallas-vmem-budget]
    outs = pl.pallas_call(
        kernel,
        grid=(B * H, Sp // bq, blocks_kv),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sp, dh), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qf, kf, vf)
    o = outs[0].reshape(B, H, Sp, dh).transpose(0, 2, 1, 3)
    return o[:, :S]
