"""Segmented k-th-smallest selection over CSR segments (DESIGN.md §3).

The construction plane's inner op: for every CSR segment (one vertex's
incident pair slots) select the k-th smallest slot value, with a floor
``lo`` so the caller gets ``max(lo, kth)`` directly (the clamped fixpoint
update of ``core_time``). Values live in a small integer domain
``[0, inf_value]``, which admits a *counting bisection* formulation: the
k-th smallest is the least ``x`` with ``|{i in seg : w_i <= x}| >= k``.
Each bisection step needs only a segmented count — no sort, no scatter.

Three interchangeable backends:

* ``count_le_csr`` / ``kth_smallest_csr`` — jnp, used inside the jitted
  construction sweep (`core_time._sweep_jax`). Segments are contiguous, so
  the count is a cumsum + two gathers; XLA lowers this without scatter
  (whose CPU lowering is serial) and without sort.
* ``segmented_count_le`` — Pallas kernel. The TPU-native formulation turns
  the segmented count into a one-hot compare + row reduction over
  (slot_block x segment_block) tiles, exactly like ``kcore_peel``'s degree
  histogram: dense VPU work, no atomics, deterministic accumulation over
  the slot-block grid dimension. ``kth_smallest_pallas`` runs the same
  bisection with the Pallas counter as the inner op.
* ``segmented_kth_smallest_np`` — numpy packed-sort reference (tests and
  the host construction engine share this formulation).

All three are asserted equal in ``tests/test_kernels.py``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode
from .contracts import ANY_INT, ArraySpec, kernel_contract

#: 1-D blocks match XLA's T(1024) tiling of long int32 vectors on a TPU;
#: a 512 block is refused by Mosaic ("XLA layout ... does not match")
DEFAULT_SLOT_BLOCK = 1024
DEFAULT_SEG_BLOCK = 1024


# ----------------------------------------------------------------------
# jnp (XLA) path — contiguous-CSR counting, used by the jitted sweep
# ----------------------------------------------------------------------

def count_le_csr(w: jnp.ndarray, thr: jnp.ndarray, seg: jnp.ndarray,
                 vptr: jnp.ndarray) -> jnp.ndarray:
    """int32[n] per-segment count of ``w[i] <= thr[seg[i]]``.

    ``seg`` must be non-decreasing with segments delimited by ``vptr``
    (CSR); the count is then a cumsum + boundary gathers, which XLA CPU
    handles far better than scatter-based ``segment_sum``.
    """
    x = (w <= thr[seg]).astype(jnp.int32)
    s = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(x)])
    return s[vptr[1:]] - s[vptr[:-1]]


def kth_smallest_csr(w: jnp.ndarray, lo: jnp.ndarray, k: int, inf_value: int,
                     steps: int, seg: jnp.ndarray, vptr: jnp.ndarray,
                     count_fn=count_le_csr) -> jnp.ndarray:
    """Per-segment ``max(lo, k-th smallest of w)`` clamped to ``inf_value``.

    Counting bisection over ``[lo, inf_value]``: invariantly the answer is
    in ``[lo, hi]``; ``steps`` must be >= ceil(log2(inf_value + 1)).
    Segments whose k-th smallest is below ``lo`` resolve to ``lo``; segments
    with fewer than k qualifying slots resolve to ``inf_value``.
    """
    hi = jnp.full_like(lo, inf_value)

    def bstep(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        ge = count_fn(w, mid, seg, vptr) >= k
        new_lo = jnp.where(ge | (lo >= hi), lo, mid + 1)
        new_hi = jnp.where(ge & (lo < hi), mid, hi)
        return new_lo, new_hi

    lo, _ = jax.lax.fori_loop(0, steps, bstep, (lo, hi))
    return jnp.minimum(lo, inf_value)


# ----------------------------------------------------------------------
# Pallas path — one-hot tile histogram (kcore_peel idiom)
# ----------------------------------------------------------------------

def _count_le_kernel(seg_ref, w_ref, thr_ref, out_ref):
    sb = pl.program_id(0)                      # slot-block index (accumulated)
    gb = pl.program_id(1)                      # segment-block index
    base = gb * out_ref.shape[0]
    seg = seg_ref[...]
    w = w_ref[...]
    thr = thr_ref[...]                         # this segment block's thresholds
    gids = base + jax.lax.broadcasted_iota(
        jnp.int32, (seg.shape[0], out_ref.shape[0]), 1)
    hit = (seg[:, None] == gids) & (w[:, None] <= thr[None, :])
    part = jnp.sum(hit.astype(jnp.int32), axis=0)

    @pl.when(sb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part


@kernel_contract(
    in_specs={
        "w": ArraySpec(("E",), ANY_INT),
        "seg": ArraySpec(("E",), ANY_INT),
        "thr": ArraySpec(("n",), ANY_INT),
    },
    out_specs=ArraySpec(("n",), ("int32",)),
    # per step: two slot blocks (seg, w) + threshold block + out block, i32
    vmem_bound=lambda a: 4 * (2 * a["slot_block"] + 2 * a["seg_block"]),
)
def segmented_count_le(w, seg, thr, n: int, *,
                       slot_block: int = DEFAULT_SLOT_BLOCK,
                       seg_block: int = DEFAULT_SEG_BLOCK,
                       interpret: bool | None = None) -> jnp.ndarray:
    """int32[n] Pallas counterpart of :func:`count_le_csr` (``seg`` need not
    be sorted here — the histogram never assumes contiguity)."""
    e = w.shape[0]
    ep = int(np.ceil(max(e, 1) / slot_block)) * slot_block
    npad = int(np.ceil(max(n, 1) / seg_block)) * seg_block
    seg_p = jnp.pad(seg.astype(jnp.int32), (0, ep - e), constant_values=-1)
    w_p = jnp.pad(w.astype(jnp.int32), (0, ep - e))
    thr_p = jnp.pad(thr.astype(jnp.int32), (0, npad - n))
    out = pl.pallas_call(
        _count_le_kernel,
        grid=(ep // slot_block, npad // seg_block),
        in_specs=[
            pl.BlockSpec((slot_block,), lambda s, g: (s,)),
            pl.BlockSpec((slot_block,), lambda s, g: (s,)),
            pl.BlockSpec((seg_block,), lambda s, g: (g,)),
        ],
        out_specs=pl.BlockSpec((seg_block,), lambda s, g: (g,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.int32),
        interpret=interpret_mode(interpret),
    )(seg_p, w_p, thr_p)
    return out[:n]


@kernel_contract(
    in_specs={
        "w": ArraySpec(("E",), ANY_INT),
        "seg": ArraySpec(("E",), ANY_INT),
        "lo": ArraySpec(("n",), ANY_INT),
    },
    out_specs=ArraySpec(("n",), ("int32",)),
    # the inner segmented_count_le carries the per-step VMEM bound
)
def kth_smallest_pallas(w, seg, n: int, k: int, inf_value: int, *,
                        lo=None, interpret: bool | None = None) -> jnp.ndarray:
    """Per-segment clamped k-th smallest with the Pallas counter as the
    bisection inner op. Host-driven bisection loop (one kernel per step)."""
    lo = jnp.zeros(n, jnp.int32) if lo is None else lo.astype(jnp.int32)
    hi = jnp.full(n, inf_value, jnp.int32)
    steps = int(np.ceil(np.log2(inf_value + 1))) + 1 if inf_value > 0 else 1
    for _ in range(steps):
        mid = (lo + hi) // 2
        ge = segmented_count_le(w, seg, mid, n, interpret=interpret) >= k
        lo = jnp.where(ge | (lo >= hi), lo, mid + 1)
        hi = jnp.where(ge & (lo < hi), mid, hi)
    return jnp.minimum(lo, inf_value)


# ----------------------------------------------------------------------
# numpy reference
# ----------------------------------------------------------------------

def segmented_kth_smallest_np(w: np.ndarray, vptr: np.ndarray, k: int,
                              inf_value: int,
                              lo: np.ndarray | None = None) -> np.ndarray:
    """Reference: per-segment ``max(lo, k-th smallest)`` clamped to
    ``inf_value`` (segments are ``w[vptr[i]:vptr[i+1]]``)."""
    n = vptr.shape[0] - 1
    out = np.full(n, inf_value, np.int64)
    for v in range(n):
        segv = np.sort(w[vptr[v]:vptr[v + 1]])
        if segv.shape[0] >= k:
            out[v] = min(int(segv[k - 1]), inf_value)
    if lo is not None:
        out = np.maximum(out, lo)
    return np.minimum(out, inf_value)
