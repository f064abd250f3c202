"""Public jit'd wrappers: Pallas kernel <-> pure-jnp reference dispatch.

Each kernel's interpret mode follows the backend
(:func:`repro.kernels.interpret_mode`): on the CPU the Pallas interpreter
executes the kernel body op-for-op, on a TPU the same ``pl.pallas_call``
lowers to Mosaic. ``use_pallas(False)`` routes everything through the jnp
references (the default inside big jitted training graphs, where XLA
fusion is already the right tool and kernel dispatch would only fragment
it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import kcore_peel as _kp
from . import label_prop as _lp
from . import ref
from . import segment_matmul as _sm

_USE_PALLAS = True


def use_pallas(flag: bool):
    global _USE_PALLAS
    _USE_PALLAS = flag


def degree_count(src, dst, alive, n: int):
    if _USE_PALLAS:
        return _kp.degree_count(src, dst, alive, n)
    return ref.degree_count(src, dst, alive, n)


def kcore_peel_round(src, dst, alive, n: int, k: int):
    if _USE_PALLAS:
        new_alive = _kp.peel_round(src, dst, alive, n, k)
        return new_alive, jnp.any(new_alive != alive)
    return ref.kcore_peel_round(src, dst, alive, n, k)


def kcore_fixpoint(src, dst, n: int, k: int):
    """Device-side k-core edge mask (used by serving/benches)."""
    return ref.kcore_fixpoint(src, dst, n, k)


def label_prop_round(labels, link_l, link_r, link_p, active):
    if _USE_PALLAS:
        return _lp.label_prop_round(labels, link_l, link_r, link_p, active)
    return ref.label_prop_round(labels, link_l, link_r, link_p, active)


def matmul(a, b):
    if _USE_PALLAS:
        return _sm.matmul(a, b)
    return ref.matmul(a, b)


def segment_sum(vals, ids, num_segments: int):
    if _USE_PALLAS:
        return _sm.segment_sum(vals, ids, num_segments)
    return ref.segment_sum_sorted(vals, ids, num_segments)


def embedding_bag(table, ids, weights=None):
    return _sm.embedding_bag(table, ids, weights)


def flash_attention(q, k, v, *, causal: bool = False):
    if _USE_PALLAS:
        return _fa.flash_attention(q, k, v, causal=causal)
    return ref.flash_attention(q, k, v, causal=causal)
