"""Kernel-contract negatives — the shapes the ``kernels`` passes must
NOT flag. This file sits inside the strict include roots, so any false
positive here fails CI.

* padding idiom before the grid division (``ceil`` multiple provable)
* index_map as a pure function of the grid indices
* block sizes well under the VMEM budget
* int64 packed-offset math routed through a checked caster
* a complete, provably-int32 device-layout construction site
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK = 256


class PackedOverflowError(OverflowError):
    """Packed offsets left the int32 range."""


def _checked_i32(a):
    a = np.asarray(a)
    if a.size and (a.max() > np.iinfo(np.int32).max
                   or a.min() < np.iinfo(np.int32).min):
        raise PackedOverflowError("packed offsets exceed int32")
    return a.astype(np.int32, copy=False)


def _body(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1


def padded_grid(w):
    """The padding idiom the divisibility rule must prove through."""
    e = w.shape[0]
    ep = int(np.ceil(max(e, 1) / BLOCK)) * BLOCK
    wp = jnp.pad(w, (0, ep - e))
    return pl.pallas_call(
        _body,
        grid=(ep // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,))],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((ep,), jnp.int32),
        interpret=True,
    )(wp)


def packed_slots(k_index, n, u):
    """int64 first, then the checked caster: the sanctioned narrowing."""
    slots = np.asarray(k_index, np.int64) * int(n) + np.asarray(u, np.int64)
    return _checked_i32(slots)


def tiny_layout(n_entries):
    """Every declared array present and constructed int32."""
    z = np.zeros(n_entries, np.int32)
    return {
        "node_u": z, "node_v": z, "node_ct": z,
        "live_from": z, "live_to": z, "row_ptr": z,
        "ent_ts": z, "ent_left": z, "ent_right": z, "ent_parent": z,
        "vrow_ptr": z, "vent_ts": z, "vent_node": z,
        "ver_ts_from": z, "ver_ts_to": z, "ver_ct": z,
        "ver_src": z, "ver_k": z, "knode_ptr": z,
        "live_ptr": z, "live_node": z,
    }
