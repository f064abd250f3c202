"""Pallas kernel: one masked min-label propagation round (batched TCCS).

One round over the (B, N) query-x-forest-node matrix:

    label[b, x] <- min(label[b, x], label[b, l(x)], label[b, r(x)],
                       label[b, p(x)])          (links masked per query)
    label[b, x] <- min(label[b, x], label[b, label[b, x]])   (pointer jump)

The serving path does not call this kernel: ``core/batch_query.py`` jumps
along parent links only. The binary child bound from the paper is what
fixes the neighbour count at 3, making the round a constant number of
VMEM gathers.

Tiling: grid (B/8,). Each step holds eight queries' full label, activity
and link rows — Mosaic blocks rows in eights, and its gather
(``take_along_axis``) needs the index block to have the gathered block's
shape, so a step cannot gather from a full row with a column block of
links. Activity rides as int32 because Mosaic gathers no booleans.
Mosaic (jax 0.9.0) also gathers within one (8, 128) vreg only, so on a
TPU the kernel compiles for padded rows of at most 128 nodes; wider rows
are refused with "Multiple source vregs along gather dimension".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import interpret_mode
from .contracts import ANY_INT, ArraySpec, INT_OR_BOOL, kernel_contract


def _label_prop_kernel(labels_ref, active_ref, l_ref, r_ref, p_ref,
                       out_ref):
    row = labels_ref[...]              # (8, Np) label rows
    act_i = active_ref[...]            # (8, Np) int32 activity
    act = act_i != 0
    N = row.shape[1]

    def nb(link):
        ok = (link >= 0) & act
        linkc = jnp.clip(link, 0, N - 1)
        lab = jnp.take_along_axis(row, linkc, axis=1)
        a = jnp.take_along_axis(act_i, linkc, axis=1) != 0
        return jnp.where(ok & a, lab, N)

    new = jnp.minimum(row, jnp.minimum(nb(l_ref[...]),
                                       jnp.minimum(nb(r_ref[...]),
                                                   nb(p_ref[...]))))
    # the jump reads the pre-round labels, like ref.label_prop_round
    jc = jnp.clip(new, 0, N - 1)
    jumped = jnp.where(new < N, jnp.take_along_axis(row, jc, axis=1), new)
    out_ref[...] = jnp.minimum(new, jumped)


#: query rows per grid step (Mosaic's sublane tile)
ROW_BLOCK = 8


def _label_prop_vmem(a: dict) -> int:
    # per step: six (8, n_pad) int32 blocks (labels, activity, three
    # link rows, output)
    bn = a["bn"]
    n_pad = int(np.ceil(max(a["labels"].shape[1], 1) / bn)) * bn
    return 4 * 6 * ROW_BLOCK * n_pad


@kernel_contract(
    in_specs={
        "labels": ArraySpec(("B", "N"), ANY_INT),
        "link_l": ArraySpec(("B", "N"), ANY_INT),
        "link_r": ArraySpec(("B", "N"), ANY_INT),
        "link_p": ArraySpec(("B", "N"), ANY_INT),
        "active": ArraySpec(("B", "N"), INT_OR_BOOL),
    },
    out_specs=ArraySpec(("B", "N"), ("int32",)),
    vmem_bound=_label_prop_vmem,
)
def label_prop_round(labels, link_l, link_r, link_p, active, *,
                     bn: int = 128, interpret: bool | None = None):
    """One (B, N) propagation + jump round. Matches ref.label_prop_round.
    Rows pad to a multiple of ``bn`` nodes and of 8 queries."""
    B, N = labels.shape
    Bp = int(np.ceil(max(B, 1) / ROW_BLOCK)) * ROW_BLOCK
    Np = int(np.ceil(max(N, 1) / bn)) * bn
    pad2 = lambda a, fill: jnp.pad(a, ((0, Bp - B), (0, Np - N)),
                                   constant_values=fill)
    labels_p = pad2(labels.astype(jnp.int32), N)
    act_p = pad2(active.astype(jnp.int32), 0)
    l_p = pad2(link_l.astype(jnp.int32), -1)
    r_p = pad2(link_r.astype(jnp.int32), -1)
    p_p = pad2(link_p.astype(jnp.int32), -1)
    rows = pl.BlockSpec((ROW_BLOCK, Np), lambda b: (b, 0))
    out = pl.pallas_call(
        _label_prop_kernel,
        grid=(Bp // ROW_BLOCK,),
        in_specs=[rows] * 5,
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.int32),
        interpret=interpret_mode(interpret),
    )(labels_p, act_p, l_p, r_p, p_p)
    return out[:B, :N]
