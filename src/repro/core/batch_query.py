"""Batched TCCS query engine (device plane; beyond-paper, DESIGN.md §3, §8).

Algorithm 1 answers one query in tens of microseconds on a CPU by chasing
pointers. A TPU should instead answer *thousands of queries per launch*.
This module evaluates a whole batch ``(u_b, ts_b, te_b)`` at once against the
packed PECB arrays:

1. **Entry points** — the paper's per-vertex lookup (Alg 1 line 3) becomes a
   vectorized lower-bound binary search over the per-vertex version CSR.
2. **Link resolution** — the paper's per-node binary search (Alg 1 line 10)
   becomes a ``(B, M)`` vectorized lower-bound over the per-node entry CSR:
   for every query b and forest node x of its live list we resolve x's parent
   at ``ts_b`` in ``O(log t̄)`` steps, all queries and nodes in parallel.
3. **Traversal** — BFS becomes pointer jumping. The ts-forest's parent and
   child links agree, so the active nodes of one component form one subtree
   of it, and every node of that subtree reaches the subtree's top node by
   following active parent links. Each node starts at its active parent
   (or itself) and jumps ``top <- top[top]`` until nothing changes: one
   gather per round, O(log depth) rounds, the fixpoint detected by a
   ``lax.while_loop``. Every program returns the launch's round count as
   one more ``int32`` scalar output, downloaded with the masks.
   Steps 2 and 3 run over each query's **live list**, not over every
   node of the mirror: at one start time ts the nodes of stratum k whose
   lifetime ``[live_from, live_to]`` holds ts form a spanning forest of
   its vertices, and every other node is a self-loop that can never be a
   member. The layout keeps these lists as one CSR by (stratum, start
   time) (``live_ptr``/``live_node``), so row b reads the
   ``M = max_live_nodes`` ids of row ``(slot_b // n, ts_b)``, masks the
   lanes past the row's count, maps parent links to list positions and
   pointer-jumps over list positions. The work per row is ``(B, M)``,
   with M the longest list: under n, where a stratum window holds tens
   of thousands of nodes.

Node activity uses the forest-membership lifetimes recorded by the
builder: a node participates for query b iff
``live_from <= ts_b <= live_to`` (it is on b's live list) and
``ct <= te_b``. This is what makes the stale entries of expired nodes
harmless here (the host DFS never reaches them; the data-parallel
propagation must leave them out explicitly).

Query API v2 additions (DESIGN.md §8):

* :func:`batch_query_full` — besides the vertex mask, derives **edge
  membership** on device: the converged top nodes give forest-node
  membership (``top[b, x] == top[b, entry_b]``, the masked gather inside
  :func:`_component_masks` that already produces the vertex mask), and a
  *core-time version* j is then a member iff its record covers ``ts_b``,
  ``ct_j <= te_b`` and the vertex mask is set at its ``src`` endpoint (one
  gather over the version arrays, :func:`_version_member`). The resulting
  ``(B, V)`` mask is exact against the brute-force induced-edge oracle —
  it feeds the EDGES/SUBGRAPH result modes without any host-side graph
  traversal.
* :func:`window_sweep` — the same vertex over W sliding windows in ONE
  launch (the contact-tracing trajectory query). The per-vertex entry
  segment ``[vrow_ptr[u], vrow_ptr[u+1])`` is resolved once and shared by
  all windows; everything downstream reuses the batched propagation core
  with B = W.

Output equality with Algorithm 1 (and, for edge modes, with
``kcore.tccs_oracle_edges``) is asserted in tests for random graphs and
random query batches.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import contracts as kernel_contracts
from .pecb_index import PECBIndex, StratifiedPECB

NONE = -1

_I32_MIN = np.iinfo(np.int32).min
_I32_MAX = np.iinfo(np.int32).max


class LayoutOverflowError(OverflowError):
    """A device-layout value does not fit int32.

    The packed layout keeps every array int32 on device (half the
    transfer and VMEM footprint of int64), which is only sound while the
    global id/offset space — the stratified ``K*n+1`` row-pointer rows,
    the fused entry offsets, the ``k_index*n + u`` query slots — stays
    below 2**31. The layout builders compute in int64 and narrow through
    :func:`_i32`, which raises this at *build* time instead of letting
    the device index silently wrap."""


def _i32(a, what: str = "array") -> np.ndarray:
    """Checked int32 narrowing for layout arrays (the dtype-flow pass
    treats calls to this as guarded; a raw ``np.asarray(x, np.int32)`` of
    packed-extent arithmetic is a finding)."""
    arr = np.asarray(a)
    if arr.size:
        mx, mn = int(arr.max()), int(arr.min())
        if mx > _I32_MAX or mn < _I32_MIN:
            raise LayoutOverflowError(
                f"{what}: value range [{mn}, {mx}] exceeds int32; the "
                "packed device layout cannot address this index — shard "
                "the workload or shrink the stratum set")
    return arr.astype(np.int32, copy=False)


@dataclasses.dataclass(frozen=True)
class DeviceIndex:
    """PECB arrays on device + static metadata (hashable for jit)."""

    n: int
    t_max: int
    node_u: jnp.ndarray
    node_v: jnp.ndarray
    node_ct: jnp.ndarray
    live_from: jnp.ndarray
    live_to: jnp.ndarray
    row_ptr: jnp.ndarray
    ent_ts: jnp.ndarray
    ent_left: jnp.ndarray
    ent_right: jnp.ndarray
    ent_parent: jnp.ndarray
    vrow_ptr: jnp.ndarray
    vent_ts: jnp.ndarray
    vent_node: jnp.ndarray
    # core-time version arrays (query API v2: EDGES/SUBGRAPH modes).
    # Padded to length >= 1 with inert records (ts_from=1, ts_to=0).
    ver_ts_from: jnp.ndarray
    ver_ts_to: jnp.ndarray
    ver_ct: jnp.ndarray
    ver_src: jnp.ndarray
    ver_k: jnp.ndarray        # per-version stratum k (constant per-k mirror)
    # int32[|K|+1]: first node of each stratum ([0, N] on a per-k mirror)
    knode_ptr: jnp.ndarray
    # live-node CSR (:func:`_live_csr`): row ki*(t_max+2)+t lists stratum
    # ki's nodes live at start time t, ascending global ids
    live_ptr: jnp.ndarray     # int32[|K|*(t_max+2)+1]
    live_node: jnp.ndarray    # int32[L], padded to length >= 1
    max_node_entries: int     # static: longest per-node entry list
    max_vert_entries: int     # static: longest per-vertex entry list
    num_versions: int         # static: true version count (pre-padding)
    max_stratum_nodes: int    # static: widest stratum W
    max_live_nodes: int       # static: longest live list M, at least 1

    @property
    def num_nodes(self) -> int:
        return int(self.node_u.shape[0])


_ARRAY_FIELDS = (
    "node_u", "node_v", "node_ct", "live_from", "live_to",
    "row_ptr", "ent_ts", "ent_left", "ent_right", "ent_parent",
    "vrow_ptr", "vent_ts", "vent_node",
    "ver_ts_from", "ver_ts_to", "ver_ct", "ver_src", "ver_k", "knode_ptr",
    "live_ptr", "live_node",
)
_META_FIELDS = ("n", "t_max", "max_node_entries", "max_vert_entries",
                "num_versions", "max_stratum_nodes", "max_live_nodes")

jax.tree_util.register_pytree_node(
    DeviceIndex,
    lambda d: (tuple(getattr(d, f) for f in _ARRAY_FIELDS),
               tuple(getattr(d, f) for f in _META_FIELDS)),
    lambda meta, arrs: DeviceIndex(**dict(zip(_META_FIELDS, meta)),
                                   **dict(zip(_ARRAY_FIELDS, arrs))),
)


def _live_csr(live_from, live_to, knode_ptr, t_max):
    """Each stratum's forest nodes live at each start time, as one CSR.

    Row ``ki * (t_max + 2) + t``, for t = 0 .. t_max + 1, lists in
    ascending id the nodes x of stratum ki (``knode_ptr[ki] <= x <
    knode_ptr[ki + 1]``) with ``live_from[x] <= t <= live_to[x]``. At one
    start time the live nodes of a stratum are a spanning forest of its
    vertices, so a row holds fewer than n of them. Lifetimes lie in
    ``[1, t_max]`` (raises ``ValueError`` otherwise), so the two end rows
    are empty and any start time outside the index reads an empty row
    once clipped into ``[0, t_max + 1]``. Returns int64 ``(ptr, node)``."""
    lf = np.asarray(live_from, np.int64)
    lt = np.asarray(live_to, np.int64)
    T2 = int(t_max) + 2
    K = len(knode_ptr) - 1
    span = np.maximum(lt - lf + 1, 0)
    held = span > 0
    if held.any() and (lf[held].min() < 1 or lt[held].max() > t_max):
        raise ValueError("a forest-node lifetime leaves [1, t_max]")
    base = np.repeat(np.arange(K, dtype=np.int64) * T2, np.diff(knode_ptr))
    node = np.repeat(np.arange(lf.size, dtype=np.int64), span)
    # the row of node x at its p-th live start time is base + lf + p
    run0 = np.cumsum(span) - span
    row = np.repeat(base + lf - run0, span) + np.arange(node.size)
    ptr = np.zeros(K * T2 + 1, np.int64)
    np.cumsum(np.bincount(row, minlength=K * T2), out=ptr[1:])
    return ptr, node[np.argsort(row, kind="stable")]


def _host_layout(index):
    """(meta dict, name -> int32 host array) in the device layout — the
    single source of truth for ``to_device`` and ``refresh_device``
    (including the length->=1 inert padding of optional arrays).

    Accepts a per-k :class:`PECBIndex` or a whole :class:`StratifiedPECB`
    (routed to :func:`_host_layout_stratified`: all strata in one global
    id space, servable by the same compiled programs)."""
    if isinstance(index, StratifiedPECB):
        return _host_layout_stratified(index)
    i32 = _i32
    seg = np.diff(index.row_ptr)
    vseg = np.diff(index.vrow_ptr)
    store = index.versions
    has_vers = store is not None and store.num_versions > 0
    pad0 = np.zeros((1,), np.int32)
    padn = np.full((1,), NONE, np.int32)
    live_ptr, live_node = _live_csr(index.node_live_from, index.node_live_to,
                                    [0, index.num_nodes], index.t_max)
    arrays = {
        "node_u": i32(index.node_u),
        "node_v": i32(index.node_v),
        "node_ct": i32(index.node_ct),
        "live_from": i32(index.node_live_from),
        "live_to": i32(index.node_live_to),
        "row_ptr": i32(index.row_ptr),
        "ent_ts": i32(index.ent_ts) if index.ent_ts.size else pad0,
        "ent_left": i32(index.ent_left) if index.ent_left.size else padn,
        "ent_right": i32(index.ent_right) if index.ent_right.size else padn,
        "ent_parent": i32(index.ent_parent) if index.ent_parent.size else padn,
        "vrow_ptr": i32(index.vrow_ptr),
        "vent_ts": i32(index.vent_ts) if index.vent_ts.size else pad0,
        "vent_node": i32(index.vent_node) if index.vent_node.size else padn,
        "ver_ts_from": i32(store.ts_from) if has_vers else np.ones((1,), np.int32),
        "ver_ts_to": i32(store.ts_to) if has_vers else pad0,
        "ver_ct": i32(store.ct) if has_vers else pad0,
        "ver_src": i32(store.src) if has_vers else pad0,
        "ver_k": (np.full(store.num_versions, index.k, np.int32)
                  if has_vers else pad0),
        "knode_ptr": i32([0, index.num_nodes]),
        "live_ptr": _i32(live_ptr, "live-node row pointer"),
        "live_node": i32(live_node) if live_node.size else pad0,
    }
    meta = {
        "n": index.n,
        "t_max": index.t_max,
        "max_node_entries": int(seg.max()) if seg.size else 0,
        "max_vert_entries": int(vseg.max()) if vseg.size else 0,
        "num_versions": store.num_versions if has_vers else 0,
        "max_stratum_nodes": index.num_nodes,
        "max_live_nodes": max(int(np.diff(live_ptr).max()), 1),
    }
    return meta, arrays


def _host_layout_stratified(sx: StratifiedPECB):
    """Device layout for a whole k-stratified index.

    The per-stratum blocks are fused into ONE global node/entry id space:
    node ids shift by ``knode_ptr[ki]``, the per-stratum CSRs re-base onto
    the concatenated entry arrays, and per-vertex lookup becomes a lookup
    on the *slot* ``ki * n + u`` (``vrow_ptr`` has ``|K|*n+1`` rows). The
    strata stay link-disjoint, so :func:`batch_query`'s pointer jumping
    serves a mixed-k batch unchanged — per-query k enters only
    as the host-computed entry slot, from which the device reads the
    query's stratum (``slot // n``) and propagates over that stratum's
    nodes live at the query's start time alone (:func:`_component_masks`),
    plus the
    ``ver_k == kq`` filter of :func:`batch_query_full_mixed` (the version
    arrays are the one place where records of different strata share an
    index space).
    """
    i32 = _i32
    K = len(sx.ks)
    n = sx.n
    Ntot = sx.num_nodes
    Etot = int(sx.ent_ts.shape[0])
    VEtot = int(sx.vent_ts.shape[0])

    row_ptr = np.empty(Ntot + 1, np.int64)
    vrow_ptr = np.empty(K * n + 1, np.int64)
    ent_l = sx.ent_left.astype(np.int64)
    ent_r = sx.ent_right.astype(np.int64)
    ent_p = sx.ent_parent.astype(np.int64)
    vent_node = sx.vent_node.astype(np.int64)
    for ki in range(K):
        s, e = int(sx.knode_ptr[ki]), int(sx.knode_ptr[ki + 1])
        row_ptr[s:e] = (sx.row_ptr[s + ki:e + ki].astype(np.int64)
                        + int(sx.kent_ptr[ki]))
        vrow_ptr[ki * n:(ki + 1) * n] = (
            sx.vrow_ptr[ki * (n + 1):ki * (n + 1) + n].astype(np.int64)
            + int(sx.kvent_ptr[ki]))
        off = int(sx.knode_ptr[ki])
        if off:
            for seg in (ent_l[int(sx.kent_ptr[ki]):int(sx.kent_ptr[ki + 1])],
                        ent_r[int(sx.kent_ptr[ki]):int(sx.kent_ptr[ki + 1])],
                        ent_p[int(sx.kent_ptr[ki]):int(sx.kent_ptr[ki + 1])],
                        vent_node[int(sx.kvent_ptr[ki]):
                                  int(sx.kvent_ptr[ki + 1])]):
                seg[seg >= 0] += off
    row_ptr[Ntot] = Etot
    vrow_ptr[K * n] = VEtot

    st = sx.strata
    V = int(st.num_versions) if st is not None else 0
    seg = np.diff(row_ptr)
    vseg = np.diff(vrow_ptr)
    pad0 = np.zeros((1,), np.int32)
    padn = np.full((1,), NONE, np.int32)
    live_ptr, live_node = _live_csr(sx.node_live_from, sx.node_live_to,
                                    sx.knode_ptr, sx.t_max)
    live_seg = np.diff(live_ptr)
    arrays = {
        "node_u": i32(sx.node_u),
        "node_v": i32(sx.node_v),
        "node_ct": i32(sx.node_ct),
        "live_from": i32(sx.node_live_from),
        "live_to": i32(sx.node_live_to),
        "row_ptr": _i32(row_ptr, "fused entry row_ptr"),
        "ent_ts": i32(sx.ent_ts) if Etot else pad0,
        "ent_left": i32(ent_l) if Etot else padn,
        "ent_right": i32(ent_r) if Etot else padn,
        "ent_parent": i32(ent_p) if Etot else padn,
        "vrow_ptr": _i32(vrow_ptr, "fused K*n vertex row_ptr"),
        "vent_ts": i32(sx.vent_ts) if VEtot else pad0,
        "vent_node": i32(vent_node) if VEtot else padn,
        "ver_ts_from": i32(st.ts_from) if V else np.ones((1,), np.int32),
        "ver_ts_to": i32(st.ts_to) if V else pad0,
        "ver_ct": i32(st.ct) if V else pad0,
        "ver_src": i32(sx.ver_src) if V else pad0,
        "ver_k": (np.repeat(np.asarray(sx.ks, np.int32),
                            np.diff(st.kptr)).astype(np.int32)
                  if V else pad0),
        "knode_ptr": _i32(sx.knode_ptr, "stratum node pointer"),
        "live_ptr": _i32(live_ptr, "live-node row pointer"),
        "live_node": i32(live_node) if live_node.size else pad0,
    }
    knodes = np.diff(sx.knode_ptr)
    meta = {
        "n": n,
        "t_max": sx.t_max,
        "max_node_entries": int(seg.max()) if seg.size else 0,
        "max_vert_entries": int(vseg.max()) if vseg.size else 0,
        "num_versions": V,
        "max_stratum_nodes": int(knodes.max()) if knodes.size else 0,
        "max_live_nodes": max(int(live_seg.max()) if live_seg.size else 0, 1),
    }
    return meta, arrays


def to_device(index) -> DeviceIndex:
    """Upload a :class:`PECBIndex` or a whole :class:`StratifiedPECB`
    (mixed-k servable) to the device."""
    meta, arrays = _host_layout(index)
    if kernel_contracts.witness_enabled():
        kernel_contracts.check_layout(arrays,
                                      witness=kernel_contracts.WITNESS)
    return DeviceIndex(**meta,
                       **{k: jnp.asarray(v) for k, v in arrays.items()})


def refresh_device(prev_host: PECBIndex, prev_dev: DeviceIndex,
                   new_host: PECBIndex) -> tuple[DeviceIndex, dict]:
    """Refresh a device mirror across a streaming epoch, re-uploading only
    what changed.

    Per array (compared in the shared host layout): if the new array equals
    the old one, the resident device buffer is reused outright (zero
    transfer); if the old array is a strict prefix of the new one (a pure
    suffix grow), only the suffix is shipped and concatenated on device;
    otherwise the array is uploaded in full. Always exact — the result is
    indistinguishable from ``to_device(new_host)`` (test-asserted); the
    returned stats (``reused_bytes``/``uploaded_bytes`` + per-kind counts)
    make the transfer savings observable to the registry's refresh metrics.

    Retention epochs (``streaming.shrink_pecb_index``) land here too: a
    shrunk index shares no bytes with its predecessor (every surviving
    value is shifted), so each array takes the full-upload path — smaller
    than the buffer it replaces. ``freed_bytes`` records the net device
    memory returned by the swap (old mirror bytes minus new), the
    observable behind the bounded-memory claim the retention bench
    asserts; it is 0 for grow refreshes.
    """
    _, old_arrays = _host_layout(prev_host)
    meta, new_arrays = _host_layout(new_host)
    stats = {"reused": 0, "suffix": 0, "full": 0,
             "reused_bytes": 0, "uploaded_bytes": 0, "freed_bytes": 0}
    old_total = sum(int(a.nbytes) for a in old_arrays.values())
    new_total = sum(int(a.nbytes) for a in new_arrays.values())
    stats["freed_bytes"] = max(0, old_total - new_total)
    arrays = {}
    for name in _ARRAY_FIELDS:
        old_np, new_np = old_arrays[name], new_arrays[name]
        old_dev = getattr(prev_dev, name)
        if (old_np.shape == new_np.shape and old_dev.shape == old_np.shape
                and np.array_equal(old_np, new_np)):
            arrays[name] = old_dev
            stats["reused"] += 1
            stats["reused_bytes"] += int(new_np.nbytes)
        elif (old_np.shape[0] < new_np.shape[0]
              and old_dev.shape == old_np.shape
              and np.array_equal(old_np, new_np[:old_np.shape[0]])):
            suffix = jnp.asarray(
                np.ascontiguousarray(new_np[old_np.shape[0]:]))
            arrays[name] = jnp.concatenate([old_dev, suffix])
            stats["suffix"] += 1
            stats["reused_bytes"] += int(old_np.nbytes)
            stats["uploaded_bytes"] += int(suffix.nbytes)
        else:
            arrays[name] = jnp.asarray(new_np)
            stats["full"] += 1
            stats["uploaded_bytes"] += int(new_np.nbytes)
    return DeviceIndex(**meta, **arrays), stats


def stratum_device(dix: DeviceIndex, sx: StratifiedPECB,
                   k: int) -> DeviceIndex:
    """Carve ONE stratum's block out of a fused stratified device mirror.

    A single-k program (the window sweep) pays propagation cost on live
    lists as long as the mirror's longest — on the fused mixed-k mirror,
    the k = 2 stratum's for a launch that can only ever touch stratum k.
    This slices the ``[knode_ptr[ki],
    knode_ptr[ki+1])`` node block plus its entry / vertex-entry / version
    segments into a standalone per-k :class:`DeviceIndex` (a handful of
    eager device slices and one download of the stratum's live-row
    pointers), with forest-node links rebased into the block's local id
    space. Array-for-array equal to ``to_device(sx.slice_k(k))``
    (test-asserted); the static ``max_*_entries`` meta keeps the fused
    mirror's values — a valid upper bound costing at most a few extra
    binary-search steps — while ``max_live_nodes`` is the stratum's own
    longest live list.
    """
    ki = sx.k_index(k)
    n = dix.n
    nlo, nhi = int(sx.knode_ptr[ki]), int(sx.knode_ptr[ki + 1])
    T2 = dix.t_max + 2
    live_ptr = dix.live_ptr[ki * T2:(ki + 1) * T2 + 1]
    own_ptr = np.asarray(live_ptr)
    plo, phi = int(own_ptr[0]), int(own_ptr[-1])
    elo, ehi = int(sx.kent_ptr[ki]), int(sx.kent_ptr[ki + 1])
    vlo, vhi = int(sx.kvent_ptr[ki]), int(sx.kvent_ptr[ki + 1])
    st = sx.strata
    slo, shi = ((int(st.kptr[ki]), int(st.kptr[ki + 1]))
                if st is not None else (0, 0))
    pad0 = jnp.zeros((1,), jnp.int32)
    padn = jnp.full((1,), NONE, jnp.int32)

    def links(a):
        seg = a[elo:ehi]
        # node links are global forest ids; -1 stays the no-link sentinel
        return jnp.where(seg >= 0, seg - nlo, seg) if nlo else seg

    has_ent, has_vent, has_ver = ehi > elo, vhi > vlo, shi > slo
    vent_node = dix.vent_node[vlo:vhi]
    if nlo and has_vent:
        vent_node = jnp.where(vent_node >= 0, vent_node - nlo, vent_node)
    return DeviceIndex(
        n=n, t_max=dix.t_max,
        node_u=dix.node_u[nlo:nhi],
        node_v=dix.node_v[nlo:nhi],
        node_ct=dix.node_ct[nlo:nhi],
        live_from=dix.live_from[nlo:nhi],
        live_to=dix.live_to[nlo:nhi],
        row_ptr=dix.row_ptr[nlo:nhi + 1] - elo,
        ent_ts=dix.ent_ts[elo:ehi] if has_ent else pad0,
        ent_left=links(dix.ent_left) if has_ent else padn,
        ent_right=links(dix.ent_right) if has_ent else padn,
        ent_parent=links(dix.ent_parent) if has_ent else padn,
        vrow_ptr=dix.vrow_ptr[ki * n:(ki + 1) * n + 1] - vlo,
        vent_ts=dix.vent_ts[vlo:vhi] if has_vent else pad0,
        vent_node=vent_node if has_vent else padn,
        ver_ts_from=(dix.ver_ts_from[slo:shi] if has_ver
                     else jnp.ones((1,), jnp.int32)),
        ver_ts_to=dix.ver_ts_to[slo:shi] if has_ver else pad0,
        ver_ct=dix.ver_ct[slo:shi] if has_ver else pad0,
        ver_src=dix.ver_src[slo:shi] if has_ver else pad0,
        ver_k=dix.ver_k[slo:shi] if has_ver else pad0,
        knode_ptr=jnp.asarray([0, nhi - nlo], jnp.int32),
        live_ptr=live_ptr - plo,
        live_node=dix.live_node[plo:phi] - nlo if phi > plo else pad0,
        max_node_entries=dix.max_node_entries,
        max_vert_entries=dix.max_vert_entries,
        num_versions=shi - slo,
        max_stratum_nodes=nhi - nlo,
        max_live_nodes=max(int(np.diff(own_ptr).max()), 1),
    )


def _lower_bound(ts_arr: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                 target: jnp.ndarray, steps: int) -> jnp.ndarray:
    """Vectorized lower_bound: smallest i in [lo, hi) with ts_arr[i] >= target.

    All of ``lo``/``hi``/``target`` share a broadcastable shape; returns hi
    when no element qualifies. ``steps`` must be >= ceil(log2(max segment)).
    """
    size = ts_arr.shape[0]
    for _ in range(max(steps, 1)):
        mid = (lo + hi) // 2
        mid_c = jnp.clip(mid, 0, size - 1)
        go_right = (ts_arr[mid_c] < target) & (mid < hi)
        lo = jnp.where(go_right & (lo < hi), mid + 1, lo)
        hi = jnp.where((~go_right) & (lo < hi), mid, hi)
    return lo


def _first_day(ts):
    """Start times moved up to 1: no edge precedes time 1, so a window
    that starts earlier holds the edges of the one that starts at 1
    (Algorithm 1 and the oracle answer both alike)."""
    return jnp.maximum(ts, 1)


def _entry_steps(dix: DeviceIndex) -> tuple[int, int]:
    vsteps = int(np.ceil(np.log2(max(dix.max_vert_entries, 1) + 1))) + 1
    nsteps = int(np.ceil(np.log2(max(dix.max_node_entries, 1) + 1))) + 1
    return vsteps, nsteps


def _entry_nodes(dix: DeviceIndex, vlo, vhi, ts, te):
    """Resolve entry nodes given per-query vertex CSR bounds (Alg 1 line 3).
    Returns (e0_ok, e0c): validity mask + clipped entry node ids."""
    vsteps, _ = _entry_steps(dix)
    N = dix.num_nodes
    vi = _lower_bound(dix.vent_ts, vlo, vhi, ts, vsteps)
    has_entry = vi < vhi
    e0 = jnp.where(has_entry,
                   dix.vent_node[jnp.clip(vi, 0, dix.vent_ts.shape[0] - 1)],
                   NONE)
    e0_ok = has_entry & (e0 >= 0)
    e0c = jnp.clip(e0, 0, N - 1)
    e0_ok = e0_ok & (dix.node_ct[e0c] <= te)
    return e0_ok, e0c


def _component_masks(dix: DeviceIndex, slot, e0_ok, e0c, ts, te):
    """Steps 2-5: per-(query, node) parent resolution, activity masking,
    pointer jumping to each component's top node, membership collection.

    Each query works on its **live list**: the nodes of its stratum that
    are live at its start time, row ``ki * (t_max + 2) + clip(ts_b, 0,
    t_max + 1)`` of the live-node CSR with ``ki = slot_b // n``. Row b
    reads ``M = dix.max_live_nodes`` contiguous ids from the row's start
    (moved left where that would run past the array's end, since a
    dynamic slice clamps its start) and masks the lanes outside the row.
    Every other node of the stratum is a self-loop at ts_b, so dropping
    it changes no component and no round count. Parent links and the
    entry node map to list positions through a stratum-local ``(B, W)``
    buffer (``W = dix.max_stratum_nodes``) that holds each live node's
    lane and -1 elsewhere: a parent that is not live is no link.

    Returns ``(bool[B, n] vertex mask, int32 rounds)``: forest-node
    membership is ``top[x] == top[entry_b]`` (masked by activity),
    scattered to the member nodes' endpoints; ``rounds`` counts the
    pointer-jump rounds the batch took, the last one (which finds no
    change) included."""
    B = ts.shape[0]
    W = dix.max_stratum_nodes
    M = dix.max_live_nodes
    L = dix.live_node.shape[0]
    n = dix.n
    T2 = dix.t_max + 2
    _, nsteps = _entry_steps(dix)

    # -- each row's live list: lanes [lo_b, hi_b) of [start_b, start_b+M) --
    ki = slot // n
    row = ki * T2 + jnp.clip(ts, 0, T2 - 1)
    first = dix.live_ptr[row]
    start = jnp.minimum(first, L - M)
    lo = (first - start)[:, None]
    hi = lo + (dix.live_ptr[row + 1] - first)[:, None]
    lane = jnp.arange(M, dtype=jnp.int32)[None, :]
    live = (lo <= lane) & (lane < hi)
    ids = jax.vmap(
        lambda s: jax.lax.dynamic_slice(dix.live_node, (s,), (M,)))(start)

    # -- node id -> lane of the row's list, -1 where not live at ts ------
    base = dix.knode_ptr[ki][:, None]
    bi = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, M))
    lane_of = jnp.full((B, W), NONE, jnp.int32).at[
        bi, jnp.where(live, ids - base, W)].set(
            jnp.broadcast_to(lane, (B, M)), mode="drop")

    def position(x):
        loc = jnp.clip(x - base, 0, W - 1)
        return jnp.where(x >= 0, jnp.take_along_axis(lane_of, loc, axis=1),
                         NONE)

    # -- 2. per-(query, node) parent link at ts, as a lane ---------------
    idx = _lower_bound(dix.ent_ts, dix.row_ptr[ids], dix.row_ptr[ids + 1],
                       ts[:, None], nsteps)
    parent = position(dix.ent_parent[jnp.clip(idx, 0,
                                              dix.ent_ts.shape[0] - 1)])

    # -- 3. per-(query, node) activity ----------------------------------
    active = live & (dix.node_ct[ids] <= te[:, None])

    # -- 4. pointer jumping along active parent links --------------------
    pc = jnp.clip(parent, 0, M - 1)
    up = (parent >= 0) & active & jnp.take_along_axis(active, pc, axis=1)
    top0 = jnp.where(up, pc, lane)

    def body(state):
        top, _, rounds = state
        nxt = jnp.take_along_axis(top, top, axis=1)
        return nxt, jnp.any(nxt != top), rounds + 1

    top, _, rounds = jax.lax.while_loop(
        lambda s: s[1], body, (top0, jnp.array(True), jnp.int32(0)))

    # -- 5. membership: top[x] == top[entry_b], masked by activity -------
    e0l = position(e0c[:, None])
    root = jnp.take_along_axis(top, jnp.clip(e0l, 0, M - 1), axis=1)
    member = (active & (top == root) & (e0_ok[:, None] & (e0l >= 0))
              ).astype(jnp.int32)

    out = jnp.zeros((B, n), jnp.int32)
    out = out.at[bi, dix.node_u[ids]].max(member)
    out = out.at[bi, dix.node_v[ids]].max(member)
    return out.astype(bool), rounds


def _version_member(dix: DeviceIndex, vertex_mask, ts, te):
    """bool[B, V] core-time version membership: version j is a member edge
    for query b iff its record covers ``ts_b``, ``ct_j <= te_b`` and its
    src endpoint is in the component (one gather over the vertex mask)."""
    src_in = vertex_mask[:, dix.ver_src]
    return (
        (dix.ver_ts_from[None, :] <= ts[:, None])
        & (ts[:, None] <= dix.ver_ts_to[None, :])
        & (dix.ver_ct[None, :] <= te[:, None])
        & src_in
    )


@jax.jit
def batch_query(dix: DeviceIndex, u: jnp.ndarray, ts: jnp.ndarray,
                te: jnp.ndarray):
    """(bool[B, n] vertex-membership of each query's k-core component,
    int32 pointer-jump rounds of the launch)."""
    B = u.shape[0]
    if dix.num_nodes == 0:
        return jnp.zeros((B, dix.n), bool), jnp.int32(0)
    ts = _first_day(ts)
    e0_ok, e0c = _entry_nodes(dix, dix.vrow_ptr[u], dix.vrow_ptr[u + 1], ts, te)
    return _component_masks(dix, u, e0_ok, e0c, ts, te)


@jax.jit
def batch_query_full(dix: DeviceIndex, u: jnp.ndarray, ts: jnp.ndarray,
                     te: jnp.ndarray):
    """(bool[B, n] vertex mask, bool[B, V] version-membership mask, int32
    pointer-jump rounds).

    The version mask is the device-side EDGES/SUBGRAPH payload: exactly the
    member edges of each query's component (oracle-exact; see module doc).
    """
    B = u.shape[0]
    if dix.num_nodes == 0:
        return (jnp.zeros((B, dix.n), bool),
                jnp.zeros((B, dix.ver_src.shape[0]), bool), jnp.int32(0))
    ts = _first_day(ts)
    e0_ok, e0c = _entry_nodes(dix, dix.vrow_ptr[u], dix.vrow_ptr[u + 1], ts, te)
    vmask, rounds = _component_masks(dix, u, e0_ok, e0c, ts, te)
    return vmask, _version_member(dix, vmask, ts, te), rounds


@jax.jit
def batch_query_full_mixed(dix: DeviceIndex, slot: jnp.ndarray,
                           ts: jnp.ndarray, te: jnp.ndarray,
                           kq: jnp.ndarray):
    """Mixed-k batch against a stratified :class:`DeviceIndex`: one
    compiled program, per-query k as a device operand.

    ``slot`` is the per-query entry slot ``k_index(k) * n + u`` (computed
    host-side from the :class:`StratifiedPECB` handle; strata are
    link-disjoint so propagation needs no k mask) and ``kq`` the per-query
    k filtering the shared version arrays for the EDGES/SUBGRAPH payload.
    Returns ``(bool[B, n] vertex mask, bool[B, V] version mask, int32
    pointer-jump rounds)``.
    """
    B = slot.shape[0]
    if dix.num_nodes == 0:
        return (jnp.zeros((B, dix.n), bool),
                jnp.zeros((B, dix.ver_src.shape[0]), bool), jnp.int32(0))
    ts = _first_day(ts)
    e0_ok, e0c = _entry_nodes(dix, dix.vrow_ptr[slot],
                              dix.vrow_ptr[slot + 1], ts, te)
    vmask, rounds = _component_masks(dix, slot, e0_ok, e0c, ts, te)
    vermask = (_version_member(dix, vmask, ts, te)
               & (dix.ver_k[None, :] == kq[:, None]))
    return vmask, vermask, rounds


def mixed_slots(sx: StratifiedPECB,
                queries: list[tuple[int, int]]) -> np.ndarray:
    """Host-side slot computation for a mixed-k batch: ``(u, k) ->
    k_index(k) * n + u``. Raises ``KeyError`` for an unsupported k — the
    serving planner short-circuits those before batching."""
    # int64 math first: k_index*n + u walks the fused slot space, which
    # outgrows int32 long before any single stratum does
    slots = np.asarray([sx.k_index(k) * sx.n + u for (u, k) in queries],
                       np.int64)
    return _i32(slots, "mixed-k entry slots")


def batch_query_mixed_np(sx: StratifiedPECB,
                         queries: list[tuple[int, int, int, int]]) -> list[set[int]]:
    """Host wrapper: mixed-k ``(u, ts, te, k)`` batch -> vertex sets
    (tests/benches)."""
    dix = to_device(sx)
    slot = jnp.asarray(mixed_slots(sx, [(u, k) for (u, _, _, k) in queries]))
    ts = jnp.asarray([q[1] for q in queries], jnp.int32)
    te = jnp.asarray([q[2] for q in queries], jnp.int32)
    kq = jnp.asarray([q[3] for q in queries], jnp.int32)
    vmask, _, _ = batch_query_full_mixed(dix, slot, ts, te, kq)
    mask = np.asarray(vmask)
    return [set(np.nonzero(row)[0].tolist()) for row in mask]


def batch_query_mixed_edges_np(sx: StratifiedPECB,
                               queries: list[tuple[int, int, int, int]]) -> list[set[int]]:
    """Host wrapper: mixed-k ``(u, ts, te, k)`` batch -> member *edge id*
    sets (tests/benches)."""
    if sx.strata is None:
        raise ValueError("index has no version store")
    dix = to_device(sx)
    slot = jnp.asarray(mixed_slots(sx, [(u, k) for (u, _, _, k) in queries]))
    ts = jnp.asarray([q[1] for q in queries], jnp.int32)
    te = jnp.asarray([q[2] for q in queries], jnp.int32)
    kq = jnp.asarray([q[3] for q in queries], jnp.int32)
    _, vermask, _ = batch_query_full_mixed(dix, slot, ts, te, kq)
    vermask = np.asarray(vermask)[:, :dix.num_versions]
    eid = sx.strata.edge_id
    return [set(eid[np.nonzero(row)[0]].tolist()) for row in vermask]


@jax.jit
def window_sweep(dix: DeviceIndex, u: jnp.ndarray, ts: jnp.ndarray,
                 te: jnp.ndarray) -> jnp.ndarray:
    """(bool[W, n] vertex masks for ONE vertex over W windows, int32
    pointer-jump rounds), one launch.

    ``u`` is a scalar: the vertex's entry segment ``[vrow_ptr[u],
    vrow_ptr[u+1])`` is resolved once and shared by every window — the
    sweep never re-gathers per-query CSR bounds the way ``batch_query``
    must for a heterogeneous batch.
    """
    W = ts.shape[0]
    if dix.num_nodes == 0:
        return jnp.zeros((W, dix.n), bool), jnp.int32(0)
    ts = _first_day(ts)
    vlo = jnp.broadcast_to(dix.vrow_ptr[u], (W,))
    vhi = jnp.broadcast_to(dix.vrow_ptr[u + 1], (W,))
    e0_ok, e0c = _entry_nodes(dix, vlo, vhi, ts, te)
    return _component_masks(dix, jnp.broadcast_to(u, (W,)), e0_ok, e0c,
                            ts, te)


def batch_query_np(index: PECBIndex, queries: list[tuple[int, int, int]]) -> list[set[int]]:
    """Host convenience wrapper returning vertex sets (for tests/benches)."""
    dix = to_device(index)
    u = jnp.asarray([q[0] for q in queries], jnp.int32)
    ts = jnp.asarray([q[1] for q in queries], jnp.int32)
    te = jnp.asarray([q[2] for q in queries], jnp.int32)
    mask = np.asarray(batch_query(dix, u, ts, te)[0])
    return [set(np.nonzero(row)[0].tolist()) for row in mask]


def batch_query_edges_np(index: PECBIndex,
                         queries: list[tuple[int, int, int]]) -> list[set[int]]:
    """Host wrapper over :func:`batch_query_full` returning per-query member
    *edge id* sets (for tests/benches)."""
    dix = to_device(index)
    store = index.versions
    if store is None:
        raise ValueError("index has no version store")
    u = jnp.asarray([q[0] for q in queries], jnp.int32)
    ts = jnp.asarray([q[1] for q in queries], jnp.int32)
    te = jnp.asarray([q[2] for q in queries], jnp.int32)
    _, vermask, _ = batch_query_full(dix, u, ts, te)
    vermask = np.asarray(vermask)[:, :dix.num_versions]
    return [set(store.edge_id[np.nonzero(row)[0]].tolist()) for row in vermask]
