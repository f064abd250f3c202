"""Shape-bucketed, device-sharded execution of the batched query plane
(DESIGN.md §7.2, §7.6).

``batch_query`` is ``jax.jit``-compiled, and XLA specializes on the batch
shape: a stream of ragged micro-batches (B = 13, 57, 200, ...) would compile
once *per distinct size*. The fix is shape bucketing: pad every batch up to
the next power of two (floored at ``min_bucket``, capped at ``max_batch``),
so a serving process compiles at most ``log2(max_batch / min_bucket) + 1``
programs per index and then never again. Padding lanes use the inert query
``(u=0, ts=1, te=0)``: ``te < ts`` can match nothing (core times are >= 1),
so pad lanes return empty masks and are sliced off before unpacking.

Multi-device: when the process sees more than one JAX device, the (B, n)
propagation shards over the batch dimension with ``jax.sharding`` — a 1-D
``('batch',)`` mesh, queries placed with ``PartitionSpec('batch')``, index
arrays replicated by the partitioner (they are read-only gather operands).
Buckets are sized to multiples of the device count so the placement is
exact. With one device (one TPU chip, or the CPU the tests run on) or a
bucket not divisible by the mesh, arrays stay uncommitted and jit runs
single-device — semantics identical, tested on virtual CPU devices by the
sharded subprocess suite (tests/test_distributed.py) and on four chips by
``chip_smoke.py --chips 4``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.batch_query import (DeviceIndex, batch_query,
                                    batch_query_full,
                                    batch_query_full_mixed, window_sweep)
from repro.obs.trace import Tracer

#: Inert padding query: te < ts matches no core-time entry (cts are >= 1).
PAD_QUERY = (0, 1, 0)


def bucket_size(b: int, min_bucket: int = 8, max_batch: int = 256) -> int:
    """Smallest power-of-two bucket >= b, floored/capped to the configured
    range. ``b`` beyond ``max_batch`` is the batcher's bug, not ours."""
    if not 1 <= b <= max_batch:
        raise ValueError(f"batch size {b} outside [1, {max_batch}]")
    bucket = max(min_bucket, 1 << (b - 1).bit_length())
    return min(bucket, max_batch)


def pad_queries(u, ts, te, bucket: int):
    """int32[(bucket,)] x3, padded with the inert query."""
    u = np.asarray(u, np.int32)
    ts = np.asarray(ts, np.int32)
    te = np.asarray(te, np.int32)
    b = u.shape[0]
    if b > bucket:
        raise ValueError(f"batch of {b} queries exceeds bucket {bucket}")
    if b == bucket:
        return u, ts, te
    pad = bucket - b
    return (
        np.concatenate([u, np.full(pad, PAD_QUERY[0], np.int32)]),
        np.concatenate([ts, np.full(pad, PAD_QUERY[1], np.int32)]),
        np.concatenate([te, np.full(pad, PAD_QUERY[2], np.int32)]),
    )


class ShardedExecutor:
    """Runs padded query batches on all visible devices.

    One executor per engine; stateless across calls apart from the device
    mesh and each calling thread's last launch times, so it is safe to
    share between batcher worker threads (jit dispatch is thread-safe).

    Every launch runs as three live spans on the calling thread:
    ``executor.dispatch`` (pad, upload, enqueue, any compile),
    ``executor.wait`` (until the outputs are ready; carries the launch's
    ``jump_rounds``, ``jump_width`` and ``live_width``) and
    ``executor.download`` (the masks and the round count in one
    ``device_get``). The rounds add to the ``jump_rounds`` counter, the
    mirror's widest stratum (``DeviceIndex.max_stratum_nodes``, which the
    live lists are drawn from) to ``jump_width``, its longest live list
    (``DeviceIndex.max_live_nodes``, the width each row propagates over)
    to ``live_width`` and the launch to ``jump_launches``, traced or not.
    """

    def __init__(self, devices=None, *, metrics=None, tracer=None):
        self.devices = list(devices) if devices is not None else jax.devices()
        self.num_devices = len(self.devices)
        # observability sinks (DESIGN.md §11.4): compile events from the
        # jit caches are *recorded*, not inferred — a compile storm shows
        # up as jit_compile_* counters and "compile"-category trace spans
        self.metrics = metrics
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self._local = threading.local()
        if self.num_devices > 1:
            self.mesh = Mesh(np.asarray(self.devices), ("batch",))
            self.batch_sharding = NamedSharding(self.mesh, P("batch"))
        else:
            self.mesh = None
            self.batch_sharding = None

    def _track_compile(self, fn, program: str, bucket: int, t0: float):
        """Called after a jit dispatch: if the program's cache grew, this
        launch paid a compile — count it and record a trace span covering
        the dispatch (on CPU the compile completes synchronously inside
        it, so the span duration is a faithful compile cost)."""
        t1 = time.perf_counter()
        if self.metrics is not None:
            self.metrics.count("jit_compiles")
            self.metrics.count(f"jit_compile_{program}")
            self.metrics.observe("jit_compile", t1 - t0)
        self.tracer.start_span(
            "jit_compile", parent=None, cat="compile", t0=t0,
            program=program, bucket=bucket,
            cache_size=fn._cache_size()).end(t1)

    def _dispatch(self, fn, program: str, bucket: int, args):
        c0 = fn._cache_size()
        t0 = time.perf_counter()
        out = fn(*args)
        if fn._cache_size() > c0:
            self._track_compile(fn, program, bucket, t0)
        return out

    def _dispatch_span(self, program: str, bucket: int):
        """The live ``executor.dispatch`` span of a launch; its start is
        this thread's last dispatch time (:meth:`last_launch`)."""
        self._local.dispatched = time.perf_counter()
        return self.tracer.span("executor.dispatch", program=program,
                                bucket=bucket)

    def _collect(self, out, dix: DeviceIndex) -> list:
        """Wait for a launch's outputs (masks..., rounds), then download
        them in one ``device_get``; returns the host masks and counts the
        launch's pointer-jump rounds and widths."""
        with self.tracer.span("executor.wait") as wait:
            # repro: ignore[hot-path-transfer] — block_until_ready, the sync
            jax.block_until_ready(out)
        self._local.waited = time.perf_counter()
        with self.tracer.span("executor.download"):
            # repro: ignore[hot-path-transfer] — device_get of masks + rounds
            *masks, rounds = jax.device_get(out)
        rounds = int(rounds)
        width, live = dix.max_stratum_nodes, dix.max_live_nodes
        wait.set("jump_rounds", rounds)
        wait.set("jump_width", width)
        wait.set("live_width", live)
        if self.metrics is not None:
            self.metrics.count("jump_rounds", rounds)
            self.metrics.count("jump_width", width)
            self.metrics.count("live_width", live)
            self.metrics.count("jump_launches")
        return [np.asarray(m) for m in masks]

    def last_launch(self) -> tuple[float, float]:
        """(dispatch start, wait end) of this thread's last launch."""
        return self._local.dispatched, self._local.waited

    def align(self, bucket: int) -> int:
        """Round a bucket up to a multiple of the device count (no-op for
        power-of-two device counts <= bucket, the common case)."""
        d = self.num_devices
        if d <= 1 or bucket % d == 0:
            return bucket
        return ((bucket + d - 1) // d) * d

    def final_bucket(self, b: int, min_bucket: int, max_batch: int) -> int:
        """The executed batch shape for ``b`` requests: power-of-two bucket,
        aligned to the device count. Single owner of the formula — callers
        use this for padding metrics and pass the result to ``run``."""
        return self.align(bucket_size(b, min_bucket, max_batch))

    def _check_aligned(self, bucket: int) -> None:
        if self.align(bucket) != bucket:
            raise ValueError(f"bucket {bucket} is not device-aligned; "
                             "use final_bucket()")

    def _place(self, up, tsp, tep, bucket):
        if self.batch_sharding is not None and bucket % self.num_devices == 0:
            # the one deliberate upload: padded query arrays onto the
            # batch sharding before dispatch
            # repro: ignore[hot-path-transfer]
            return tuple(jax.device_put(jnp.asarray(a), self.batch_sharding)
                         for a in (up, tsp, tep))
        return jnp.asarray(up), jnp.asarray(tsp), jnp.asarray(tep)

    def run(self, dix: DeviceIndex, u, ts, te, bucket: int) -> np.ndarray:
        """bool[B, n] membership masks for the *unpadded* prefix. ``bucket``
        must come from ``final_bucket`` (already device-aligned)."""
        b = len(u)
        self._check_aligned(bucket)
        with self._dispatch_span("batch_query", bucket):
            qu, qts, qte = self._place(*pad_queries(u, ts, te, bucket),
                                       bucket)
            out = self._dispatch(batch_query, "batch_query", bucket,
                                 (dix, qu, qts, qte))
        mask, = self._collect(out, dix)
        return mask[:b]

    def run_full(self, dix: DeviceIndex, u, ts, te,
                 bucket: int) -> tuple[np.ndarray, np.ndarray]:
        """(bool[B, n] vertex masks, bool[B, V] version-membership masks)
        for the unpadded prefix — the EDGES/SUBGRAPH-mode launch."""
        b = len(u)
        self._check_aligned(bucket)
        with self._dispatch_span("batch_query_full", bucket):
            qu, qts, qte = self._place(*pad_queries(u, ts, te, bucket),
                                       bucket)
            out = self._dispatch(batch_query_full, "batch_query_full",
                                 bucket, (dix, qu, qts, qte))
        vmask, vermask = self._collect(out, dix)
        return vmask[:b], vermask[:b, :dix.num_versions]

    def run_full_mixed(self, dix: DeviceIndex, slot, ts, te, kq,
                       bucket: int) -> tuple[np.ndarray, np.ndarray]:
        """Mixed-k full-mode launch against a *stratified* device index:
        ``slot`` is the per-query entry slot ``k_index(k) * n + u`` and
        ``kq`` the per-query k filtering the shared version arrays — both
        plain device operands, so every k mix shares one compiled program
        per bucket. Returns the same ``(vertex masks, version masks)``
        pair as :meth:`run_full`."""
        b = len(slot)
        self._check_aligned(bucket)
        with self._dispatch_span("batch_query_full_mixed", bucket):
            qs, qts, qte = self._place(*pad_queries(slot, ts, te, bucket),
                                       bucket)
            kq = np.asarray(kq, np.int32)
            if kq.shape[0] < bucket:
                # pad lanes are already inert via te < ts; kq=0 matches no
                # stratum, keeping the version mask all-False twice over
                kq = np.concatenate([kq, np.zeros(bucket - b, np.int32)])
            if (self.batch_sharding is not None
                    and bucket % self.num_devices == 0):
                # repro: ignore[hot-path-transfer] — padded operand upload
                qkq = jax.device_put(jnp.asarray(kq), self.batch_sharding)
            else:
                qkq = jnp.asarray(kq)
            out = self._dispatch(
                batch_query_full_mixed, "batch_query_full_mixed", bucket,
                (dix, qs, qts, qte, qkq))
        vmask, vermask = self._collect(out, dix)
        return vmask[:b], vermask[:b, :dix.num_versions]

    def run_sweep(self, dix: DeviceIndex, u: int, ts, te,
                  bucket: int) -> np.ndarray:
        """bool[W, n] masks of one vertex over W windows in one launch.
        Windows pad with the inert (ts=1, te=0) window; the batch (window)
        dimension shards exactly like ``run``'s."""
        w = len(ts)
        self._check_aligned(bucket)
        with self._dispatch_span("window_sweep", bucket):
            _, tsp, tep = pad_queries([u] * w, ts, te, bucket)
            _, qts, qte = self._place(np.zeros(bucket, np.int32), tsp, tep,
                                      bucket)
            out = self._dispatch(window_sweep, "window_sweep", bucket,
                                 (dix, jnp.int32(u), qts, qte))
        mask, = self._collect(out, dix)
        return mask[:w]

    @staticmethod
    def compile_count() -> int:
        """Number of distinct programs compiled for the batched query plane
        (jit cache entries, summed over the vertex-mask, full-mode and
        window-sweep programs). Bucketing tests assert this stays flat
        across batch sizes within one bucket."""
        return (batch_query._cache_size() + batch_query_full._cache_size()
                + batch_query_full_mixed._cache_size()
                + window_sweep._cache_size())
