"""Async decompression service with adaptive micro-batching.

The decode plan (``core.plan``) fuses blobs that arrive *together* in
one call.  A serving workload does not arrive together: requests trickle in
per-tensor from many producers, and decoding each on arrival reproduces the
few-streams provisioning pathology of paper Fig. 1a launch by launch — every
dispatch carries one blob's chunks instead of a saturated stream table.

``DecompressionService`` closes that gap.  Producers submit blobs from any
thread and get a ``concurrent.futures.Future`` back; a single worker thread
coalesces everything that arrives inside an adaptive micro-batching window

  * flush when the window holds ``max_batch_blobs`` blobs, or
  * flush when ``max_delay_ms`` has elapsed since the window opened, or
  * flush early when the queue goes idle for ``idle_ms`` (adaptive part:
    a burst is fused whole, a lone straggler is not held hostage),

builds ONE fused chunk table per ``(codec, width, chunk_elems, bits)`` group
per window (``format.concat_blobs``), and resolves each request's future
from the scattered rows.  Concurrent same-group requests therefore share a
single engine dispatch — dispatch amplification < 1.0 vs. per-blob decode.

In front of the dispatch path sits a decoded-blob LRU cache keyed by a
content digest of the compressed payload (``blob_digest``) and bounded by a
byte budget; repeated blobs (hot shards, shared embedding planes) resolve
without touching the engine.  Identical blobs inside one window are deduped
into a single decode as well.

    svc = DecompressionService(max_batch_blobs=64, max_delay_ms=2.0)
    fut = svc.submit(blob)           # any thread
    out = fut.result()               # decoded ndarray, bit-exact
    svc.stats()                      # blobs/window, dispatches/window,
                                     # cache hit rate, p50/p99 latency
    svc.close()                      # graceful: drains, then joins

``api.decompress_many`` routes through a process-wide default service
(``default_service()``); ``checkpoint.restore(..., service=)`` and
``data.pipeline.CompressedLoader(service=)`` opt consumers in explicitly.
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import format as fmt
from repro.core import plan as plan_mod
from repro.core import transfers
from repro.core.engine import CodagEngine, EngineConfig

_CLOSE = object()          # queue sentinel; nothing is enqueued after it

# Moved to core/format.py (the plan executor's staging caches need them
# too); re-exported here for compatibility — same objects, one definition.
blob_digest = fmt.blob_digest
pad_table_to_bucket = fmt.pad_table_to_bucket


class _LRUCache:
    """Byte-budgeted LRU of decoded ndarrays. Not thread-safe on its own —
    the service touches it from the worker thread under the service lock."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._entries: "collections.OrderedDict[str, np.ndarray]" = \
            collections.OrderedDict()
        self.bytes = 0

    def get(self, key: str) -> Optional[np.ndarray]:
        arr = self._entries.get(key)
        if arr is not None:
            self._entries.move_to_end(key)
        return arr

    def put(self, key: str, arr: np.ndarray) -> None:
        if arr.nbytes > self.max_bytes:
            return
        if key in self._entries:
            # content-keyed: the stored value is identical, but a re-put is
            # a use — refresh recency so hot digests don't age out as cold.
            self._entries.move_to_end(key)
            return
        stored = arr.copy()          # private copy: callers may mutate theirs
        stored.flags.writeable = False
        self._entries[key] = stored
        self.bytes += stored.nbytes
        while self.bytes > self.max_bytes and self._entries:
            _, evicted = self._entries.popitem(last=False)
            self.bytes -= evicted.nbytes

    def __len__(self) -> int:
        return len(self._entries)


@dataclasses.dataclass
class _Request:
    blob: fmt.CompressedBlob
    future: Future
    t_submit: float
    # content digest, precomputed on the producer thread when the cache is
    # on (hashing parallelizes across producers; the worker stays on the
    # dispatch path).  None when the cache is off — the worker then dedupes
    # by blob object identity instead of content.
    digest: Optional[str] = None
    # resolve with a device-resident jax array instead of a host ndarray
    # (the decoded-blob cache keeps host bytes either way and hands device
    # requesters a view of them on a hit).
    device: bool = False


@dataclasses.dataclass(frozen=True)
class ServiceStats:
    """Cumulative snapshot; rates/percentiles derived at snapshot time."""

    windows: int
    blobs: int
    dispatches: int
    cache_hits: int
    cache_misses: int
    errors: int
    cache_bytes: int
    blobs_per_window: float
    dispatches_per_window: float
    cache_hit_rate: float
    latency_p50_ms: float
    latency_p99_ms: float
    # per-device dispatch accounting (multi-device services only): device
    # string -> fused dispatches scheduled onto it by the round-robin
    # group→device assignment.  Empty for single-device services.
    device_dispatches: Dict[str, int] = dataclasses.field(
        default_factory=dict)

    @property
    def dispatch_amplification(self) -> float:
        """Engine dispatches per submitted blob; < 1.0 means coalescing wins
        over the one-dispatch-per-blob baseline."""
        return self.dispatches / max(1, self.blobs)


class DecompressionService:
    """Micro-batching decode front-end; see module docstring.

    Parameters
    ----------
    engine:           the CodagEngine every fused dispatch runs on.
    max_batch_blobs:  flush the window once it holds this many blobs.  An
                      atomic ``submit_many`` larger than this stays whole.
    max_delay_ms:     hard latency bound — flush this long after the first
                      blob of the window arrived even if requests keep
                      trickling in.
    idle_ms:          flush early once the queue has been idle this long
                      (<= max_delay_ms).  Small values favor latency, values
                      equal to ``max_delay_ms`` favor coalescing.
    cache_bytes:      decoded-blob LRU budget; 0 disables the cache.
    bucket_shapes:    pad fused tables to power-of-two buckets
                      (``pad_table_to_bucket``) so steady-state windows hit
                      the jit cache instead of recompiling.  Costs up to 2x
                      zero rows per dispatch; disable for exact per-call
                      dispatch geometry (the default service disables both
                      this and the cache, for ``decompress_many``'s
                      one-shot batches).
    bucket_cols_floor: explicit minimum pow2 column bucket for fused
                      window tables; None consults the tuned-defaults
                      table (``core.tuning``), falling back to 128.
    compile_cache:    wire up jax's persistent compilation cache via
                      ``tuning.enable_compile_cache`` — True for the
                      default directory, or a path.  A restarted replica
                      then loads its decode kernels from disk instead of
                      recompiling them.
    devices:          optional list of ``jax.Device``s — each window's
                      fused group dispatches are assigned round-robin
                      across them (group i → device (rr+i) mod N), with
                      per-device dispatch counts in ``ServiceStats``.  A
                      mesh of decompressors behind one submit queue; None
                      keeps the single default device.
    store:            optional ``core.store.TieredBlobStore`` — the lower
                      tiers behind this service's decoded-blob LRU (which
                      becomes the store's TIER 0).  ``submit_key(key)``
                      then resolves a request for a blob that is NOT in
                      host memory by demand-paging it through the store's
                      host-cache/backend tiers (on the store's prefetch
                      pool — the service worker never blocks on I/O) and
                      decoding on arrival; repeats hit the decoded cache.
    latency_window:   how many recent request latencies feed p50/p99.
    """

    def __init__(self, engine: Optional[CodagEngine] = None, *,
                 max_batch_blobs: int = 64, max_delay_ms: float = 2.0,
                 idle_ms: Optional[float] = None,
                 cache_bytes: int = 32 << 20,
                 bucket_shapes: bool = True,
                 bucket_cols_floor: Optional[int] = None,
                 compile_cache=None,
                 devices: Optional[Sequence] = None,
                 store=None,
                 latency_window: int = 4096):
        if max_batch_blobs < 1:
            raise ValueError("max_batch_blobs must be >= 1")
        if compile_cache:
            # persistent jit cache: a replica restart reloads its decode
            # kernels from disk instead of re-paying XLA compilation.
            # True = the default cache dir; a path pins the location.
            from repro.core import tuning
            tuning.enable_compile_cache(
                None if compile_cache is True else compile_cache)
        self.engine = engine or CodagEngine(EngineConfig())
        self.max_batch_blobs = int(max_batch_blobs)
        self.max_delay_ms = float(max_delay_ms)
        self.idle_ms = min(float(idle_ms if idle_ms is not None else 0.5),
                           self.max_delay_ms) if max_delay_ms > 0 else 0.0
        self.bucket_shapes = bool(bucket_shapes)
        # explicit pow2-bucketing column floor; None = consult the tuned
        # defaults inside pad_table_to_bucket (historical 128 fallback)
        self.bucket_cols_floor = bucket_cols_floor
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._closed = False
        self._cache = _LRUCache(cache_bytes) if cache_bytes > 0 else None
        self._latencies: "collections.deque[float]" = collections.deque(
            maxlen=latency_window)
        self.store = store
        if store is not None:
            store.attach_tier0(self)   # store.stats() surfaces tier-0 LRU
        self._devices = list(devices) if devices else []
        self._rr = 0                       # round-robin device cursor
        self._device_dispatches: Dict[str, int] = {}
        self._windows = 0
        self._blobs = 0
        self._dispatches = 0
        self._cache_hits = 0
        self._cache_misses = 0
        self._errors = 0
        self._worker = threading.Thread(target=self._worker_loop,
                                        name="codag-decomp-service",
                                        daemon=True)
        self._worker.start()

    # ------------------------------------------------------------- submit

    def submit(self, blob: fmt.CompressedBlob,
               device_out: bool = False) -> Future:
        """Enqueue one blob; returns a Future of the decoded array.

        ``device_out=True`` resolves the future with a device-resident jax
        array: decode + reassembly stay on device, and a cache hit hands
        out a device view of the cached host bytes."""
        return self.submit_many([blob], device_out=device_out)[0]

    def submit_many(self, blobs: Sequence[fmt.CompressedBlob],
                    device_out: bool = False) -> List[Future]:
        """Enqueue blobs ATOMICALLY: they enter the same window together
        (a window may grow past ``max_batch_blobs`` to keep a batch whole)."""
        if not blobs:
            return []
        now = time.perf_counter()
        reqs = [_Request(b, Future(), now,
                         blob_digest(b) if self._cache is not None else None,
                         device=device_out)
                for b in blobs]
        with self._lock:
            if self._closed:
                raise RuntimeError("DecompressionService is closed")
            # put under the lock so close() cannot interleave its sentinel
            # in front of us (anything after the sentinel would never drain).
            self._q.put(reqs)
        return [r.future for r in reqs]

    def submit_array(self, ca, device_out: bool = False) -> Future:
        """Enqueue a ``api.CompressedArray``; the future resolves to the
        recombined logical array (lo/hi planes joined for 8-byte dtypes)."""
        futs = self.submit_many(list(ca.blobs), device_out=device_out)
        out: Future = Future()
        pending = [len(futs)]
        lk = threading.Lock()
        combine = (fmt.combine_planes_device if device_out
                   else fmt.combine_planes)

        def _done(_):
            with lk:
                pending[0] -= 1
                if pending[0]:
                    return
            try:
                outs = [f.result() for f in futs]
                out.set_result(combine(outs, ca.orig_dtype, ca.orig_shape))
            except BaseException as e:  # propagate any blob failure
                out.set_exception(e)

        for f in futs:
            f.add_done_callback(_done)
        return out

    def submit_key(self, key: str, device_out: bool = False) -> Future:
        """Enqueue a blob BY STORE KEY: a decoded-cache miss for bytes that
        aren't even in host RAM resolves through the tiered store instead
        of failing — the store demand-pages the compressed payload
        (tier-1 host cache, else backend fetch on the store's pool), and
        the decode is submitted the moment the payload lands.  The payload
        may be a single ``CompressedBlob`` or a pickled
        ``api.CompressedArray`` (plane blobs recombined).  Requires
        ``store=`` at construction."""
        if self.store is None:
            raise RuntimeError("submit_key requires DecompressionService"
                               "(store=...): no lower tiers to page from")
        out: Future = Future()

        def _paged(fut: Future) -> None:
            try:
                obj = fut.result()
            except BaseException as e:     # missing key / corrupt payload
                out.set_exception(e)
                return
            try:
                inner = (self.submit_array(obj, device_out=device_out)
                         if hasattr(obj, "blobs")
                         else self.submit(obj, device_out=device_out))
            except BaseException as e:     # service closed, bad payload
                out.set_exception(e)
                return

            def _done(f: Future) -> None:
                try:
                    out.set_result(f.result())
                except BaseException as e:
                    out.set_exception(e)

            inner.add_done_callback(_done)

        self.store.fetch_async(key).add_done_callback(_paged)
        return out

    def decode(self, blob: fmt.CompressedBlob, device_out: bool = False):
        """Blocking single-blob convenience."""
        return self.submit(blob, device_out=device_out).result()

    def decode_arrays(self, cas: Sequence,
                      device_out: bool = False) -> List:
        """Blocking batch decode of ``CompressedArray``s.  All plane blobs of
        all arrays enter one window atomically, so the call costs exactly one
        dispatch per group key (same accounting as ``plan.DecodePlan``)."""
        flat = [b for ca in cas for b in ca.blobs]
        futs = self.submit_many(flat, device_out=device_out)
        outs = [f.result() for f in futs]
        combine = (fmt.combine_planes_device if device_out
                   else fmt.combine_planes)
        result, i = [], 0
        for ca in cas:
            n = len(ca.blobs)
            result.append(combine(outs[i:i + n], ca.orig_dtype,
                                  ca.orig_shape))
            i += n
        return result

    # ----------------------------------------------------------- lifecycle

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: refuse new submits, drain every queued request
        (all outstanding futures resolve), then join the worker.

        Returns True once the worker has exited; False if the drain was
        still running when ``timeout`` elapsed (the shutdown keeps
        progressing in the background — call again to keep waiting)."""
        with self._lock:
            if not self._closed:
                self._closed = True
                self._q.put(_CLOSE)
        self._worker.join(timeout)
        return not self._worker.is_alive()

    def __enter__(self) -> "DecompressionService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # --------------------------------------------------------------- stats

    def stats(self) -> ServiceStats:
        with self._lock:
            lats = sorted(self._latencies)
            windows, blobs = self._windows, self._blobs
            dispatches = self._dispatches
            hits, misses = self._cache_hits, self._cache_misses
            errors = self._errors
            cache_bytes = self._cache.bytes if self._cache else 0
            device_dispatches = dict(self._device_dispatches)

        def pct(p: float) -> float:
            if not lats:
                return 0.0
            return lats[min(len(lats) - 1, int(p * (len(lats) - 1)))] * 1e3

        return ServiceStats(
            windows=windows, blobs=blobs, dispatches=dispatches,
            cache_hits=hits, cache_misses=misses, errors=errors,
            cache_bytes=cache_bytes,
            blobs_per_window=blobs / max(1, windows),
            dispatches_per_window=dispatches / max(1, windows),
            cache_hit_rate=hits / max(1, hits + misses),
            latency_p50_ms=pct(0.50), latency_p99_ms=pct(0.99),
            device_dispatches=device_dispatches)

    # -------------------------------------------------------------- worker

    def _worker_loop(self) -> None:
        while True:
            item = self._q.get()
            if item is _CLOSE:
                break
            window: List[_Request] = list(item)
            deadline = time.perf_counter() + self.max_delay_ms / 1e3
            closing = False
            while len(window) < self.max_batch_blobs:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=min(remaining,
                                                  self.idle_ms / 1e3))
                except queue.Empty:
                    break                        # queue idle — flush early
                if nxt is _CLOSE:
                    closing = True
                    break
                window.extend(nxt)
            try:
                self._process_window(window)
            except BaseException as e:   # the worker must survive anything:
                # a dead worker would hang every outstanding & future request
                for req in window:
                    if not req.future.done():
                        self._fail(req, e)
            if closing:
                break

    def _resolve(self, req: _Request, value: np.ndarray) -> None:
        with self._lock:
            self._latencies.append(time.perf_counter() - req.t_submit)
        try:
            req.future.set_result(value)
        except BaseException:            # future cancelled by the caller
            pass

    def _fail(self, req: _Request, exc: BaseException) -> None:
        with self._lock:
            self._errors += 1
            self._latencies.append(time.perf_counter() - req.t_submit)
        try:
            req.future.set_exception(exc)
        except BaseException:            # future cancelled by the caller
            pass

    def _process_window(self, window: List[_Request]) -> None:
        """One micro-batch: cache/dedupe pass, then one ``DecodePlan`` per
        group key — the same parse/group → stage → dispatch → reassemble
        pipeline every other entry path runs (``core.plan``), with the
        service's bucketing applied at plan build.  Building per group
        (rather than one window-wide plan) keeps failures isolated to the
        request (bad metadata) or the group (unlowerable blobs, decode
        error) that caused them.

        With ``devices`` configured, the plan's fused group dispatches are
        assigned round-robin across them — per-window multi-device
        scheduling; each group's table is staged on and decoded by its
        assigned device, and per-device dispatch counts land in
        ``ServiceStats.device_dispatches``.

        Results are served in the shape each request asked for: host
        ndarrays, or device-resident jax arrays (``device_out`` submits).
        The decode itself always stays on device; the host matrix is
        materialized at most ONCE per group, and only when some requester
        (or the cache) actually needs host bytes — an all-device window on
        a cache-less service performs zero device→host transfers."""
        import jax.numpy as jnp

        hits = misses = dispatches = 0
        device_dispatches: Dict[str, int] = {}
        # dedupe identical payloads in-window (by content digest with the
        # cache on, by blob identity without); order is preserved so the
        # plan's groups follow first-occurrence order.
        unique: "collections.OrderedDict[object, List[_Request]]" = \
            collections.OrderedDict()
        for req in window:
            try:
                fmt.group_key(req.blob)   # metadata sanity (bad codec etc.)
            except Exception as e:
                self._fail(req, e)
                continue
            dedupe_key = req.digest if req.digest is not None \
                else id(req.blob)
            cached = (self._cache.get(req.digest)
                      if self._cache is not None else None)
            if cached is not None:
                hits += 1
                # cache keeps host bytes; device requesters get a device
                # view of them (read-only, so no defensive copy needed)
                self._resolve(req, jnp.asarray(cached) if req.device
                              else cached.copy())
                continue
            misses += 1
            unique.setdefault(dedupe_key, []).append(req)

        # order reps into key groups (first-occurrence order, same as the
        # plan's parse/group stage); each group lowers to its OWN one-group
        # DecodePlan inside the per-group try, so an unlowerable group
        # (corrupt extras, impossible metadata) fails alone.
        by_key: "Dict[tuple, List[List[_Request]]]" = {}
        for reqs in unique.values():
            by_key.setdefault(fmt.group_key(reqs[0].blob), []).append(reqs)
        for key, group_reqs in by_key.items():
            device = None
            if self._devices:
                device = self._devices[self._rr % len(self._devices)]
                self._rr += 1
            need_host = self._cache is not None or any(
                not r.device for reqs in group_reqs for r in reqs)
            try:
                plan = plan_mod.DecodePlan.build(
                    [reqs[0].blob for reqs in group_reqs],
                    bucket=self.bucket_shapes,
                    bucket_floor=self.bucket_cols_floor)
                (g,) = plan.groups          # one key -> one fused group
                table_dev = plan.decode_group_device(
                    0, self.engine, device=device)
                table = (transfers.to_host(table_dev) if need_host
                         else None)
                dispatches += 1
                if device is not None:
                    k = str(device)
                    device_dispatches[k] = device_dispatches.get(k, 0) + 1
            except Exception as e:
                for reqs in group_reqs:
                    for req in reqs:
                        self._fail(req, e)
                continue
            for bid, row0 in zip(g.blob_ids, g.row_offsets):
                reqs = group_reqs[bid]
                blob = reqs[0].blob
                row = row0 + blob.num_chunks
                out = out_dev = None
                try:
                    if need_host:
                        out = fmt.reassemble(blob,
                                             table[row0:row].copy())
                    if any(r.device for r in reqs):
                        out_dev = fmt.reassemble_device(
                            blob, table_dev[row0:row])
                except Exception as e:   # bad per-blob metadata fails alone
                    for req in reqs:
                        self._fail(req, e)
                    continue
                if self._cache is not None and reqs[0].digest is not None:
                    self._cache.put(reqs[0].digest, out)   # put() copies
                first_host = True
                for req in reqs:
                    if req.device:
                        # jax arrays are immutable — duplicates share one
                        self._resolve(req, out_dev)
                    else:
                        self._resolve(req, out if first_host else out.copy())
                        first_host = False

        with self._lock:
            self._windows += 1
            self._blobs += len(window)
            self._dispatches += dispatches
            self._cache_hits += hits
            self._cache_misses += misses
            for k, v in device_dispatches.items():
                self._device_dispatches[k] = \
                    self._device_dispatches.get(k, 0) + v


# Process-wide default service (``api.decompress_many`` routes through it).
_default_service: Optional[DecompressionService] = None
_default_lock = threading.Lock()


def default_service() -> DecompressionService:
    """The lazily-created shared service.  Recreated transparently if a
    previous one was closed.  ``bucket_shapes`` AND the cache stay off here
    so one-shot ``api.decompress_many`` batches keep exact, call-local
    dispatch accounting (one dispatch per group, every time — no hidden
    process-wide memory of earlier calls); long-lived serving paths should
    construct their own service with bucketing + cache on."""
    global _default_service
    with _default_lock:
        if _default_service is None or _default_service.closed:
            _default_service = DecompressionService(bucket_shapes=False,
                                                    cache_bytes=0)
        return _default_service
