"""Compressed token store + input pipeline.

Token shards are stored codec-compressed (RLE v2 by default — token streams
from natural corpora have heavy repetition/locality) and decompressed ON
DEVICE by the CODAG engine before each train step: the paper's data-analytics
pipeline pattern (§I — "read compressed data into GPU memory, run a
decompression kernel, then the query") transplanted to the training input
path.

The loader double-buffers host->device transfer of chunk i+1 against the
decode of chunk i via an async prefetch thread, mirroring the engine-level
latency-hiding story.
"""
from __future__ import annotations

import collections
import queue
import threading
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import plan as cplan
from repro.core import store as blobstore
from repro.core.engine import CodagEngine, EngineConfig
from repro.core.server import DecompressionService


def synthetic_corpus(n_tokens: int, vocab: int, seed: int = 0,
                     run_bias: float = 0.3) -> np.ndarray:
    """Zipf-distributed tokens with run/locality structure (compressible,
    like real BPE streams — frequent tokens + repeated n-grams)."""
    rng = np.random.default_rng(seed)
    base = rng.zipf(1.3, size=n_tokens)
    tokens = np.minimum(base - 1, vocab - 1).astype(np.uint32)
    # inject runs (repeated tokens / copied spans) for realism
    n_runs = int(n_tokens * run_bias / 8)
    starts = rng.integers(0, max(1, n_tokens - 16), n_runs)
    for s in starts:
        l = int(rng.integers(2, 9))
        tokens[s:s + l] = tokens[s]
    return tokens


class CompressedTokenStore:
    """Store of codec-compressed token shards: in-memory, or spilled to a
    ``core.store.TieredBlobStore`` (``build(spill_dir=...)``) and
    demand-paged back with lookahead prefetch — a corpus larger than host
    RAM streams through a bounded compressed-shard cache."""

    def __init__(self, blobs: List[fmt.CompressedBlob], vocab: int, *,
                 store: Optional[blobstore.TieredBlobStore] = None,
                 keys: Optional[List[str]] = None,
                 shard_meta: Optional[List[tuple]] = None):
        self.blobs = blobs
        self.vocab = vocab
        self._store = store
        self._keys = list(keys or [])
        # (compressed_bytes, uncompressed_bytes) per spilled shard, so
        # ratio/accounting never page anything back in
        self._meta = list(shard_meta or [])

    @classmethod
    def build(cls, tokens: np.ndarray, vocab: int,
              shard_tokens: int = 1 << 20,
              codec: str = fmt.RLE_V2,
              chunk_bytes: int = 64 * 1024,
              spill_dir: Optional[str] = None,
              host_budget_bytes: int = 64 << 20,
              prefetch_workers: int = 4) -> "CompressedTokenStore":
        """``spill_dir=None`` keeps every compressed shard in host RAM.
        With a ``spill_dir``, shards are written through a
        ``TieredBlobStore`` (atomic one-file-per-shard) and the store
        demand-pages them back on access, keeping at most
        ``host_budget_bytes`` of compressed shards resident."""
        shard_arrays = (tokens[i:i + shard_tokens].astype(np.uint32)
                        for i in range(0, len(tokens), shard_tokens))
        if spill_dir is None:
            return cls([enc.compress(s, codec, chunk_bytes)
                        for s in shard_arrays], vocab)
        st = blobstore.filesystem_store(
            spill_dir, host_budget_bytes=host_budget_bytes,
            prefetch_workers=prefetch_workers)
        keys, meta = [], []
        for si, s in enumerate(shard_arrays):
            b = enc.compress(s, codec, chunk_bytes)
            key = f"shard_{si:06d}.blob"
            st.put(key, b)               # write-through; not cached (admit
            keys.append(key)             # happens on first read access)
            meta.append((b.compressed_bytes, b.uncompressed_bytes))
        return cls([], vocab, store=st, keys=keys, shard_meta=meta)

    @property
    def spilled(self) -> bool:
        return self._store is not None

    @property
    def store(self) -> Optional[blobstore.TieredBlobStore]:
        """The backing ``TieredBlobStore`` (spilled mode only)."""
        return self._store

    @property
    def num_shards(self) -> int:
        return len(self._keys) if self.spilled else len(self.blobs)

    def blob(self, i: int) -> fmt.CompressedBlob:
        """Shard ``i``'s compressed blob; demand-paged in spilled mode."""
        if self.spilled:
            return self._store.get(self._keys[i])
        return self.blobs[i]

    def prefetch_shards(self, lo: int, hi: int) -> None:
        """Async lookahead: schedule shards ``[lo, hi)`` for paging-in
        (no-op for the in-memory store)."""
        if self.spilled:
            self._store.prefetch(self._keys[max(0, lo):hi])

    def _blob_windows(self, window: int,
                      lookahead: int = 1) -> Iterator[List[fmt.CompressedBlob]]:
        """Shard blobs in windows; spilled mode overlaps the next window's
        paging with the consumer's decode of the current one
        (``TieredBlobStore.stream_windows``) and releases consumed windows
        back under the host budget."""
        if not self.spilled:
            for i in range(0, len(self.blobs), window):
                yield self.blobs[i:i + window]
            return
        yield from self._store.stream_windows(self._keys, window=window,
                                              lookahead=lookahead)

    @property
    def ratio(self) -> float:
        if self.spilled:
            c = sum(m[0] for m in self._meta)
            u = sum(m[1] for m in self._meta)
        else:
            c = sum(b.compressed_bytes for b in self.blobs)
            u = sum(b.uncompressed_bytes for b in self.blobs)
        return c / max(1, u)

    def decoded_shards(self, engine: CodagEngine, window: int = 1,
                       device_out: bool = False,
                       mesh=None) -> Iterator[np.ndarray]:
        """Decode shards; ``window`` > 1 coalesces that many shards' chunks
        into one batched dispatch per codec group (CODAG provisioning) while
        bounding peak host memory to ~window uncompressed shards.
        ``device_out=True`` yields device-resident int32 jax arrays —
        decode, reassembly, and the int32 widening never visit the host.
        ``mesh`` (implies device out) decodes each shard's chunk rows
        across the mesh's data-axis devices and yields token shards BORN
        sharded over that axis (``NamedSharding`` on the token dim) — the
        input pipeline feeds a data-parallel step without a gather."""
        device_out = device_out or mesh is not None
        out_sh = None
        if mesh is not None:
            from repro.distributed import sharding as shd
            out_sh = shd.decode_out_sharding(mesh)
            # the int32 widening is a device op too; re-commit under the
            # data-axis sharding so the yielded shard carries it verbatim
            # (ragged tail shards that cannot satisfy the spec stay put)
            cast = lambda a: (jax.device_put(a.astype(jnp.int32), out_sh)
                              if cplan.placeable(a.shape, out_sh)
                              else a.astype(jnp.int32))
        elif device_out:
            cast = lambda a: a.astype(jnp.int32)
        else:
            cast = lambda a: a.astype(np.int32)
        for blobs in self._blob_windows(max(1, window)):
            for out in cplan.decompress_blobs(
                    blobs, engine,
                    device_out=device_out, mesh=mesh, out_shardings=out_sh):
                yield cast(out)

    def decoded_shards_async(self, service: DecompressionService,
                             lookahead: int = 4,
                             device_out: bool = False) -> Iterator[np.ndarray]:
        """Decode shards through a ``DecompressionService``: keep up to
        ``lookahead`` shard requests in flight and yield results in order.
        The service worker overlaps decode of shard i+1..i+lookahead with
        the consumer's use of shard i (and coalesces the in-flight shards
        into fused dispatches), replacing the loader's ad-hoc prefetch
        thread.  ``device_out=True`` serves device-resident shards."""
        cast = (lambda a: a.astype(jnp.int32)) if device_out \
            else (lambda a: a.astype(np.int32))
        n = self.num_shards
        look = max(1, lookahead)
        futs: "collections.deque" = collections.deque()
        idx = 0
        self.prefetch_shards(0, look)      # prime the paging pipeline
        while idx < n and len(futs) < look:
            self.prefetch_shards(idx + 1, idx + 1 + look)
            futs.append(service.submit(self.blob(idx),
                                       device_out=device_out))
            idx += 1
        while futs:
            out = futs.popleft().result()
            if idx < n:
                # shard idx pages in (hit — its fetch was issued a step
                # ago) while idx+1..idx+look stream in behind it
                self.prefetch_shards(idx + 1, idx + 1 + look)
                futs.append(service.submit(self.blob(idx),
                                           device_out=device_out))
                idx += 1
            yield cast(out)


class CompressedLoader:
    """Batches (tokens, labels) from a CompressedTokenStore with on-device
    decompression and async prefetch.

    Peak decoded-shard buffering is ``decode_window`` (shards fused into one
    batched dispatch, materialized together) plus the prefetch queue's 2 —
    not the single shard of the pre-batching loader.  ``decode_window=1``
    restores the old one-shard-per-dispatch behavior.

    ``service``: decode through a shared ``DecompressionService`` instead of
    a private engine + prefetch thread.  The loader keeps ``decode_window``
    shard requests in flight (``decoded_shards_async``): the service worker
    owns the decode concurrency, coalesces the in-flight shards into fused
    dispatches, and its decoded-blob cache makes repeat epochs over the same
    shards dispatch-free.

    ``device_out``: feed device shards end to end — shards decode to
    device-resident arrays and the batch slicing / vocab clamp are device
    ops, so token data crosses host→device once (the compressed upload) and
    never comes back."""

    def __init__(self, store: CompressedTokenStore, batch: int, seq: int,
                 engine: Optional[CodagEngine] = None, prefetch: bool = True,
                 decode_window: int = 4,
                 service: Optional[DecompressionService] = None,
                 device_out: bool = False, mesh=None):
        if service is not None and mesh is not None:
            raise ValueError("mesh= is not supported with service=: the "
                             "service decodes on its own single-engine "
                             "worker; use the engine path for sharded "
                             "token shards")
        self.store = store
        self.batch = batch
        self.seq = seq
        self.engine = engine or CodagEngine(EngineConfig())
        self.prefetch = prefetch
        # shards whose chunks are fused into one batched decode dispatch
        # (engine mode) or kept in flight on the service (service mode)
        self.decode_window = decode_window
        self.service = service
        # mesh: decode every shard's rows across the mesh's data-axis
        # devices; token shards enter the batch assembly born sharded
        self.mesh = mesh
        self.device_out = device_out or mesh is not None

    def __iter__(self) -> Iterator[Dict[str, jnp.ndarray]]:
        need = self.batch * self.seq + 1
        xp = jnp if self.device_out else np
        buf = xp.zeros(0, xp.int32)

        def shard_iter():
            while True:  # loop over shards forever
                if self.service is not None:
                    yield from self.store.decoded_shards_async(
                        self.service, lookahead=self.decode_window,
                        device_out=self.device_out)
                else:
                    yield from self.store.decoded_shards(
                        self.engine, window=self.decode_window,
                        device_out=self.device_out, mesh=self.mesh)

        src = shard_iter()
        t = None
        stop = threading.Event()
        if self.prefetch and self.service is None:
            q: "queue.Queue" = queue.Queue(maxsize=2)

            def worker():
                # Bounded-timeout puts + a stop flag: when the consumer
                # drops the iterator, the worker exits within one timeout
                # instead of blocking on q.put forever holding a decoded
                # shard (the old leak — one zombie thread per dropped
                # iterator).  Stop is also checked before each decode so
                # shutdown never waits on another shard's dispatch.
                while not stop.is_set():
                    try:
                        s = next(src)
                    except StopIteration:
                        return
                    while not stop.is_set():
                        try:
                            q.put(s, timeout=0.05)
                            break
                        except queue.Full:
                            continue

            t = threading.Thread(target=worker, daemon=True,
                                 name="codag-loader-prefetch")
            t.start()
            get = q.get
        else:
            # service mode: the service worker already decodes ahead of the
            # consumer — no ad-hoc prefetch thread needed.
            get = lambda: next(src)

        try:
            while True:
                while len(buf) < need:
                    buf = xp.concatenate([buf, get()])
                flat = buf[:need]
                buf = buf[need - 1:]
                toks = (flat[:-1].reshape(self.batch, self.seq)
                        % self.store.vocab)
                labs = (flat[1:].reshape(self.batch, self.seq)
                        % self.store.vocab)
                yield {"tokens": jnp.asarray(toks),
                       "labels": jnp.asarray(labs)}
        finally:
            # runs on generator close/GC as well as break/throw: shut the
            # prefetch worker down so no thread outlives its iterator
            if t is not None:
                stop.set()
                try:
                    while True:
                        q.get_nowait()       # unblock a mid-put worker
                except queue.Empty:
                    pass
                t.join(timeout=5.0)
