"""jit'd dispatch layer over the decompression kernels.

Backends:
  "xla"    — the per-codec chunk bodies vmapped across chunks and compiled
             by XLA: the decode path on every platform, the TPU included.
  "pallas" — pl.pallas_call kernels (``interpret=None`` resolves to the
             interpreter on the CPU backend and to Mosaic elsewhere).
  "oracle" — the sequential stream-based reference decoders.

Dispatch is pure registry lookup: ``registry.get(codec).decode`` is a
``kernels.harness.DecodeSpec`` carrying all three backends' bodies, so this
module names no codec.  All entry points take the device pytree from
``CompressedBlob.to_device()`` plus the blob's static metadata, and return
(num_chunks, chunk_elems) in the codec's device dtype.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Dict, Optional

import jax

from repro.core import format as fmt
from repro.core import registry
from repro.core import transfers
from repro.kernels import harness
from repro.kernels.harness import Epilogue  # noqa: F401  (public alias)
from repro.kernels.harness import words_view  # noqa: F401  (public alias)

BACKENDS = ("xla", "pallas", "oracle")


@functools.partial(jax.jit, static_argnames=("codec", "width", "chunk_elems",
                                             "backend", "interpret", "bits",
                                             "epilogue", "tune"))
def _decode_impl(dev: Dict[str, Any], *, codec: str, width: int,
                 chunk_elems: int, backend: str, interpret: bool,
                 bits: int, epilogue, tune) -> jax.Array:
    with jax.named_scope(f"codag.decode.{codec}"):
        return harness.run(registry.get(codec).decode, dev, width=width,
                           chunk_elems=chunk_elems, backend=backend,
                           interpret=interpret, bits=bits, epilogue=epilogue,
                           tune=tune)


# Dispatch observers (``count_dispatches``).  A plain list-of-lists instead
# of rebinding the module attribute: nested/overlapping contexts each get
# every dispatch, and exiting one never clobbers another.  Dispatches may be
# issued from worker threads (the DecompressionService), so registration,
# unregistration, and the record fan-out all happen under one lock.
_observers: list = []
_observers_lock = threading.Lock()


def decode(dev: Dict[str, Any], *, codec: str, width: int, chunk_elems: int,
           backend: str = "xla", interpret: Optional[bool] = None,
           bits: int = 0, epilogue=None, tune=None) -> jax.Array:
    """Decode every chunk. Returns (num_chunks, chunk_elems) device array.

    ``epilogue``: optional ``harness.Epilogue`` fused into the dispatch
    (cast / widen / dequant applied before the matrix ever exists for the
    consumer); overrides the codec's registered default epilogue.

    ``tune``: sorted kernel-knob tuple (jit-static; see ``core.tuning``).
    ``None`` resolves the tuned defaults for ``(codec, width)`` on the
    current device — callers that trace this function inside an outer jit
    (the plan executors) must resolve and pass it explicitly instead, so a
    swapped tuning table can never silently reuse a stale compilation.
    """
    if tune is None:
        from repro.core import tuning
        tune = tuning.kernel_tune(codec, width)
    interpret = harness.resolve_interpret(interpret)
    # Observer fan-out happens entirely under the lock: the old pattern
    # (truthiness check outside, iteration inside) was a TOCTOU — a context
    # registered between check and fan-out saw a dispatch-count of zero for
    # a dispatch issued strictly inside it, and one unregistered in that
    # window could still be appended to after its context closed.
    with _observers_lock:
        if _observers:
            rec = {"num_chunks": int(dev["comp"].shape[0]), "codec": codec,
                   "width": width, "chunk_elems": chunk_elems,
                   "backend": backend, "interpret": interpret, "bits": bits}
            for calls in _observers:
                calls.append(dict(rec))
    return _decode_impl(dev, codec=codec, width=width,
                        chunk_elems=chunk_elems, backend=backend,
                        interpret=interpret, bits=bits, epilogue=epilogue,
                        tune=tune)


@contextlib.contextmanager
def count_dispatches():
    """Observe python-level ``decode`` dispatches (= kernel launches issued).

    Yields a list that grows one entry per call, with the static decode
    kwargs plus the table's chunk count.  Reentrant: contexts may nest or
    overlap arbitrarily — each active context records every dispatch issued
    while it is open, and closing one leaves the others intact.
    """
    calls: list = []
    with _observers_lock:
        _observers.append(calls)
    try:
        yield calls
    finally:
        # remove by identity: two open contexts may hold equal-valued lists
        with _observers_lock:
            for i, obs in enumerate(_observers):
                if obs is calls:
                    del _observers[i]
                    break


def table_inputs(table: fmt.CompressedBlob, placement=None):
    """(device pytree, static decode bits) for a blob / merged chunk table.

    ``placement``: optional ``jax.Device`` / ``jax.sharding.Sharding`` the
    staged tables should live under (multi-device schedulers stage per
    device).  All uploads go through the ``transfers.to_device`` funnel so
    staging traffic is countable."""
    dev = {k: transfers.to_device(v, placement)
           for k, v in table.to_device().items()}
    return dev, registry.get(table.codec).static_bits(table)


def decode_table_device(table: fmt.CompressedBlob, backend: str = "xla",
                        interpret: Optional[bool] = None,
                        epilogue=None) -> jax.Array:
    """Decode a flat chunk table with ONE dispatch; result stays on device.

    ``table`` may be a single blob or a multi-blob merge from
    ``format.concat_blobs`` (the batch scheduler's stream table): every row
    is an independent stream regardless of which blob it came from.  Returns
    the raw (num_chunks, chunk_elems) device matrix; callers that own a
    blob→row mapping scatter it back themselves
    (``format.reassemble_device``).
    """
    dev, bits = table_inputs(table)
    return decode(dev, codec=table.codec, width=table.width,
                  chunk_elems=table.chunk_elems, backend=backend,
                  interpret=interpret, bits=bits, epilogue=epilogue)


def decode_table(table: fmt.CompressedBlob, backend: str = "xla",
                 interpret: Optional[bool] = None):
    """Host variant of :func:`decode_table_device`: one dispatch, then one
    sanctioned device→host materialization (``transfers.to_host``)."""
    return transfers.to_host(decode_table_device(table, backend, interpret))


def decode_blob(blob: fmt.CompressedBlob, backend: str = "xla",
                interpret: Optional[bool] = None):
    """Host convenience: decode a CompressedBlob back to the original array."""
    return fmt.reassemble(blob, decode_table(blob, backend, interpret))
