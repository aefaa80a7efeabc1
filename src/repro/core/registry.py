"""Codec plugin registry — the framework claim of paper §IV-A, made literal.

CODAG's software contribution is that a decompressor is a *framework*: the
reader, group-table, and all-thread expansion machinery are shared, and a
codec author supplies only a header parse and a value expression.  This
module is the single place where a codec declares everything the rest of the
system needs:

  * ``encode``        — the host-side encoder (array -> ``CompressedBlob``)
  * ``decode``        — a ``kernels.harness.DecodeSpec`` covering the three
                        backends (xla / pallas / oracle)
  * ``needs_words``   — whether the device layout carries a uint32 word view
                        (bit-oriented codecs)
  * ``shared_extras`` — extras keys shared across blobs of a batch group
                        (everything else is a per-chunk table and is stacked
                        row-wise by ``format.concat_blobs``)
  * ``static_bits``   — the codec's static decode parameter, part of the
                        batch-scheduler group key
  * ``byte_stream``   — the codec consumes raw bytes (consumers may view any
                        dtype as uint8 before encoding, e.g. checkpoints)
  * ``plane_decompose_64`` — 8-byte dtypes should be split into lo/hi uint32
                        planes before encoding (keeps runs / value locality)
  * ``demo_data``     — a generator of codec-appropriate compressible data
                        (drives the bench matrices and smoke tests)
  * ``count_groups``  — optional host-side header walk counting compressed
                        groups in one chunk row (Table V symbol lengths)

``ops.decode``, ``encoders.compress``, ``format.group_key`` /
``concat_blobs`` / ``to_device``, the batch scheduler, checkpointing, and
the benchmarks all dispatch through this table; none of them name a codec.

Adding a codec == writing one plugin module that calls ``register()`` (see
``kernels/dbp.py`` for the canonical example) and listing it in
``_PLUGINS`` (or importing it yourself before use).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np


def _no_bits(blob: Any) -> int:
    return 0


@dataclasses.dataclass(frozen=True)
class Codec:
    """Everything one codec contributes to the framework."""

    name: str
    # (arr, chunk_bytes, *, bits=None) -> format.CompressedBlob
    encode: Callable[..., Any]
    # kernels.harness.DecodeSpec (opaque here: core must not import kernels)
    decode: Any
    needs_words: bool = False
    shared_extras: Tuple[str, ...] = ()
    byte_stream: bool = False
    plane_decompose_64: bool = False
    static_bits: Callable[[Any], int] = _no_bits
    # (n_elems, rng) -> np.ndarray of codec-appropriate compressible data
    demo_data: Optional[Callable[[int, Any], np.ndarray]] = None
    # (comp_row: np.ndarray, width: int) -> group count for one chunk
    count_groups: Optional[Callable[[np.ndarray, int], int]] = None


_REGISTRY: Dict[str, Codec] = {}

# Built-in plugin modules; each registers its Codec on import.  Third-party
# codecs simply call register() from their own module instead.
_PLUGINS: Dict[str, str] = {
    "rle_v1": "repro.kernels.rle_v1",
    "rle_v2": "repro.kernels.rle_v2",
    "tdeflate": "repro.kernels.tdeflate",
    "bitpack": "repro.kernels.bitpack",
    "dbp": "repro.kernels.dbp",
    "huffman": "repro.kernels.huffman",
    "lzss": "repro.kernels.lzss",
}


def register(codec: Codec) -> Codec:
    """Register (or replace) a codec. Returns it, so plugins can keep a ref."""
    _REGISTRY[codec.name] = codec
    return codec


def get(name: str) -> Codec:
    """Look up a codec, lazily importing its built-in plugin module."""
    codec = _REGISTRY.get(name)
    if codec is None and name in _PLUGINS:
        importlib.import_module(_PLUGINS[name])
        codec = _REGISTRY.get(name)
    if codec is None:
        raise ValueError(
            f"unknown codec {name!r}; registered: {sorted(set(_REGISTRY) | set(_PLUGINS))}")
    return codec


def names() -> Tuple[str, ...]:
    """All registered codec names (built-in plugins force-loaded first)."""
    for name in _PLUGINS:
        if name not in _REGISTRY:
            importlib.import_module(_PLUGINS[name])
    return tuple(_REGISTRY)
