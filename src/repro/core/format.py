"""CODAG-JAX chunked container format (CJC).

Mirrors the indexed-chunk layout of modern compressed data formats (ORC
stripes / Parquet pages, paper §II-B): the uncompressed stream is split into
fixed-size chunks, each chunk is compressed independently, and an index of
per-chunk offsets/sizes enables chunk-parallel decompression.

TPU adaptation: instead of a byte stream + offset list (pointer-chasing), the
device layout is *rectangular* — a dense ``(num_chunks, max_comp_bytes)``
uint8 matrix plus per-chunk length vectors — so a Pallas grid cell (the
"warp" analog, DESIGN.md §2) can DMA its chunk with a plain BlockSpec.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, Optional

import numpy as np

from repro.core import registry

DEFAULT_CHUNK_BYTES = 128 * 1024  # 128 KiB, same as the paper's evaluation

# Codec registry keys (the authoritative per-codec metadata lives in
# ``repro.core.registry``; these are just the canonical name constants).
RLE_V1 = "rle_v1"
RLE_V2 = "rle_v2"
TDEFLATE = "tdeflate"
BITPACK = "bitpack"
DBP = "dbp"
CODECS = (RLE_V1, RLE_V2, TDEFLATE, BITPACK, DBP)

# Widths supported on device. 8-byte dtypes are transparently viewed as two
# 4-byte lanes (TPUs have no 64-bit vector type; runs of u64 are runs of the
# u32 pair view, so RLE still applies).
SUPPORTED_WIDTHS = (1, 2, 4)


def _as_bytes_view(arr: np.ndarray) -> tuple[np.ndarray, int, np.dtype]:
    """Flatten ``arr`` into a (bytes_view, elem_width, device_dtype) triple."""
    a = np.ascontiguousarray(arr)
    width = a.dtype.itemsize
    if width == 8:  # view u64/f64/i64 as u32 pairs
        a = a.view(np.uint32)
        width = 4
    if width not in SUPPORTED_WIDTHS:
        raise ValueError(f"unsupported element width {width}")
    dev_dtype = {1: np.uint8, 2: np.uint16, 4: np.uint32}[width]
    return a.reshape(-1).view(dev_dtype), width, np.dtype(dev_dtype)


@dataclasses.dataclass
class CompressedBlob:
    """Host-side compressed container (numpy)."""

    codec: str
    width: int                    # bytes per element (1/2/4)
    chunk_elems: int              # uncompressed elements per full chunk
    total_elems: int              # total uncompressed elements
    orig_dtype: str               # dtype string of the original array
    orig_shape: tuple             # original shape (for reconstruction)
    comp: np.ndarray              # (num_chunks, max_comp_bytes) uint8
    comp_lens: np.ndarray         # (num_chunks,) int32 — valid bytes per row
    out_lens: np.ndarray          # (num_chunks,) int32 — elements per chunk
    extras: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    @property
    def num_chunks(self) -> int:
        return int(self.comp.shape[0])

    @property
    def compressed_bytes(self) -> int:
        """True compressed payload size (index + per-chunk bytes), no padding."""
        extra = sum(int(v.nbytes) for k, v in self.extras.items()
                    if k.startswith("hdr_"))
        return int(self.comp_lens.sum()) + extra

    @property
    def uncompressed_bytes(self) -> int:
        return self.total_elems * self.width

    @property
    def ratio(self) -> float:
        """Compression ratio as reported in the paper (comp/uncomp, Table V)."""
        return self.compressed_bytes / max(1, self.uncompressed_bytes)

    def to_device(self, pad_comp_to: Optional[int] = None) -> Dict[str, Any]:
        """Return a pytree of device-layout numpy arrays (jnp-convertible).

        ``pad_comp_to`` optionally rounds max_comp_bytes up (e.g. to a lane
        multiple) so BlockSpecs tile cleanly.
        """
        comp = self.comp
        want = comp.shape[1]
        # Pad so byte loads 4-at-a-time and bitstream peeks never run off the
        # end (Alg. 1's "input buffer holds at least two cache lines").
        want = max(want + 8, pad_comp_to or 0)
        want = int(np.ceil(want / 128) * 128)  # lane-align
        if want != comp.shape[1]:
            comp = np.zeros((comp.shape[0], want), np.uint8)
            comp[:, : self.comp.shape[1]] = self.comp
        out = {
            "comp": comp,
            "comp_lens": self.comp_lens.astype(np.int32),
            "out_lens": self.out_lens.astype(np.int32),
        }
        if registry.get(self.codec).needs_words:
            # bit codecs consume uint32 words (input_stream funnel loads)
            out["comp_words"] = np.ascontiguousarray(comp).view(np.uint32)
        out.update(self.extras)
        return out


def group_key(blob: "CompressedBlob") -> tuple:
    """Batching key: blobs with equal keys share one decode dispatch.

    Everything static to ``ops.decode`` must be in the key — codec, element
    width, chunk geometry, and the codec's own static decode parameter
    (``registry.Codec.static_bits``, e.g. bitpack's bit width).
    """
    bits = registry.get(blob.codec).static_bits(blob)
    return (blob.codec, blob.width, blob.chunk_elems, bits)


def concat_blobs(blobs: list["CompressedBlob"]) -> "CompressedBlob":
    """Merge same-key blobs into one flat chunk table.

    The result is a valid ``CompressedBlob`` whose rows are the chunks of
    every input blob in order, so a single ``ops.decode`` treats each chunk
    from each blob as an independent stream (the CODAG provisioning move:
    one saturated launch instead of N under-provisioned ones).  Callers
    scatter the (total_chunks, chunk_elems) output back per blob by row
    ranges; the merged blob's ``orig_shape`` is a flat placeholder.

    Memory note: every merged row is padded to the group-wide max compressed
    row length, so grouping a near-incompressible blob with well-compressed
    ones inflates the host table toward num_chunks * chunk_bytes.  Callers
    that care bound the batch (``pipeline.decoded_shards(window=)``); if it
    bites at checkpoint scale, sub-bucket groups by comp-row magnitude at
    the cost of extra dispatches.
    """
    if not blobs:
        raise ValueError("concat_blobs needs at least one blob")
    key = group_key(blobs[0])
    for b in blobs[1:]:
        if group_key(b) != key:
            raise ValueError(f"group key mismatch: {group_key(b)} != {key}")
    if len(blobs) == 1:
        return blobs[0]
    max_len = max(b.comp.shape[1] for b in blobs)
    total_chunks = sum(b.num_chunks for b in blobs)
    comp = np.zeros((total_chunks, max_len), np.uint8)
    row = 0
    for b in blobs:
        comp[row:row + b.num_chunks, : b.comp.shape[1]] = b.comp
        row += b.num_chunks
    extras: Dict[str, np.ndarray] = {}
    shared = registry.get(blobs[0].codec).shared_extras
    for k, v0 in blobs[0].extras.items():
        if k in shared:      # group-wide scalars (e.g. bitpack_bits)
            extras[k] = v0
        else:                # per-chunk tables: stack rows
            extras[k] = np.concatenate([b.extras[k] for b in blobs], axis=0)
    total_elems = sum(b.total_elems for b in blobs)
    return CompressedBlob(
        codec=blobs[0].codec,
        width=blobs[0].width,
        chunk_elems=blobs[0].chunk_elems,
        total_elems=int(total_elems),
        orig_dtype=blobs[0].orig_dtype,
        orig_shape=(int(total_elems),),
        comp=comp,
        comp_lens=np.concatenate([b.comp_lens for b in blobs]).astype(np.int32),
        out_lens=np.concatenate([b.out_lens for b in blobs]).astype(np.int32),
        extras=extras,
    )


def pad_table_rows(table: "CompressedBlob", target_rows: int) -> "CompressedBlob":
    """Pad a chunk table to ``target_rows`` with zero-length trailing chunks.

    Padding rows have ``comp_lens == out_lens == 0`` — every decode body
    exits immediately on them, the same convention the engine's block mode
    relies on — and sit at the END of the table so callers' row-range
    scatter is unaffected.  Used by the service's pow2 shape bucketing and
    by the sharded executor's per-device uniform padding (every device of a
    mesh axis must decode the same local row count).
    """
    rows = table.num_chunks
    if target_rows < rows:
        raise ValueError(f"cannot pad {rows} rows down to {target_rows}")
    if target_rows == rows:
        return table
    pad = target_rows - rows
    comp = np.zeros((target_rows, table.comp.shape[1]), np.uint8)
    comp[:rows] = table.comp
    shared = registry.get(table.codec).shared_extras
    extras = {}
    for k, v in table.extras.items():
        if k in shared or v.shape[:1] != (rows,):
            extras[k] = v                    # group-wide scalar/table
        else:                                # per-chunk rows: pad with zeros
            extras[k] = np.concatenate(
                [v, np.zeros((pad,) + v.shape[1:], v.dtype)], axis=0)
    return dataclasses.replace(
        table, comp=comp,
        comp_lens=np.concatenate(
            [table.comp_lens, np.zeros(pad, np.int32)]).astype(np.int32),
        out_lens=np.concatenate(
            [table.out_lens, np.zeros(pad, np.int32)]).astype(np.int32),
        extras=extras)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length() if n > 1 else 1


def pad_table_to_bucket(table: "CompressedBlob",
                        cols_floor: Optional[int] = None) -> "CompressedBlob":
    """Pad a merged chunk table to power-of-two row/column buckets.

    Every micro-batch window fuses a different set of blobs, so the merged
    table's ``(num_chunks, max_comp_bytes)`` shape is fresh almost every
    window — and each fresh shape is a new XLA compile.  Padding rows with
    zero-length chunks (:func:`pad_table_rows`) and columns with zero bytes
    buckets the jit cache by ``(group key, pow2 rows, pow2 cols)``: after a
    handful of windows the steady state is compile-free.

    ``cols_floor`` is the minimum column bucket — the knob trading padding
    waste (small tables inflated to the floor) against jit-cache pressure
    (more distinct shapes below it).  Explicit values win; ``None``
    consults the tuned-defaults table for this blob's (codec, width) on
    the current device (``core.tuning``), and with no tuning entry the
    historical floor of 128 applies unchanged.
    """
    if cols_floor is None:
        from repro.core import tuning
        cols_floor = tuning.bucket_cols_floor(table.codec, table.width)
    floor = 128 if cols_floor is None else int(cols_floor)
    padded = pad_table_rows(table, _next_pow2(table.num_chunks))
    cols = int(padded.comp.shape[1])
    target_cols = max(floor, _next_pow2(cols))
    if target_cols == cols:
        return padded
    comp = np.zeros((padded.num_chunks, target_cols), np.uint8)
    comp[:, :cols] = padded.comp
    return dataclasses.replace(padded, comp=comp)


def blob_digest(blob: "CompressedBlob") -> str:
    """Content hash of a compressed blob — equal digests decode identically.

    Covers everything the decode output depends on: codec + static decode
    metadata, the dense comp matrix (padding is all-zeros by construction,
    so it is deterministic), the length vectors, and every extras table.
    Used as the service cache key, the plan executor's staging cache key,
    and by the golden-vector conformance suite as the committed encoder
    fingerprint.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(f"{blob.codec}|{blob.width}|{blob.chunk_elems}|"
             f"{blob.total_elems}|{blob.orig_dtype}|{blob.orig_shape}"
             .encode())
    h.update(np.ascontiguousarray(blob.comp_lens, np.int64).tobytes())
    h.update(np.ascontiguousarray(blob.out_lens, np.int64).tobytes())
    h.update(np.ascontiguousarray(blob.comp).tobytes())
    for k in sorted(blob.extras):
        v = np.ascontiguousarray(blob.extras[k])
        h.update(f"|{k}|{v.dtype}|{v.shape}|".encode())
        h.update(v.tobytes())
    return h.hexdigest()


def combine_planes(outs: list, orig_dtype: str, orig_shape: tuple) -> np.ndarray:
    """Recombine decoded plane blobs into one logical array.

    One blob is the common case (``reassemble`` already restored
    dtype/shape); two blobs are the lo/hi uint32 planes of an 8-byte dtype
    (``api.compress`` plane decomposition).
    """
    if len(outs) == 1:
        return outs[0]
    lo, hi = outs
    u64 = (lo.reshape(-1).astype(np.uint64)
           | (hi.reshape(-1).astype(np.uint64) << np.uint64(32)))
    return u64.view(np.dtype(orig_dtype)).reshape(orig_shape)


def chunk_array(arr: np.ndarray, chunk_bytes: int = DEFAULT_CHUNK_BYTES):
    """Split ``arr`` into fixed-size element chunks (last may be short)."""
    flat, width, dev_dtype = _as_bytes_view(arr)
    chunk_elems = max(1, chunk_bytes // width)
    n = flat.shape[0]
    num_chunks = max(1, (n + chunk_elems - 1) // chunk_elems)
    chunks = [flat[i * chunk_elems : min((i + 1) * chunk_elems, n)]
              for i in range(num_chunks)]
    return chunks, chunk_elems, width, dev_dtype


def build_blob(
    codec: str,
    arr: np.ndarray,
    encoded: list[bytes],
    chunk_elems: int,
    width: int,
    extras: Optional[Dict[str, np.ndarray]] = None,
    total_elems: Optional[int] = None,
) -> CompressedBlob:
    """Assemble the rectangular device layout from per-chunk byte strings."""
    if total_elems is None:
        flat, _, _ = _as_bytes_view(arr)
        total_elems = flat.shape[0]
    n = total_elems
    num_chunks = len(encoded)
    max_len = max(len(e) for e in encoded) if encoded else 1
    comp = np.zeros((num_chunks, max_len), np.uint8)
    comp_lens = np.zeros((num_chunks,), np.int32)
    out_lens = np.zeros((num_chunks,), np.int32)
    for i, e in enumerate(encoded):
        comp[i, : len(e)] = np.frombuffer(e, np.uint8)
        comp_lens[i] = len(e)
        out_lens[i] = min(chunk_elems, n - i * chunk_elems)
    return CompressedBlob(
        codec=codec,
        width=width,
        chunk_elems=chunk_elems,
        total_elems=int(n),
        orig_dtype=str(arr.dtype),
        orig_shape=tuple(arr.shape),
        comp=comp,
        comp_lens=comp_lens,
        out_lens=out_lens,
        extras=extras or {},
    )


def reassemble(blob: CompressedBlob, chunks_out: np.ndarray) -> np.ndarray:
    """Stitch decoded (num_chunks, chunk_elems) back to the original array."""
    flat = np.ascontiguousarray(chunks_out.reshape(-1)[: blob.total_elems])
    return flat.view(np.dtype(blob.orig_dtype)).reshape(blob.orig_shape)


# --------------------------------------------------------------------------
# Device-side reassembly (the ISSUE-4 tentpole: a decoded blob is born,
# reassembled, and consumed on device — no host round trip).
# --------------------------------------------------------------------------


def _require_x64(dtype: np.dtype) -> None:
    import jax
    if dtype.itemsize == 8 and not jax.config.jax_enable_x64:
        raise ValueError(
            f"device-resident reassembly to {dtype} needs 64-bit jax types; "
            "enable them (`with jax.enable_x64(True):` or "
            "jax_enable_x64=True) or use the host path (reassemble / "
            "combine_planes)")


def device_view(flat, dtype, shape=None):
    """Device analog of ``flat.view(dtype).reshape(shape)`` — a pure bitcast
    reinterpretation, jit-compatible.  ``flat`` is a 1-D jax array; widening
    views (e.g. uint8 bytes -> float32, uint32 pairs -> uint64) regroup
    ``itemsize_ratio`` consecutive elements per output element."""
    import jax.numpy as jnp
    from jax import lax
    od = np.dtype(dtype)
    _require_x64(od)
    cur = np.dtype(flat.dtype)
    if od == cur:
        out = flat
    elif od.itemsize == cur.itemsize:
        out = lax.bitcast_convert_type(flat, od)
    elif od.itemsize > cur.itemsize:
        k = od.itemsize // cur.itemsize
        if flat.shape[0] % k:
            raise ValueError(f"{flat.shape[0]} {cur} elements do not view "
                             f"evenly as {od}")
        out = lax.bitcast_convert_type(flat.reshape(-1, k), od)
    else:
        out = lax.bitcast_convert_type(flat, od).reshape(-1)
    return out.reshape(shape if shape is not None else (-1,))


def reassemble_indices(blob: CompressedBlob) -> Optional[np.ndarray]:
    """Precomputed gather for device reassembly, or ``None`` when trivial.

    Returns the flat source index per output element — output position ``p``
    reads ``chunks_out.reshape(-1)[idx[p]]`` — derived from the per-row
    destination offsets (exclusive cumsum of ``out_lens``).  For the standard
    layout (every chunk full except a trailing tail, the ``build_blob``
    invariant) the decode matrix is already contiguous and a reshape+trim
    suffices, so ``None`` is returned and no index table needs staging.
    """
    out_lens = np.asarray(blob.out_lens, np.int64)
    n = len(out_lens)
    expect = np.clip(blob.total_elems - np.arange(n) * blob.chunk_elems,
                     0, blob.chunk_elems)
    if np.array_equal(out_lens, expect):
        return None               # contiguous: reshape(-1)[:total] is exact
    dest = np.concatenate([[0], np.cumsum(out_lens)])   # per-row dest offsets
    if dest[-1] != blob.total_elems:
        raise ValueError(f"out_lens sum {dest[-1]} != total {blob.total_elems}")
    p = np.arange(blob.total_elems, dtype=np.int64)
    row = np.searchsorted(dest, p, side="right") - 1
    return (row * blob.chunk_elems + (p - dest[row])).astype(np.int32)


def reassemble_rows_device(table, *, row0: int, num_chunks: int,
                           total_elems: int, orig_dtype: str,
                           orig_shape: tuple, indices=None,
                           transformed: bool = False):
    """Jit-compatible row-range reassembly from a fused group table.

    Slices ``num_chunks`` rows starting at ``row0`` out of the decoded
    ``(group_chunks, chunk_elems)`` device matrix and stitches them into
    the blob's original array, all as traced device ops (zero host syncs
    when called inside jit / with pre-staged ``indices``).

    ``indices``: the precomputed per-row-destination gather from
    :func:`reassemble_indices`, or None for the contiguous reshape+trim
    fast path.  ``transformed=True`` marks output of a fused decode
    epilogue — element values (and dtype) are the epilogue's, so the
    original-dtype bitcast is skipped and only the trim + reshape applies.
    """
    import jax.numpy as jnp
    from jax import lax
    rows = lax.slice_in_dim(table, row0, row0 + num_chunks)
    flat = jnp.reshape(rows, (-1,))
    if indices is None:
        flat = lax.slice_in_dim(flat, 0, total_elems)
    else:
        flat = flat[indices] if total_elems else flat[:0]
    if transformed:
        n = int(np.prod(orig_shape)) if orig_shape else 1
        return flat.reshape(orig_shape if n == total_elems else (-1,))
    return device_view(flat, orig_dtype, orig_shape)


def reassemble_device(blob: CompressedBlob, chunks_out, *,
                      indices: Optional[Any] = None,
                      transformed: bool = False):
    """Device analog of :func:`reassemble`: stitch the decoded
    ``(num_chunks, chunk_elems)`` jax matrix back to the original array
    without leaving the device (jit-compatible; bit-exact vs the host path).

    ``indices``: optional pre-staged gather from :func:`reassemble_indices`
    (e.g. carried by a ``DecodePlan``); by default it is derived here from
    the blob's host metadata.
    """
    if indices is None:
        indices = reassemble_indices(blob)
    return reassemble_rows_device(
        chunks_out, row0=0, num_chunks=blob.num_chunks,
        total_elems=blob.total_elems, orig_dtype=blob.orig_dtype,
        orig_shape=tuple(blob.orig_shape), indices=indices,
        transformed=transformed)


def combine_planes_device(outs: list, orig_dtype: str, orig_shape: tuple):
    """Device analog of :func:`combine_planes` (jit-compatible).

    Two plane blobs are the lo/hi uint32 halves of an 8-byte dtype; their
    recombination is a lane interleave + bitcast, which needs 64-bit jax
    types enabled (a consumer that cannot hold a 64-bit device array has no
    use for a device-resident one).
    """
    import jax.numpy as jnp
    from jax import lax
    if len(outs) == 1:
        return outs[0]
    _require_x64(np.dtype(orig_dtype))
    lo, hi = outs
    pair = jnp.stack([lo.reshape(-1).astype(jnp.uint32),
                      hi.reshape(-1).astype(jnp.uint32)], axis=-1)
    u64 = lax.bitcast_convert_type(pair, jnp.uint64)
    return device_view(u64, orig_dtype, orig_shape)
