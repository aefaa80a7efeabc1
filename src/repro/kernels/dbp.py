"""dbp codec plugin — frame-of-reference delta + bitpack (NEW codec).

The extensibility proof for the codec-plugin framework: everything dbp
needs — encoder, all three decode backends, batch-scheduler grouping,
checkpoint restore, pipeline decode, bench/test matrices — comes from this
one module plus its ``registry.register`` call.  Nothing outside the plugin
names the codec.

Format (ORC RLE v2 direct-mode spirit; the natural encoding for token ids,
timestamps, sorted ids, and quantized optimizer state): the chunk is split
into groups of up to 256 elements; each group stores its minimum (the frame
of reference) and LSB-first bitpacks every element's offset from it.

Per-group byte-aligned layout:
  byte 0            bit width b (0..32; 0 = all elements equal the ref)
  byte 1            count-1 (group length 1..256)
  bytes 2..2+w-1    ref, little-endian, w = element width
  payload           ceil(count*b/8) bytes, LSB-first packed (val - ref)

Phase 1 parses that fixed-shape header (trivially sequential: the payload
length depends on b and count).  Phase 2 is pure all-thread: every lane
funnel-shifts its own b-bit field out of the payload and adds the ref — the
same position-independence that makes plain bitpack the paper's best case,
but with per-group references so unsorted-but-local data still compresses.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import registry
from repro.core import streams as st
from repro.kernels import harness

GROUP = 128            # encoder group size (any count in 1..256 decodes)
MAX_GROUP_LEN = 256


def max_groups(out_len: int) -> int:
    return out_len + 4   # any stream of >=1-element groups is decodable


# --------------------------------------------------------------------------
# host encoder
# --------------------------------------------------------------------------


def encode_dbp_chunk(x: np.ndarray, width: int) -> bytes:
    """Encode one chunk: per-group (bits, count-1, ref, packed offsets)."""
    out = bytearray()
    xs = np.ascontiguousarray(x).astype(np.uint32)
    for i in range(0, xs.shape[0], GROUP):
        g = xs[i:i + GROUP]
        ref_v = int(g.min())
        deltas = (g - np.uint32(ref_v)).astype(np.uint64)
        bits = int(deltas.max()).bit_length()
        out.append(bits)
        out.append(len(g) - 1)
        out.extend(int(ref_v).to_bytes(4, "little")[:width])
        if bits:
            payload = enc.pack_bits(deltas, bits).tobytes()
            out.extend(payload[: (len(g) * bits + 7) // 8])
    return bytes(out)


def compress_dbp(arr: np.ndarray, chunk_bytes: int = fmt.DEFAULT_CHUNK_BYTES,
                 bits=None) -> fmt.CompressedBlob:
    """Host encoder entry point (``bits`` is unused: widths are per-group)."""
    chunks, chunk_elems, width, _ = fmt.chunk_array(arr, chunk_bytes)
    encoded = [encode_dbp_chunk(c, width) for c in chunks]
    return fmt.build_blob(fmt.DBP, arr, encoded, chunk_elems, width)


# --------------------------------------------------------------------------
# decode: header parse + value expression (the whole kernel)
# --------------------------------------------------------------------------


def _parse(comp, pos, width: int):
    bits = st.read_byte_at(comp, pos)
    count = st.read_byte_at(comp, pos + 1) + 1
    return {
        "length": count,
        "advance": 2 + width + ((count * bits + 7) >> 3),
        "ref": st.read_value_at(comp, pos + 2, width),
        "bits": bits,
        "payoff": pos + 2 + width,
    }


def _express(comp, f, k, width: int):
    """Lane k funnel-shifts its b-bit offset from the payload, adds ref.

    The 40-bit window (an unaligned uint32 + one spill byte) covers any
    b <= 32 at any intra-byte offset 0..7.
    """
    bits = f["bits"]
    bitpos = f["payoff"] * 8 + k * bits
    byte = bitpos >> 3
    off = (bitpos & 7).astype(jnp.uint32)
    w0 = st.gather_values(comp, byte, 4)
    b4 = jnp.take(comp, byte + 4, mode="clip").astype(jnp.uint32)
    lo = jnp.right_shift(w0, off)
    hi = jnp.where(off > 0,
                   jnp.left_shift(b4, (jnp.uint32(32) - off) & jnp.uint32(31)),
                   jnp.uint32(0))
    # dynamic-width mask; shift amount capped at 31 to stay well-defined
    nb = jnp.minimum(bits, 31).astype(jnp.uint32)
    mask = jnp.where(bits >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << nb) - jnp.uint32(1))
    return f["ref"] + ((lo | hi) & mask)


SPEC = harness.TwoPhaseSpec(
    fields=(harness.Field("ref", jnp.uint32),
            harness.Field("bits", jnp.int32),
            harness.Field("payoff", jnp.int32)),
    parse=_parse,
    express=_express,
    max_groups=max_groups,
    max_group_len=MAX_GROUP_LEN,
)


def _count_groups(row, width: int) -> int:
    pos, groups = 0, 0
    while pos < len(row):
        bits, count = int(row[pos]), int(row[pos + 1]) + 1
        pos += 2 + width + (count * bits + 7) // 8
        groups += 1
    return groups


def _demo_data(n: int, rng) -> np.ndarray:
    """Sorted-id / timestamp-like uint32s: small per-group value ranges."""
    return np.cumsum(rng.integers(0, 16, n)).astype(np.uint32)


CODEC = registry.register(registry.Codec(
    name=fmt.DBP,
    encode=compress_dbp,
    # oracle defaults to the harness's generic group-serial driver — a new
    # codec gets a paper-faithful sequential reference for free.
    decode=harness.DecodeSpec.from_two_phase(SPEC),
    plane_decompose_64=True,
    demo_data=_demo_data,
    count_groups=_count_groups,
))
