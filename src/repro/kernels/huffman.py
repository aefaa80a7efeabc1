"""Parallel canonical Huffman over bytes — the gap-array codec.

A pure entropy stream is the worst case for CODAG's two-phase split: symbol
boundaries are only known after decoding, so Phase 2 cannot jump to element
``k`` of a group.  The fix (Rivera et al.'s gap array, arXiv 2201.09118) is
an encoder-side index: the stream is cut into fixed-size *segments* of
``SUB`` symbols, and a per-segment gap entry (bit offset + count) lets every
segment decode independently:

  Phase 1 is trivially parallel here — gap entries are fixed-size, so the
      per-segment tables are a vectorized gather, not a leader loop.
  Phase 2 (lockstep expansion): seed one bit cursor per segment from its
      gap entry, then step ALL segments together — every step peeks
      ``MAX_CODE_BITS`` LSB-first bits per cursor lane (one vectorized
      funnel-shift load), resolves (symbol, code length) through the
      chunk's flat canonical-decode LUT, writes the symbol column, and
      advances each cursor by its own code length.  ``SUB`` steps decode
      the whole chunk with n_segments-way parallelism.

Chunk layout:

  [gap table: n_segments x 5 bytes] [Huffman payload, LSB-first bits]
  gap entry g: bytes 0..3 = u32 LE absolute bit offset of segment g's
  first symbol (relative to the chunk row start, gap table included);
  byte 4 = symbol count - 1 (1..SUB symbols).

``n_segments`` is recoverable from the stream alone: entry 0's bit offset
is the gap table's own size in bits, so ``offset0 / 40`` counts segments
(what ``count_groups`` reports for Table V symbol lengths).

The per-chunk canonical code is carried as ``hdr_hlens`` (256 code lengths,
the only table a real container would ship — counted in ``ratio``); the
flat 4096-entry decode LUTs (``lut_hsym`` / ``lut_hbits``) are derived from
it at encode time and ride the device pytree like tdeflate's.

The oracle walks segment by segment, trusting both the offsets and the
count bytes; the golden vectors and the round trips against the original
data check the encoder's offsets.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import registry
from repro.core import streams as st
from repro.kernels import harness

HUFFMAN = "huffman"

SUB = 32                 # symbols per self-synchronizing segment
GAP_ENTRY_BYTES = 5      # u32 LE bit offset + (count - 1) byte


# --------------------------------------------------------------------------
# host encoder (vectorized: one np scatter packs the whole chunk)
# --------------------------------------------------------------------------


def _pack_lsb(vals: np.ndarray, nbits: np.ndarray) -> Tuple[bytes, np.ndarray]:
    """Pack variable-width fields LSB-first. Returns (payload, start bits).

    Same disjoint-bit-field scatter as ``encoders.pack_bits``, generalized
    to per-field widths: field bit ranges never overlap, so scatter-add is
    scatter-or and each uint64 accumulator word stays below 2^43.
    """
    nbits = nbits.astype(np.int64)
    ends = np.cumsum(nbits)
    starts = ends - nbits
    total = int(ends[-1]) if ends.size else 0
    nwords = (total + 31) // 32
    acc = np.zeros(nwords + 2, np.uint64)
    v = vals.astype(np.uint64)
    word = (starts >> 5).astype(np.int64)
    off = (starts & 31).astype(np.uint64)
    np.add.at(acc, word, (v << off) & np.uint64(0xFFFFFFFF))
    np.add.at(acc, word + 1, np.where(off > 0, v >> (np.uint64(32) - off),
                                      np.uint64(0)))
    payload = acc[:nwords].astype(np.uint32).tobytes()[: (total + 7) // 8]
    return payload, starts


def encode_huffman_chunk(data: np.ndarray) -> Tuple[bytes, np.ndarray]:
    """Encode one uint8 chunk. Returns (gap table + payload, code lengths)."""
    data = np.ascontiguousarray(data).view(np.uint8)
    lens = enc.limited_huffman_lengths(
        np.bincount(data, minlength=256).astype(np.int64), enc.MAX_CODE_BITS)
    n = data.shape[0]
    if n == 0:
        return b"", lens.astype(np.uint8)
    codes = enc.canonical_codes(lens)
    # pre-reversed for LSB-first emission, indexed by byte value
    rev = np.array([enc._bit_reverse(int(codes[s]), int(lens[s]))
                    for s in range(256)], np.uint64)
    payload, starts = _pack_lsb(rev[data], lens[data])
    nseg = (n + SUB - 1) // SUB
    gap_bits = nseg * GAP_ENTRY_BYTES * 8
    head = np.empty((nseg, GAP_ENTRY_BYTES), np.uint8)
    head[:, :4] = (gap_bits + starts[::SUB]).astype("<u4") \
        .view(np.uint8).reshape(nseg, 4)
    head[:, 4] = (np.minimum(SUB, n - np.arange(nseg) * SUB) - 1).astype(np.uint8)
    return head.tobytes() + payload, lens.astype(np.uint8)


def compress_huffman(arr: np.ndarray,
                     chunk_bytes: int = fmt.DEFAULT_CHUNK_BYTES,
                     bits: int | None = None) -> fmt.CompressedBlob:
    chunks, chunk_elems, width, _ = fmt.chunk_array(arr, chunk_bytes)
    # byte codec: re-chunk at byte granularity (like tdeflate)
    chunks = [np.ascontiguousarray(c).view(np.uint8) for c in chunks]
    payloads, hlens, lut_s, lut_b = [], [], [], []
    for c in chunks:
        p, hl = encode_huffman_chunk(c)
        payloads.append(p)
        hlens.append(hl)
        s, b = enc.build_decode_lut(hl.astype(np.int32))
        lut_s.append(s)
        lut_b.append(b)
    extras = {
        "hdr_hlens": np.stack(hlens),
        "lut_hsym": np.stack(lut_s),
        "lut_hbits": np.stack(lut_b),
    }
    total_bytes = sum(int(c.shape[0]) for c in chunks)
    return fmt.build_blob(HUFFMAN, arr, payloads, chunk_elems * width, 1,
                          extras, total_elems=total_bytes)


# --------------------------------------------------------------------------
# decode bodies
# --------------------------------------------------------------------------


def _decode_lockstep(comp, words, lut_sym, lut_bits, out_len,
                     chunk_elems: int, unroll: int = 1) -> jnp.ndarray:
    """All segments decode in lockstep: one bit cursor per segment, SUB
    steps, each a vectorized peek/LUT/advance across every cursor lane."""
    nseg = (chunk_elems + SUB - 1) // SUB
    segs = jnp.arange(nseg, dtype=jnp.int32)
    bitpos = st.gather_values(comp, segs * GAP_ENTRY_BYTES, 4).astype(jnp.int32)

    def one(t, bitpos, out):
        v = st.peek_bits(st.BitStream(words=words, pos=bitpos),
                         enc.MAX_CODE_BITS)
        sym = jnp.take(lut_sym, v.astype(jnp.int32), mode="clip")
        nb = jnp.take(lut_bits, v.astype(jnp.int32), mode="clip")
        return bitpos + nb, out.at[:, t].set(sym.astype(jnp.uint32))

    def step(i, carry):
        bitpos, out = carry
        for u in range(unroll):     # static unroll inside one loop step
            bitpos, out = one(i * unroll + u, bitpos, out)
        return bitpos, out

    _, out = lax.fori_loop(0, SUB // unroll, step,
                           (bitpos, jnp.zeros((nseg, SUB), jnp.uint32)))
    flat = out.reshape(-1)[:chunk_elems]
    idx = jnp.arange(chunk_elems, dtype=jnp.int32)
    return jnp.where(idx < out_len, flat, 0)


def _body(inputs, consts, out_len, *, chunk_elems, width, bits, sub_unroll=1):
    comp, words, lut_sym, lut_bits = inputs
    # no Phase 1: the segment gap table lets every lane decode at once
    with jax.named_scope(harness.PHASE2):
        out = _decode_lockstep(comp, words, lut_sym, lut_bits, out_len,
                               chunk_elems, unroll=sub_unroll)
        return out.astype(harness.DEV_DTYPE[width])


def _body_oracle(inputs, consts, out_len, *, chunk_elems, width, bits):
    """Sequential reference: segment by segment through the gap table, each
    segment decoded serially from its own bit offset and blend-written at
    the running count — validates offsets AND count bytes."""
    comp, words, lut_sym, lut_bits = inputs
    dt = harness.DEV_DTYPE[width]
    lanes = jnp.arange(SUB, dtype=jnp.int32)

    def cond(s):
        return s[1] < out_len

    def body(s):
        g, cnt, buf = s
        bitoff = st.read_value_at(comp, g * GAP_ENTRY_BYTES, 4).astype(jnp.int32)
        count = st.read_byte_at(comp, g * GAP_ENTRY_BYTES + 4) + 1

        def inner(t, c):
            pos, vals = c
            v = st.peek_bits(st.BitStream(words=words, pos=pos),
                             enc.MAX_CODE_BITS)
            sym = jnp.take(lut_sym, v.astype(jnp.int32), mode="clip")
            nb = jnp.take(lut_bits, v.astype(jnp.int32), mode="clip")
            return pos + nb, vals.at[t].set(sym.astype(dt))

        _, vals = lax.fori_loop(0, SUB, inner,
                                (bitoff, jnp.zeros((SUB,), dt)))
        cur = lax.dynamic_slice(buf, (cnt,), (SUB,))
        new = jnp.where(lanes < count, vals, cur)
        return (g + 1, cnt + count,
                lax.dynamic_update_slice(buf, new, (cnt,)))

    _, _, buf = lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.int32(0), jnp.zeros((chunk_elems + SUB,), dt)))
    return buf[:chunk_elems]


def _pallas(body, inputs, consts, out_lens, *, chunk_elems, width, bits,
            interpret, tune=()):
    """Generic wrapper with the codec's ``sub_unroll`` knob baked into the
    lockstep body (plain bodies never see ``tune``; the override does)."""
    unroll = int(dict(tune).get("sub_unroll", 1))
    tuned = functools.partial(_body, sub_unroll=unroll)
    return harness._generic_pallas(tuned, inputs, consts, out_lens,
                                   chunk_elems=chunk_elems, width=width,
                                   bits=bits, interpret=interpret, tune=tune)


# --------------------------------------------------------------------------
# registry plumbing
# --------------------------------------------------------------------------


def _chunk_inputs(dev):
    """Per-chunk operands: raw bytes (gap table), word view (payload bits),
    and the two flat canonical-decode LUTs."""
    words = dev.get("comp_words")
    if words is None:
        words = harness.words_view(dev["comp"])
    return (dev["comp"], words,
            dev["lut_hsym"].astype(jnp.int32),
            dev["lut_hbits"].astype(jnp.int32))


def _count_groups(row, width: int) -> int:
    if len(row) < GAP_ENTRY_BYTES:
        return 0
    # entry 0's bit offset == the gap table's own size in bits
    off0 = int.from_bytes(bytes(bytearray(row[:4])), "little")
    return off0 // (GAP_ENTRY_BYTES * 8)


def _demo_data(n: int, rng) -> np.ndarray:
    """Geometrically skewed bytes — the entropy coder's natural habitat."""
    return np.minimum(rng.geometric(0.25, n) - 1, 255).astype(np.uint8)


CODEC = registry.register(registry.Codec(
    name=HUFFMAN,
    encode=compress_huffman,
    decode=harness.DecodeSpec(
        body=_body,
        body_oracle=_body_oracle,
        chunk_inputs=_chunk_inputs,
        pallas_override=_pallas,
        tunables=(harness.Tunable("sub_unroll", (1, 2, 4), 1),),
    ),
    needs_words=True,
    byte_stream=True,
    demo_data=_demo_data,
    count_groups=_count_groups,
))
