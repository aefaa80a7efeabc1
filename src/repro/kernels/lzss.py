"""Multi-byte LZSS (GPULZ-style, arXiv 2304.07342) — element-granular LZ.

Where tdeflate works on bytes with Huffman-coded tokens, lzss trades ratio
for parallel decode: tokens are byte-aligned, and matches/literals are in
*element* units (the blob's width — GPULZ's "multi-byte" granularity), so
a u32 stream's matches never split an element.

Token stream, width = element bytes:

  control c in [0, 127]   -> literal run of c+1 elements (1..128);
                             (c+1)*width little-endian value bytes follow
  control c in [128, 255] -> match of c-128+MIN_MATCH elements (2..129);
                             u16 LE distance in elements follows
                             (1 <= dist <= 65535, chunk-local window)

Decode is the paper's two-phase split, with GPULZ's twist that Phase 1 is
an offset prefix sum and Phase 2 is an all-thread copy:

  Phase 1 (serial leader loop): parse one token per step into
      (start, is_match, dist, litoff) group tables — the output-offset
      prefix sum falls out of the running ``start`` counter.
  Phase 2 (all-thread): marker-scatter/cumsum maps every output lane to
      its token.  Back-references may point into other matches (and
      overlap their own output), so the per-lane source is resolved by
      pointer doubling — ``ptr = ptr[ptr]``, ``ceil(log2(chunk_elems))``
      rounds: every chain strictly decreases and terminates at a literal
      lane, after which ONE vectorized ``streams.gather_values`` reads
      every lane's value from the compressed stream.  No serial command
      loop: Phase 2 is all-thread, like the paper's RLE expansion.

The oracle is the classic serial token walk using the Table II ``memcpy``
(overlap-safe circular window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import registry
from repro.core import streams as st
from repro.kernels import harness

LZSS = "lzss"

MIN_MATCH = 2
MAX_MATCH = MIN_MATCH + 127   # 129 elements
MAX_LIT = 128
MAX_DIST = 65535
CW = 132                      # oracle blend window >= max(MAX_MATCH, MAX_LIT)


def max_tokens(out_len: int) -> int:
    return out_len + 4        # every token emits >= 1 element


# --------------------------------------------------------------------------
# host encoder: greedy hash-of-2 chain over elements (single probe)
# --------------------------------------------------------------------------


def encode_lzss_chunk(x: np.ndarray, width: int) -> bytes:
    xs = np.ascontiguousarray(x).astype(np.uint32)
    vals = xs.tolist()
    n = len(vals)
    out = bytearray()
    head: dict = {}

    def flush(lo: int, hi: int) -> None:
        i = lo
        while i < hi:
            k = min(MAX_LIT, hi - i)
            out.append(k - 1)
            out.extend(enc._values_bytes(xs[i:i + k], width))
            i += k

    i, lit = 0, 0
    while i < n:
        m, dist = 0, 0
        if i + MIN_MATCH <= n:
            key = (vals[i], vals[i + 1])
            cand = head.get(key, -1)
            head[key] = i
            if cand >= 0 and i - cand <= MAX_DIST:
                lim = min(MAX_MATCH, n - i)
                while m < lim and vals[cand + m] == vals[i + m]:
                    m += 1
                dist = i - cand
        # profitable only if the 3 token bytes undercut the literal bytes
        if m >= MIN_MATCH and m * width > 3:
            flush(lit, i)
            out.append(128 + (m - MIN_MATCH))
            out.extend(dist.to_bytes(2, "little"))
            for j in range(i + 1, min(i + 4, i + m, n - MIN_MATCH + 1)):
                head[(vals[j], vals[j + 1])] = j
            i += m
            lit = i
        else:
            i += 1
    flush(lit, n)
    return bytes(out)


def compress_lzss(arr: np.ndarray,
                  chunk_bytes: int = fmt.DEFAULT_CHUNK_BYTES,
                  bits: int | None = None) -> fmt.CompressedBlob:
    chunks, chunk_elems, width, _ = fmt.chunk_array(arr, chunk_bytes)
    encoded = [encode_lzss_chunk(c, width) for c in chunks]
    return fmt.build_blob(LZSS, arr, encoded, chunk_elems, width)


# --------------------------------------------------------------------------
# decode bodies
# --------------------------------------------------------------------------


def _body(inputs, consts, out_len, *, chunk_elems, width, bits, dbl_unroll=1):
    (comp,) = inputs
    dt = harness.DEV_DTYPE[width]
    MT = max_tokens(chunk_elems)

    # ---- Phase 1: sequential token parse -> group tables ------------------
    def cond(s):
        return jnp.logical_and(s[2] < out_len, s[1] < MT)

    def body1(s):
        pos, g, cnt, starts, kinds, dists, litoffs = s
        c = st.read_byte_at(comp, pos)
        is_m = c >= 128
        length = jnp.where(is_m, c - 128 + MIN_MATCH, c + 1)
        dist = st.read_value_at(comp, pos + 1, 2).astype(jnp.int32)
        starts = starts.at[g].set(cnt)
        kinds = kinds.at[g].set(is_m)
        dists = dists.at[g].set(dist)
        litoffs = litoffs.at[g].set(pos + 1)
        adv = jnp.where(is_m, 3, 1 + length * width)
        return pos + adv, g + 1, cnt + length, starts, kinds, dists, litoffs

    with jax.named_scope(harness.PHASE1):
        init = (jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.full((MT,), chunk_elems, jnp.int32),  # sentinel = chunk_elems
                jnp.zeros((MT,), jnp.bool_),
                jnp.zeros((MT,), jnp.int32),
                jnp.zeros((MT,), jnp.int32))
        _, _, _, starts, kinds, dists, litoffs = lax.while_loop(
            cond, body1, init)

    # ---- Phase 2: all-thread copy resolution ------------------------------
    with jax.named_scope(harness.PHASE2):
        src = harness.resolve_backrefs(starts, kinds, dists, litoffs, width,
                                       chunk_elems, unroll=dbl_unroll)
        vals = st.gather_values(comp, src, width)
        idx = jnp.arange(chunk_elems, dtype=jnp.int32)
        return jnp.where(idx < out_len, vals, 0).astype(dt)


def _body_oracle(inputs, consts, out_len, *, chunk_elems, width, bits):
    """Serial token walk with the Table II primitives: blend-write literal
    runs, overlap-safe circular-window ``memcpy`` for matches."""
    (comp,) = inputs
    dt = harness.DEV_DTYPE[width]
    lanes = jnp.arange(CW, dtype=jnp.int32)

    def cond(s):
        return s[1].pos < out_len

    def body(s):
        pos, out = s
        c = st.read_byte_at(comp, pos)
        is_m = c >= 128
        length = jnp.where(is_m, c - 128 + MIN_MATCH, c + 1)
        dist = st.read_value_at(comp, pos + 1, 2).astype(jnp.int32)
        out_m = st.memcpy(out, dist, length, CW)
        lit_vals = st.gather_values(comp, pos + 1 + lanes * width,
                                    width).astype(dt)
        cur = lax.dynamic_slice(out.buf, (out.pos,), (CW,))
        new = jnp.where(lanes < length, lit_vals, cur)
        out_l = out._replace(
            buf=lax.dynamic_update_slice(out.buf, new, (out.pos,)),
            pos=out.pos + length)
        out = jax.tree.map(lambda a, b: jnp.where(is_m, a, b), out_m, out_l)
        return pos + jnp.where(is_m, 3, 1 + length * width), out

    _, out = lax.while_loop(
        cond, body, (jnp.int32(0), st.outstream(chunk_elems + CW, dt)))
    idx = jnp.arange(chunk_elems, dtype=jnp.int32)
    return jnp.where(idx < out_len, out.buf[:chunk_elems], 0)


def _pallas(body, inputs, consts, out_lens, *, chunk_elems, width, bits,
            interpret, tune=()):
    """Generic wrapper with the ``dbl_unroll`` knob baked into the body
    (how many pointer-doubling gathers fuse into one loop step)."""
    unroll = int(dict(tune).get("dbl_unroll", 1))
    tuned = functools.partial(_body, dbl_unroll=unroll)
    return harness._generic_pallas(tuned, inputs, consts, out_lens,
                                   chunk_elems=chunk_elems, width=width,
                                   bits=bits, interpret=interpret, tune=tune)


# --------------------------------------------------------------------------
# registry plumbing
# --------------------------------------------------------------------------


def _count_groups(row, width: int) -> int:
    pos, n, groups = 0, len(row), 0
    while pos < n:
        c = int(row[pos])
        pos += 3 if c >= 128 else 1 + (c + 1) * width
        groups += 1
    return groups


def _demo_data(n: int, rng) -> np.ndarray:
    """Repeating element motifs + sparse noise (LZ's bread and butter)."""
    motif = rng.integers(0, 1 << 12, 48).astype(np.uint32)
    out = np.tile(motif, n // motif.size + 1)[:n].copy()
    noise = rng.random(n) < 0.04
    out[noise] = rng.integers(0, 1 << 12, int(noise.sum()))
    return out


CODEC = registry.register(registry.Codec(
    name=LZSS,
    encode=compress_lzss,
    decode=harness.DecodeSpec(
        body=_body,
        body_oracle=_body_oracle,
        pallas_override=_pallas,
        tunables=(harness.Tunable("dbl_unroll", (1, 2, 4), 1),),
    ),
    plane_decompose_64=True,
    demo_data=_demo_data,
    count_groups=_count_groups,
))
