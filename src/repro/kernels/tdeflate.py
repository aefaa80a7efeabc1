"""tdeflate (Deflate-semantics) decode — Pallas TPU kernel.

Two-phase decode/execute split (the same split RAPIDS' leader-thread decode /
collaborative write uses, and the reason the paper only gains 1.18x on
Deflate — the Huffman stage is irreducibly serial):

  Phase 1 (serial per chunk): table-driven Huffman token parse
      12-bit LSB-first peek -> flat LUT -> (symbol, nbits); extra bits for
      lengths/distances.  Consecutive literals are batched into `litrun`
      commands whose bytes land in a contiguous side buffer, so Phase 2's
      writes are wide even for literal-heavy streams.
  Phase 2 (serial across commands, vector-parallel within): Table II
      primitives — `write_from` for literal runs and the overlap-safe
      `memcpy` (Alg. 2, circular window when len > dist) for LZ matches.

Chunk-level parallelism comes from the harness's generic chunk-per-grid-cell
``pallas_call`` wrapper, exactly CODAG's warp-per-chunk provisioning.  The
Phase-2 command execution here is serial-with-vector-writes (LZ copies
depend on earlier output), so this codec plugs its own chunk bodies into the
``DecodeSpec`` interface instead of a ``TwoPhaseSpec``; the deflate
base/extra tables ride the wrapper's broadcast-constant lane.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import registry
from repro.core import streams as st
from repro.kernels import harness, ref

LEN_EXTRA = jnp.asarray(enc.LEN_EXTRA)
LEN_BASE = jnp.asarray(enc.LEN_BASE)
DIST_EXTRA = jnp.asarray(enc.DIST_EXTRA)
DIST_BASE = jnp.asarray(enc.DIST_BASE)

LITRUN_CAP = 256          # max literals batched into one command
CMD_WIN = 272             # blend window >= max(MAX_MATCH=258, LITRUN_CAP)


def max_cmds(out_len: int) -> int:
    # worst case: alternating match(>=3) + litrun(>=1) = 2 cmds / 4 bytes
    return out_len // 2 + 4


def decode_chunk(words: jnp.ndarray, lut_lsym: jnp.ndarray,
                 lut_lbits: jnp.ndarray, lut_dsym: jnp.ndarray,
                 lut_dbits: jnp.ndarray, out_len_dyn,
                 out_len_max: int, tables=None) -> jnp.ndarray:
    # deflate base/extra tables; passed in explicitly from the Pallas kernel
    # (kernels may not capture array constants), defaulted elsewhere.
    LEN_EXTRA_, LEN_BASE_, DIST_EXTRA_, DIST_BASE_ = (
        tables if tables is not None
        else (LEN_EXTRA, LEN_BASE, DIST_EXTRA, DIST_BASE))
    MC = max_cmds(out_len_max)

    # ---- Phase 1: Huffman token parse -> command list ---------------------
    def cond(s):
        bs, ci, out_cnt, done = s[0], s[1], s[2], s[6]
        return jnp.logical_and(~done,
               jnp.logical_and(out_cnt < out_len_dyn, ci < MC))

    def body(s):
        (bs, ci, out_cnt, lit_cnt, open_lit, lits, done,
         kinds, cmd_a, cmd_b) = s
        v = st.peek_bits(bs, enc.MAX_CODE_BITS)
        sym = jnp.take(lut_lsym, v.astype(jnp.int32), mode="clip")
        nb = jnp.take(lut_lbits, v.astype(jnp.int32), mode="clip")
        is_lit = (sym < 256) & (nb > 0)
        is_eob = (sym == 256) | (nb == 0)   # nb==0: invalid code, stop
        is_match = (sym > 256) & (nb > 0)
        # match decode (unconditional compute, masked advance)
        lc = jnp.clip(sym - 257, 0, 28)
        bs_m = st.skip_bits(bs, nb)
        eb = jnp.take(LEN_EXTRA_, lc)
        length = jnp.take(LEN_BASE_, lc) + st.peek_bits(bs_m, eb).astype(jnp.int32)
        bs_m = st.skip_bits(bs_m, eb)
        dv = st.peek_bits(bs_m, enc.MAX_CODE_BITS)
        dsym = jnp.take(lut_dsym, dv.astype(jnp.int32), mode="clip")
        dnb = jnp.take(lut_dbits, dv.astype(jnp.int32), mode="clip")
        bs_m = st.skip_bits(bs_m, dnb)
        deb = jnp.take(DIST_EXTRA_, dsym)
        dist = jnp.take(DIST_BASE_, dsym) + st.peek_bits(bs_m, deb).astype(jnp.int32)
        bs_m = st.skip_bits(bs_m, deb)
        # literal bookkeeping
        lits = lits.at[lit_cnt].set((sym & 0xFF).astype(jnp.uint8))
        prev_b = jnp.take(cmd_b, ci - 1, mode="clip")
        prev_a = jnp.take(cmd_a, ci - 1, mode="clip")
        extend = open_lit & is_lit & (prev_b < LITRUN_CAP) & (ci > 0)
        # where to write this token's command
        slot = jnp.where(extend, ci - 1, ci)
        new_kind = is_match
        new_a = jnp.where(is_match, dist,
                          jnp.where(extend, prev_a, lit_cnt))
        new_b = jnp.where(is_match, length,
                          jnp.where(extend, prev_b + 1, 1))
        do_write = ~is_eob
        wslot = jnp.where(do_write, slot, MC)        # OOB write drops
        kinds = kinds.at[wslot].set(new_kind)
        cmd_a = cmd_a.at[wslot].set(new_a)
        cmd_b = cmd_b.at[wslot].set(new_b)
        ci = ci + jnp.where(do_write & ~extend, 1, 0)
        lit_cnt = lit_cnt + jnp.where(is_lit, 1, 0)
        out_cnt = out_cnt + jnp.where(is_lit, 1, jnp.where(is_match, length, 0))
        open_lit = is_lit
        bs = jax.tree.map(lambda a, b: jnp.where(is_match, a, b),
                          bs_m, st.skip_bits(bs, nb))
        return (bs, ci, out_cnt, lit_cnt, open_lit, lits,
                done | is_eob, kinds, cmd_a, cmd_b)

    with jax.named_scope(harness.PHASE1):
        init = (st.bitstream(words), jnp.int32(0), jnp.int32(0),
                jnp.int32(0), jnp.bool_(False),
                jnp.zeros((out_len_max + CMD_WIN,), jnp.uint8),
                jnp.bool_(False),
                jnp.zeros((MC,), jnp.bool_),
                jnp.zeros((MC,), jnp.int32),
                jnp.zeros((MC,), jnp.int32))
        s = lax.while_loop(cond, body, init)
    n_cmds, lits, kinds, cmd_a, cmd_b = s[1], s[5], s[7], s[8], s[9]

    # ---- Phase 2: execute commands (Table II writes) ----------------------
    out0 = st.outstream(out_len_max + CMD_WIN, jnp.uint8)

    def cond2(s2):
        i, out = s2
        return jnp.logical_and(i < n_cmds, out.pos < out_len_dyn)

    def body2(s2):
        i, out = s2
        kind = jnp.take(kinds, i, mode="clip")
        a = jnp.take(cmd_a, i, mode="clip")
        b = jnp.take(cmd_b, i, mode="clip")
        out_m = st.memcpy(out, a, b, CMD_WIN)
        out_l = st.write_from(out, lits, a, b, CMD_WIN)
        out = jax.tree.map(lambda x, y: jnp.where(kind, x, y), out_m, out_l)
        return i + 1, out

    with jax.named_scope(harness.PHASE2):
        _, out = lax.while_loop(cond2, body2, (jnp.int32(0), out0))
        idx = jnp.arange(out_len_max, dtype=jnp.int32)
        return jnp.where(idx < out_len_dyn, out.buf[:out_len_max], 0)


def decode_chunk_scalar(words, lut_lsym, lut_lbits, lut_dsym, lut_dbits,
                        out_len_dyn, out_len_max: int) -> jnp.ndarray:
    """§V-E single-thread baseline: one output byte per loop step (match
    copies proceed byte-by-byte through a scalar back-reference cursor)."""
    def cond(s):
        pos, done = s[1], s[6]
        return jnp.logical_and(~done, pos < out_len_dyn)

    def body(s):
        bs, pos, rem, src, is_m, buf, done = s
        need = rem == 0
        # decode next token only when needed
        v = st.peek_bits(bs, enc.MAX_CODE_BITS)
        sym = jnp.take(lut_lsym, v.astype(jnp.int32), mode="clip")
        nb = jnp.take(lut_lbits, v.astype(jnp.int32), mode="clip")
        is_lit = (sym < 256) & (nb > 0)
        is_eob = (sym == 256) | (nb == 0)
        lc = jnp.clip(sym - 257, 0, 28)
        bs_m = st.skip_bits(bs, nb)
        eb = jnp.take(LEN_EXTRA, lc)
        length = jnp.take(LEN_BASE, lc) + st.peek_bits(bs_m, eb).astype(jnp.int32)
        bs_m = st.skip_bits(bs_m, eb)
        dv = st.peek_bits(bs_m, enc.MAX_CODE_BITS)
        dsym = jnp.take(lut_dsym, dv.astype(jnp.int32), mode="clip")
        dnb = jnp.take(lut_dbits, dv.astype(jnp.int32), mode="clip")
        bs_m = st.skip_bits(bs_m, dnb)
        deb = jnp.take(DIST_EXTRA, dsym)
        dist = jnp.take(DIST_BASE, dsym) + st.peek_bits(bs_m, deb).astype(jnp.int32)
        bs_m = st.skip_bits(bs_m, deb)
        bs_lit = st.skip_bits(bs, nb)
        new_is_m = (sym > 256) & (nb > 0)
        rem = jnp.where(need, jnp.where(is_lit, 1, length), rem)
        is_m = jnp.where(need, new_is_m, is_m)
        src = jnp.where(need, jnp.where(new_is_m, pos - dist, 0), src)
        lit_byte = (sym & 0xFF).astype(jnp.uint8)
        copy_byte = jnp.take(buf, src, mode="clip")
        # freeze token decode state when mid-copy
        bs = jax.tree.map(
            lambda new_m, new_l, old: jnp.where(
                need, jnp.where(new_is_m, new_m, new_l), old),
            bs_m, bs_lit, bs)
        done = done | (need & is_eob)
        emit = ~(need & is_eob)
        wpos = jnp.where(emit, pos, out_len_max + 8)
        buf = buf.at[wpos].set(jnp.where(is_m, copy_byte,
                                         jnp.where(need, lit_byte,
                                                   copy_byte)))
        pos = pos + jnp.where(emit, 1, 0)
        rem = rem - jnp.where(emit, 1, 0)
        src = src + 1
        return bs, pos, rem, src, is_m, buf, done

    init = (st.bitstream(words), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.bool_(False), jnp.zeros((out_len_max + 16,), jnp.uint8),
            jnp.bool_(False))
    s = lax.while_loop(cond, body, init)
    return s[5][:out_len_max]


# --------------------------------------------------------------------------
# registry plumbing: device operands + the DecodeSpec bodies
# --------------------------------------------------------------------------


def _chunk_inputs(dev):
    """Per-chunk operands: the word stream plus the four per-chunk LUTs."""
    words = dev.get("comp_words")
    if words is None:
        words = harness.words_view(dev["comp"])
    return (words,) + tuple(dev[k].astype(jnp.int32) for k in
                            ("lut_lsym", "lut_lbits", "lut_dsym", "lut_dbits"))


def _body(inputs, consts, out_len, *, chunk_elems, width, bits):
    words, ls, lb, ds, db = inputs
    return decode_chunk(words, ls, lb, ds, db, out_len, chunk_elems,
                        tables=consts or None)


def _body_scalar(inputs, consts, out_len, *, chunk_elems, width, bits):
    words, ls, lb, ds, db = inputs
    return decode_chunk_scalar(words, ls, lb, ds, db, out_len, chunk_elems)


def _body_oracle(inputs, consts, out_len, *, chunk_elems, width, bits):
    words, ls, lb, ds, db = inputs
    return ref.decode_tdeflate_impl(words, ls, lb, ds, db, out_len, chunk_elems)


def _demo_data(n, rng):
    """Repetitive text bytes (LZ matches + skewed literal frequencies)."""
    motifs = [b"the quick brown fox ", b"abcabcabc", b"codag streams "]
    out = bytearray()
    while len(out) < n:
        out += motifs[int(rng.integers(0, len(motifs)))]
    return np.frombuffer(bytes(out[:n]), np.uint8).copy()


CODEC = registry.register(registry.Codec(
    name=fmt.TDEFLATE,
    encode=enc.compress_tdeflate,
    decode=harness.DecodeSpec(
        body=_body,
        body_scalar=_body_scalar,
        body_oracle=_body_oracle,
        chunk_inputs=_chunk_inputs,
        consts=lambda: (LEN_EXTRA, LEN_BASE, DIST_EXTRA, DIST_BASE),
    ),
    needs_words=True,
    byte_stream=True,
    demo_data=_demo_data,
))
