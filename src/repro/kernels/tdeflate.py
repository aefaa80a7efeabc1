"""tdeflate (Deflate-semantics) decode — Pallas TPU kernel.

Two-phase decode/execute split (the same split RAPIDS' leader-thread decode /
collaborative write uses, and the reason the paper only gains 1.18x on
Deflate — the Huffman stage is irreducibly serial):

  Phase 1 (serial per chunk): table-driven Huffman token parse
      12-bit LSB-first peek -> flat LUT -> (symbol, nbits); extra bits for
      lengths/distances.  Consecutive literals are batched into `litrun`
      commands whose bytes land in a contiguous side buffer.  Under
      ``vmap`` over chunks a batching rule of its own runs one loop over
      all the rows until the last one ends: a row that has ended changes
      nothing, so no step selects or copies a whole buffer.
  Phase 2 (all-lane): the command lengths' prefix sum gives each command's
      output offset; ``harness.resolve_backrefs`` maps every output byte to
      its command, points match bytes back by their distance and resolves
      chained and overlapping copies by pointer doubling
      (``ceil(log2(chunk_elems))`` rounds), after which one gather reads
      every byte from the literal side buffer.  No loop runs per command.

Chunk-level parallelism comes from the harness's generic chunk-per-grid-cell
``pallas_call`` wrapper, exactly CODAG's warp-per-chunk provisioning.  LZ
copies read earlier output, so this codec plugs its own chunk bodies into the
``DecodeSpec`` interface instead of a ``TwoPhaseSpec``; the deflate
base/extra tables ride the wrapper's broadcast-constant lane.  The serial
oracle (``ref.decode_tdeflate_impl``, Table II ``memcpy``) executes the
tokens one at a time; it is the reference the other backends are checked
against.
"""
from __future__ import annotations

from typing import NamedTuple

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
CMD_WIN = 272             # slack past out_len_max on the literal side buffer


def max_cmds(out_len: int) -> int:
    # worst case: alternating match(>=3) + litrun(>=1) = 2 cmds / 4 bytes
    return out_len // 2 + 4


class _Tokens(NamedTuple):
    """Phase 1's loop state for one chunk: the bit position and counters,
    then the side buffer and command tables the token steps write."""

    pos: jnp.ndarray        # bit position in the chunk's words
    ci: jnp.ndarray         # commands written
    out_cnt: jnp.ndarray    # output bytes the commands cover
    lit_cnt: jnp.ndarray    # literals in ``lits``
    run: jnp.ndarray        # length of the open litrun (0: none open)
    done: jnp.ndarray       # end-of-block or an invalid code seen
    lits: jnp.ndarray       # literal side buffer
    kinds: jnp.ndarray      # command is a match
    cmd_a: jnp.ndarray      # match distance / litrun's first literal
    cmd_b: jnp.ndarray      # match length / litrun length


def _active(s: _Tokens, out_len_dyn) -> jnp.ndarray:
    return ~s.done & (s.out_cnt < out_len_dyn) & (s.ci < s.kinds.shape[0])


def _token_step(s: _Tokens, words, luts, tables, out_len_dyn) -> _Tokens:
    """Parse one token of one chunk and write its command.

    A chunk that is no longer active keeps its counters and writes to the
    dropped slot past each buffer, so a loop over many chunks can run every
    chunk's step until the last one ends, and the buffers see nothing but
    their writes (no select, so XLA updates them in place)."""
    lut_lsym, lut_lbits, lut_dsym, lut_dbits = luts
    len_extra, len_base, dist_extra, dist_base = tables
    act = _active(s, out_len_dyn)
    bs = st.BitStream(words, s.pos)
    v = st.peek_bits(bs, enc.MAX_CODE_BITS)
    sym = jnp.take(lut_lsym, v.astype(jnp.int32), mode="clip")
    nb = jnp.take(lut_lbits, v.astype(jnp.int32), mode="clip")
    is_lit = (sym < 256) & (nb > 0)
    is_eob = (sym == 256) | (nb == 0)   # nb==0: invalid code, stop
    is_match = (sym > 256) & (nb > 0)
    # match decode (unconditional compute, masked advance)
    lc = jnp.clip(sym - 257, 0, 28)
    bs_m = st.skip_bits(bs, nb)
    eb = jnp.take(len_extra, lc)
    length = jnp.take(len_base, lc) + st.peek_bits(bs_m, eb).astype(jnp.int32)
    bs_m = st.skip_bits(bs_m, eb)
    dv = st.peek_bits(bs_m, enc.MAX_CODE_BITS)
    dsym = jnp.take(lut_dsym, dv.astype(jnp.int32), mode="clip")
    dnb = jnp.take(lut_dbits, dv.astype(jnp.int32), mode="clip")
    bs_m = st.skip_bits(bs_m, dnb)
    deb = jnp.take(dist_extra, dsym)
    dist = jnp.take(dist_base, dsym) + st.peek_bits(bs_m, deb).astype(jnp.int32)
    bs_m = st.skip_bits(bs_m, deb)
    # a literal extends the open litrun (slot ci - 1, first literal
    # lit_cnt - run) until it holds LITRUN_CAP literals
    extend = is_lit & (s.run > 0) & (s.run < LITRUN_CAP)
    slot = jnp.where(extend, s.ci - 1, s.ci)
    new_a = jnp.where(is_match, dist,
                      s.lit_cnt - jnp.where(extend, s.run, 0))
    new_b = jnp.where(is_match, length, jnp.where(extend, s.run + 1, 1))
    do_write = act & ~is_eob
    wslot = jnp.where(do_write, slot, s.kinds.shape[0])   # OOB write drops
    lslot = jnp.where(act, s.lit_cnt, s.lits.shape[0])
    pos = jnp.where(is_match, bs_m.pos, s.pos + nb)

    def keep(new, old):
        return jnp.where(act, new, old)

    return _Tokens(
        pos=keep(pos, s.pos),
        ci=s.ci + jnp.where(do_write & ~extend, 1, 0),
        out_cnt=keep(s.out_cnt + jnp.where(
            is_lit, 1, jnp.where(is_match, length, 0)), s.out_cnt),
        lit_cnt=keep(s.lit_cnt + jnp.where(is_lit, 1, 0), s.lit_cnt),
        run=keep(jnp.where(is_lit, new_b, 0), s.run),
        done=s.done | (act & is_eob),
        lits=s.lits.at[lslot].set((sym & 0xFF).astype(jnp.uint8),
                                  mode="drop"),
        kinds=s.kinds.at[wslot].set(is_match, mode="drop"),
        cmd_a=s.cmd_a.at[wslot].set(new_a, mode="drop"),
        cmd_b=s.cmd_b.at[wslot].set(new_b, mode="drop"))


def _token_loop(words, luts, tables, out_len_dyn, s: _Tokens) -> _Tokens:
    """Phase 1 over one chunk (1-D ``words``) or over chunk rows (2-D: the
    operands but the deflate ``tables`` have a leading row axis; the tables
    do too where they differ by row).  Rows run one ``while_loop`` whose
    predicate is a scalar, "some row is active", and the words are closed
    over, not carried: no step selects or copies a whole buffer."""
    step, active = _token_step, _active
    if words.ndim == 2:
        step = jax.vmap(step, in_axes=(0, 0, 0, 0 if tables[0].ndim == 2
                                       else None, 0))
        active = jax.vmap(active)
    return lax.while_loop(
        lambda s: jnp.any(active(s, out_len_dyn)),
        lambda s: step(s, words, luts, tables, out_len_dyn), s)


@jax.custom_batching.custom_vmap
def _parse_tokens(words, luts, tables, out_len_dyn, s: _Tokens) -> _Tokens:
    """Phase 1 of one chunk or of chunk rows; under ``vmap`` one loop over
    all the rows."""
    return _token_loop(words, luts, tables, out_len_dyn, s)


@_parse_tokens.def_vmap
def _parse_tokens_vmap(axis_size, in_batched, words, luts, tables,
                       out_len_dyn, s):
    # the mapped axis, and the rows the call already has (nested vmap),
    # become one axis of rows; operands that are not mapped are broadcast,
    # and the deflate tables stay shared unless they differ by row
    wb, lb, tb, ob, sb = in_batched
    lead = (axis_size,) + words.shape[int(wb):-1]
    n = int(np.prod(lead))

    def rows(x, b):
        if not b:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        return x.reshape((n,) + x.shape[len(lead):])

    def table_rows(t, b):
        if not b:
            t = jnp.broadcast_to(t, (axis_size,) + t.shape)
        if t.ndim < len(lead) + 1:   # one table per index of the mapped axis
            t = t[:, None]
        return jnp.broadcast_to(t, lead + t.shape[-1:]).reshape(n, -1)

    if any(tb) or tables[0].ndim == 2:
        tables = tuple(map(table_rows, tables, tb))
    out = _parse_tokens(rows(words, wb), tuple(map(rows, luts, lb)), tables,
                        rows(out_len_dyn, ob), jax.tree.map(rows, s, sb))
    return (jax.tree.map(lambda x: x.reshape(lead + x.shape[1:]), out),
            jax.tree.map(lambda _: True, out))


def decode_chunk(words: jnp.ndarray, lut_lsym: jnp.ndarray,
                 lut_lbits: jnp.ndarray, lut_dsym: jnp.ndarray,
                 lut_dbits: jnp.ndarray, out_len_dyn,
                 out_len_max: int, tables=None) -> jnp.ndarray:
    # deflate base/extra tables; passed in explicitly from the Pallas kernel
    # (kernels may not capture array constants), defaulted elsewhere.
    tables = tuple(tables if tables is not None
                   else (LEN_EXTRA, LEN_BASE, DIST_EXTRA, DIST_BASE))
    MC = max_cmds(out_len_max)

    # ---- Phase 1: Huffman token parse -> command list ---------------------
    with jax.named_scope(harness.PHASE1):
        zero = jnp.int32(0)
        init = _Tokens(pos=zero, ci=zero, out_cnt=zero, lit_cnt=zero,
                       run=zero, done=jnp.bool_(False),
                       lits=jnp.zeros((out_len_max + CMD_WIN,), jnp.uint8),
                       kinds=jnp.zeros((MC,), jnp.bool_),
                       cmd_a=jnp.zeros((MC,), jnp.int32),
                       cmd_b=jnp.zeros((MC,), jnp.int32))
        s = _parse_tokens(words, (lut_lsym, lut_lbits, lut_dsym, lut_dbits),
                          tables, out_len_dyn, init)
    n_cmds, lits, kinds, cmd_a, cmd_b = s.ci, s.lits, s.kinds, s.cmd_a, s.cmd_b

    # ---- Phase 2: all-lane back-reference resolution ----------------------
    with jax.named_scope(harness.PHASE2):
        # command start offsets: exclusive prefix sum of the lengths (slots
        # past n_cmds are never written, so their length is 0); each command
        # starts below out_len_dyn, where Phase 1 stops
        ends = jnp.cumsum(cmd_b)
        starts = jnp.where(jnp.arange(MC) < n_cmds, ends - cmd_b, out_len_max)
        # a match's cmd_a is its distance, a litrun's its first literal
        src = harness.resolve_backrefs(starts, kinds, cmd_a, cmd_a, 1,
                                       out_len_max)
        out = harness.lane_take(lits, src)
        idx = jnp.arange(out_len_max, dtype=jnp.int32)
        return jnp.where(idx < jnp.minimum(ends[-1], out_len_dyn), out, 0)


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
        body_oracle=_body_oracle,
        chunk_inputs=_chunk_inputs,
        consts=lambda: (LEN_EXTRA, LEN_BASE, DIST_EXTRA, DIST_BASE),
    ),
    needs_words=True,
    byte_stream=True,
    demo_data=_demo_data,
))
