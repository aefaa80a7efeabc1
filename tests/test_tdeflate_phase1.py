"""tdeflate's Phase 1 (the Huffman token parse) over many chunk rows.

Under ``vmap`` the token loop runs once over all the rows, with a scalar
predicate, and writes each buffer in place: these tests check that
structure in the traced program, and that rows ending at very different
steps (one literal beside tens of thousands of tokens, zero-length pads, an
invalid code, the command-table bound) decode bit-exactly against the
serial oracle, the unbatched (Pallas) body and the input.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoders as enc, format as fmt
from repro.kernels import ops, tdeflate

from test_tdeflate_phase2 import (BACKENDS, _apply, _blob, _decode, _eqns,
                                  _lits)


def _vmapped_jaxpr(rows, n_words, chunk_elems):
    return jax.make_jaxpr(jax.vmap(
        lambda w, ls, lb, ds, db, n: tdeflate.decode_chunk(
            w, ls, lb, ds, db, n, chunk_elems)))(
        jnp.zeros((rows, n_words), jnp.uint32),
        *[jnp.zeros((rows, 1 << enc.MAX_CODE_BITS), jnp.int32)] * 4,
        jnp.zeros((rows,), jnp.int32))


def test_phase1_loop_is_one_scalar_loop_over_rows():
    """Under ``vmap``: one token ``while`` whose condition is a scalar, whose
    carry holds no word array, and whose body selects or copies no buffer:
    every value in the body as large as a buffer is a buffer's write."""
    rows, n_words, chunk_elems = 5, 600, 4096
    jaxpr = _vmapped_jaxpr(rows, n_words, chunk_elems)
    whiles = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "while"]
    assert len(whiles) == 1
    loop = whiles[0]
    cond = loop.params["cond_jaxpr"]
    assert [v.aval.shape for v in cond.jaxpr.outvars] == [()]
    assert cond.out_avals[0].dtype == jnp.bool_
    carry = [v.aval for v in loop.outvars]
    assert not any(a.dtype == jnp.uint32 for a in carry), carry
    assert not any(n_words in a.shape for a in carry), carry
    assert sorted(a.shape for a in carry if a.ndim == 2) == sorted(
        [(rows, chunk_elems + tdeflate.CMD_WIN)]
        + [(rows, tdeflate.max_cmds(chunk_elems))] * 3)
    body = list(_eqns(loop.params["body_jaxpr"].jaxpr))
    wide = [e.primitive.name for e in body
            if any(getattr(v.aval, "size", 0) >= rows * n_words
                   for v in e.outvars)]
    assert sorted(wide) == ["scatter"] * 4, wide


def _long_and_one_literal():
    # a 65,536-byte chunk of 47,000 tokens (mostly literals, like the
    # benchmark's price columns), then a last chunk of one literal that is
    # done after one step
    rng = np.random.default_rng(47)
    n_match = (65536 - 47000) // 2
    kinds = np.zeros(47000, bool)
    kinds[1 + rng.choice(46999, n_match, replace=False)] = True
    toks, size = [], 0
    for is_m in kinds:
        if is_m:
            toks.append(("m", 3, int(rng.integers(1, min(size, 32768) + 1))))
            size += 3
        else:
            toks.append(("l", int(rng.integers(0, 256))))
            size += 1
    assert size == 65536 and len(toks) == 47000
    return [toks, [("l", 9)]], 65536


def _with_pad_rows():
    rng = np.random.default_rng(3)
    chunks = [_lits(rng, 2000) + [("m", 48, 7)], _lits(rng, 5)]
    return chunks, 2048


ROW_MIXES = {
    "one_literal_beside_47k_tokens": _long_and_one_literal,
    "pad_rows": _with_pad_rows,
}


@pytest.mark.parametrize("mix", sorted(ROW_MIXES))
def test_rows_ending_far_apart_match_oracle_scalar_and_input(monkeypatch,
                                                             mix):
    chunks_tokens, chunk_bytes = ROW_MIXES[mix]()
    blob, arr = _blob(monkeypatch, chunks_tokens, chunk_bytes)
    n = blob.num_chunks
    if mix == "pad_rows":
        blob = fmt.pad_table_rows(blob, n + 3)
    got = {b: _decode(blob, b) for b in BACKENDS}
    np.testing.assert_array_equal(got["xla"], got["oracle"])
    np.testing.assert_array_equal(got["xla"][:n].reshape(-1)[:arr.size], arr)
    assert not got["xla"][n:].any()


def _phase1_rows(words, ls, lb, ds, db, out_lens, chunk_elems):
    """Phase 1's final state over the rows under ``vmap``, checked field by
    field, buffers included, against each row's own unbatched loop: a row
    that has ended changes nothing while the others run on."""
    mc = tdeflate.max_cmds(chunk_elems)
    zero = jnp.int32(0)
    init = tdeflate._Tokens(
        zero, zero, zero, zero, zero, jnp.bool_(False),
        jnp.zeros((chunk_elems + tdeflate.CMD_WIN,), jnp.uint8),
        jnp.zeros((mc,), bool), jnp.zeros((mc,), jnp.int32),
        jnp.zeros((mc,), jnp.int32))
    tables = (tdeflate.LEN_EXTRA, tdeflate.LEN_BASE, tdeflate.DIST_EXTRA,
              tdeflate.DIST_BASE)

    def parse(w, luts, n):
        return tdeflate._parse_tokens(w, luts, tables, n, init)

    luts = (ls, lb, ds, db)
    got = jax.jit(jax.vmap(parse))(words, luts, out_lens)
    one = jax.jit(parse)
    for i in range(words.shape[0]):
        want = one(words[i], tuple(t[i] for t in luts), out_lens[i])
        for name, g, w in zip(tdeflate._Tokens._fields, got, want):
            np.testing.assert_array_equal(g[i], w, err_msg=f"row {i} {name}")
    return got


def test_row_stopping_on_an_invalid_code():
    """A code with no symbol (``nb == 0``) stops its row there; the rows
    beside it run on to their end."""
    text = b"tdeflate rows stop apart: " * 150
    arr = np.frombuffer(text, np.uint8).copy()
    blob = enc.compress(arr, fmt.TDEFLATE, chunk_bytes=1024)
    dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
    # row 1: the literal ':' has no code any more (each chunk is coded on
    # its own, so a byte's first place in a row is a literal)
    row, z = 1, ord(":")
    lbits = np.asarray(dev["lut_lbits"]).copy()
    lbits[row][np.asarray(dev["lut_lsym"])[row] == z] = 0
    dev["lut_lbits"] = jnp.asarray(lbits)
    state = _phase1_rows(*tdeflate._chunk_inputs(dev), dev["out_lens"], 1024)
    assert [bool(d) for d in state.done] == [False, True, False, False]
    got = {b: np.asarray(ops.decode(dev, codec=fmt.TDEFLATE, width=1,
                                    chunk_elems=blob.chunk_elems, backend=b))
           for b in BACKENDS}
    np.testing.assert_array_equal(got["xla"], got["oracle"])
    rows = np.pad(arr, (0, -arr.size % 1024)).reshape(-1, 1024)
    stop = int(np.argmax(rows[row] == z))
    assert 0 < stop < 1024
    np.testing.assert_array_equal(got["xla"][row, :stop], rows[row, :stop])
    assert not got["xla"][row, stop:].any()
    for i in range(blob.num_chunks):
        if i != row:
            ol = int(blob.out_lens[i])
            np.testing.assert_array_equal(got["xla"][i, :ol],
                                          arr[i * 1024:i * 1024 + ol])


def test_row_reaching_the_command_bound(monkeypatch):
    """A row whose ``out_len`` passes ``chunk_elems`` (a table decoded with a
    smaller chunk size than it was written with) stops when its command
    table is full, ``max_cmds(chunk_elems)`` commands; its first
    ``chunk_elems`` bytes are still exact, as are the rows beside it."""
    small = 1024
    rng = np.random.default_rng(8)
    toks = _lits(rng, 4)
    while len(_apply(toks)) < 2 * small - 4:
        toks += [("l", int(rng.integers(0, 256))), ("m", 3, 1)]
    toks += _lits(rng, 2 * small - len(_apply(toks)))
    blob, arr = _blob(monkeypatch, [toks, _lits(rng, 300)], 2 * small)
    dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
    # Phase 1 alone: row 0 stops on the bound, short of its out_len
    mc = tdeflate.max_cmds(small)
    s = _phase1_rows(*tdeflate._chunk_inputs(dev), dev["out_lens"], small)
    assert [int(c) for c in s.ci] == [mc, 2]   # the short row: 256 + 44
    assert small < int(s.out_cnt[0]) < int(dev["out_lens"][0])
    assert not bool(s.done[0])
    got = {b: np.asarray(ops.decode(dev, codec=fmt.TDEFLATE, width=1,
                                    chunk_elems=small, backend=b))
           for b in BACKENDS}
    np.testing.assert_array_equal(got["xla"], got["oracle"])
    np.testing.assert_array_equal(got["xla"][0], arr[:small])
    np.testing.assert_array_equal(got["xla"][1, :300], arr[2 * small:])


def _rows_of(blob):
    dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
    return [*tdeflate._chunk_inputs(dev), dev["out_lens"]]


def _decode_chunk(chunk_elems):
    return lambda w, ls, lb, ds, db, n, tables=None: tdeflate.decode_chunk(
        w, ls, lb, ds, db, n, chunk_elems, tables=tables)


TABLES = (tdeflate.LEN_EXTRA, tdeflate.LEN_BASE, tdeflate.DIST_EXTRA,
          tdeflate.DIST_BASE)


@pytest.mark.parametrize("batching", ["nested", "nested_outer_lengths",
                                      "lengths_only", "tables_mapped",
                                      "nested_tables_mapped"])
def test_batched_rows_match_unbatched_rows(batching):
    """``vmap`` of ``vmap``, and operands that are not batched, decode what
    the unbatched body decodes row by row."""
    arr = tdeflate._demo_data(6 * 512 - 100, np.random.default_rng(4))
    blob = enc.compress(arr, fmt.TDEFLATE, chunk_bytes=512)
    args = _rows_of(blob)
    one = jax.jit(_decode_chunk(512))
    if batching == "nested":
        f = jax.vmap(jax.vmap(_decode_chunk(512)))
        args = [a.reshape((2, 3) + a.shape[1:]) for a in args]
        want = np.stack([one(*[a[i] for a in _rows_of(blob)])
                         for i in range(6)]).reshape(2, 3, 512)
    elif batching == "nested_outer_lengths":
        # outer axis: out_len only; inner axis: the rows
        f = jax.vmap(jax.vmap(_decode_chunk(512)),
                     in_axes=(None,) * 5 + (0,))
        lens = np.minimum(np.asarray(blob.out_lens)[None],
                          np.array([[511], [37]]))
        args = args[:5] + [jnp.asarray(lens, jnp.int32)]
        want = np.stack([[one(*[a[i] for a in _rows_of(blob)[:5]], lens[o, i])
                          for i in range(6)] for o in range(2)])
    elif batching == "tables_mapped":
        # the deflate tables mapped too, one copy per row
        f = jax.vmap(_decode_chunk(512))
        args = args + [tuple(jnp.stack([t] * 6) for t in TABLES)]
        want = np.stack([one(*[a[i] for a in args[:6]]) for i in range(6)])
    elif batching == "nested_tables_mapped":
        # outer axis: the rows' halves and a table copy each; inner: rows
        f = jax.vmap(jax.vmap(_decode_chunk(512), in_axes=(0,) * 6 + (None,)))
        args = [a.reshape((2, 3) + a.shape[1:]) for a in args]
        args.append(tuple(jnp.stack([t] * 2) for t in TABLES))
        want = np.stack([one(*[a[i] for a in _rows_of(blob)])
                         for i in range(6)]).reshape(2, 3, 512)
    else:
        # one chunk's words and tables, many lengths
        f = jax.vmap(_decode_chunk(512), in_axes=(None,) * 5 + (0,))
        lens = jnp.asarray([0, 1, 100, 300, 512], jnp.int32)
        args = [a[2] for a in args[:5]] + [lens]
        want = np.stack([one(*args[:5], n) for n in lens])
        # the rows that end on out_len, mid-stream, write nothing after it
        state = _phase1_rows(*[jnp.broadcast_to(a, (5,) + a.shape)
                               for a in args[:5]], lens, 512)
        assert not state.done.any()
        assert (state.out_cnt >= lens).all() and (state.out_cnt < 512 + 258).all()
        np.testing.assert_array_equal(want[-1], arr[1024:1536])
    got = np.asarray(jax.jit(f)(*args))
    np.testing.assert_array_equal(got, np.asarray(want))


def test_pallas_rows_match_xla_rows(monkeypatch):
    """The Pallas body (one chunk per grid cell, the unbatched loop) and the
    batched loop give identical rows, rows ending far apart included."""
    rng = np.random.default_rng(12)
    chunks = [_lits(rng, 3) + [("m", 258, 1)] * 3 + [("m", 247, 2)],
              _lits(rng, 1024), _lits(rng, 1)]
    blob, arr = _blob(monkeypatch, chunks, 1024)
    got = {b: _decode(blob, b) for b in ("xla", "pallas")}
    np.testing.assert_array_equal(got["xla"], got["pallas"])
    np.testing.assert_array_equal(got["xla"].reshape(-1)[:arr.size], arr)
