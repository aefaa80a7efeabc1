"""tdeflate's all-lane Phase 2 (pointer-doubling back-reference resolution)
against the serial oracle and the input.

The host encoder's greedy LZ77 rarely emits the token layouts that stress
the resolution, so most cases hand the encoder a token list of their own
(``encoders._lz77_tokens`` swapped per chunk): the Huffman coding, the
bitstream and the decode are the real ones.
"""
import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoders as enc, format as fmt
from repro.kernels import ops, tdeflate

BACKENDS = ("xla", "oracle")


def _apply(tokens) -> bytes:
    """The bytes a token list decodes to (plain serial LZ77)."""
    out = bytearray()
    for t in tokens:
        if t[0] == "l":
            out.append(t[1])
        else:
            _, length, dist = t
            assert 1 <= dist <= len(out) and enc.MIN_MATCH <= length <= enc.MAX_MATCH
            for _ in range(length):
                out.append(out[-dist])
    return bytes(out)


def _blob(monkeypatch, chunks_tokens, chunk_bytes):
    """Encode the chunks' token lists as one tdeflate blob."""
    datas = [_apply(t) for t in chunks_tokens]
    assert all(len(d) == chunk_bytes for d in datas[:-1])
    assert 0 < len(datas[-1]) <= chunk_bytes
    by_data = dict(zip(datas, chunks_tokens))
    used = []

    def tokens(data):
        used.append(data)
        return list(by_data[data])

    monkeypatch.setattr(enc, "_lz77_tokens", tokens)
    arr = np.frombuffer(b"".join(datas), np.uint8).copy()
    blob = enc.compress(arr, fmt.TDEFLATE, chunk_bytes=chunk_bytes)
    assert used == datas                      # every chunk took its tokens
    return blob, arr


def _decode(blob, backend):
    dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
    return np.asarray(ops.decode(dev, codec=fmt.TDEFLATE, width=1,
                                 chunk_elems=blob.chunk_elems,
                                 backend=backend))


def _check(blob, arr):
    got = {b: _decode(blob, b) for b in BACKENDS}
    for i in range(blob.num_chunks):
        ol = int(blob.out_lens[i])
        np.testing.assert_array_equal(got["xla"][i, :ol], got["oracle"][i, :ol],
                                      err_msg=f"xla vs oracle, chunk {i}")
    # bytes past a chunk's out_len decode to 0, as the serial executor left
    for i in range(blob.num_chunks):
        assert not got["xla"][i, int(blob.out_lens[i]):].any()
    np.testing.assert_array_equal(
        got["xla"].reshape(-1)[:blob.total_elems], arr)


def _lits(rng, n):
    return [("l", int(v)) for v in rng.integers(0, 256, n)]


def _random_tokens(rng, n_bytes):
    """Valid random tokens: literal runs and matches of every length and
    distance, many of them overlapping (dist < length) or reading earlier
    matches."""
    toks, size = _lits(rng, 4), 4
    while size < n_bytes:
        room = n_bytes - size
        if rng.random() < 0.3 or room < enc.MIN_MATCH:
            k = int(min(room, rng.integers(1, 300)))
            toks += _lits(rng, k)
            size += k
            continue
        length = int(rng.integers(enc.MIN_MATCH, min(enc.MAX_MATCH, room) + 1))
        dist = int(rng.choice([1, 2, 3, rng.integers(1, size + 1)]))
        toks.append(("m", length, min(dist, size)))
        size += length
    return toks


def _dist1_chain():
    # one literal, then dist-1 matches over the whole chunk: the last byte's
    # chain has 4095 hops, so it needs every one of log2(4096) = 12 rounds
    toks = [("l", 7)] + [("m", 258, 1)] * 15 + [("m", 4095 - 15 * 258, 1)]
    rounds = (4096 - 1).bit_length()
    assert 2 ** (rounds - 1) < len(_apply(toks)) - 1 <= 2 ** rounds
    return [toks], 4096


def _nested_sources():
    # matches whose sources lie inside earlier matches, which themselves
    # read earlier matches; then a copy spanning a match/literal boundary
    toks = [("l", c) for c in b"abcdefgh"]
    toks += [("m", 20, 8), ("m", 30, 13), ("l", 200), ("m", 40, 31),
             ("m", 9, 50), ("l", 201), ("l", 202), ("m", 100, 3)]
    toks += [("m", 258, 150), ("m", 258, 259)]
    n = len(_apply(toks))
    return [toks], n


def _litrun(n_lits):
    # LITRUN_CAP literals fill one litrun command; one more opens a second
    def make():
        rng = np.random.default_rng(n_lits)
        toks = _lits(rng, n_lits) + [("m", 10, 5)] + _lits(rng, n_lits)
        toks += [("m", 258, n_lits + 3)] + _lits(rng, n_lits)
        return [toks], len(_apply(toks))
    return make


def _short_last_chunk():
    # three full chunks and a last one whose out_len is below chunk_elems
    rng = np.random.default_rng(11)
    chunks = [_random_tokens(rng, 1024) for _ in range(3)]
    chunks = [_trim(t, 1024) for t in chunks] + [_trim(_random_tokens(rng, 333), 333)]
    return chunks, 1024


def _trim(tokens, n):
    """Cut a token list to decode to exactly ``n`` bytes."""
    out, size = [], 0
    for t in tokens:
        if size == n:
            break
        if t[0] == "l":
            out.append(t)
            size += 1
        elif size + t[1] - n >= 0 and n - size < enc.MIN_MATCH:
            out += [("l", 0)] * (n - size)
            size = n
        else:
            length = min(t[1], n - size)
            out.append(("m", length, t[2]))
            size += length
    return out


CASES = {
    "dist1_chain_every_round": _dist1_chain,
    "sources_inside_matches": _nested_sources,
    "litrun_256": _litrun(tdeflate.LITRUN_CAP),
    "litrun_257": _litrun(tdeflate.LITRUN_CAP + 1),
    "short_last_chunk": _short_last_chunk,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_phase2_matches_oracle_scalar_and_input(monkeypatch, case):
    chunks_tokens, chunk_bytes = CASES[case]()
    blob, arr = _blob(monkeypatch, chunks_tokens, chunk_bytes)
    if case == "short_last_chunk":
        assert int(blob.out_lens[-1]) < blob.chunk_elems
    _check(blob, arr)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_phase2_random_tokens(monkeypatch, seed):
    rng = np.random.default_rng(seed)
    chunks = [_trim(_random_tokens(rng, 2048), 2048) for _ in range(2)]
    chunks.append(_trim(_random_tokens(rng, 700), 700))
    blob, arr = _blob(monkeypatch, chunks, 2048)
    _check(blob, arr)


def test_zero_length_pad_rows_decode_to_zero():
    arr = np.frombuffer(b"codag streams " * 300, np.uint8).copy()
    blob = enc.compress(arr, fmt.TDEFLATE, chunk_bytes=1000)
    padded = fmt.pad_table_rows(blob, blob.num_chunks + 3)
    for b in BACKENDS:
        got = _decode(padded, b)
        assert got.shape[0] == blob.num_chunks + 3
        assert not got[blob.num_chunks:].any(), b
        np.testing.assert_array_equal(
            got[:blob.num_chunks].reshape(-1)[:arr.size], arr, err_msg=b)


def test_stream_ending_before_out_len_leaves_zeros():
    """A stream whose end-of-block comes before ``out_len``: the lanes past
    the last command decode to 0 on every backend."""
    arr = np.frombuffer(b"the quick brown fox " * 40, np.uint8).copy()
    blob = enc.compress(arr, fmt.TDEFLATE, chunk_bytes=1024)
    assert int(blob.out_lens[-1]) < blob.chunk_elems
    dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
    dev["out_lens"] = jnp.full_like(dev["out_lens"], blob.chunk_elems)
    got = {b: np.asarray(ops.decode(dev, codec=fmt.TDEFLATE, width=1,
                                    chunk_elems=blob.chunk_elems, backend=b))
           for b in BACKENDS}
    np.testing.assert_array_equal(got["xla"], got["oracle"])
    last = got["xla"][-1]
    ol = int(blob.out_lens[-1])
    np.testing.assert_array_equal(last[:ol], arr[-ol:])
    assert not last[ol:].any()


def _sub_jaxprs(eqn):
    for p in eqn.params.values():
        for q in (p if isinstance(p, (tuple, list)) else (p,)):
            if isinstance(q, jex.ClosedJaxpr):
                yield q.jaxpr
            elif isinstance(q, jex.Jaxpr):
                yield q


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for sub in _sub_jaxprs(e):
            yield from _eqns(sub)


def test_phase2_has_no_loop_over_commands():
    """The decode body holds Phase 1's token loop and the fixed doubling
    rounds, nothing whose trip count is the number of commands (loops
    nested in calls, such as Phase 1's ``custom_vmap``, included)."""
    chunk_elems = 4096
    n_words = 600
    jaxpr = jax.make_jaxpr(
        lambda w, ls, lb, ds, db, n: tdeflate.decode_chunk(
            w, ls, lb, ds, db, n, chunk_elems))(
        jnp.zeros((n_words,), jnp.uint32),
        *[jnp.zeros((1 << enc.MAX_CODE_BITS,), jnp.int32)] * 4, jnp.int32(0))
    loops = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name in ("while", "scan")]
    assert [e.primitive.name for e in loops] == ["while", "scan"]
    assert loops[1].params["length"] == (chunk_elems - 1).bit_length()


@pytest.mark.parametrize("batched", ["both", "table", "index", "nested"])
def test_lane_take_matches_take_under_vmap(batched):
    """The flat gather ``vmap`` makes of ``harness.lane_take`` reads what a
    per-row ``jnp.take(mode="clip")`` reads, out-of-range indices included."""
    from repro.kernels import harness
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.integers(0, 1000, (6, 50)).astype(np.int32))
    i = jnp.asarray(rng.integers(-5, 60, (6, 70)).astype(np.int32))

    def want_row(r, j):
        return jnp.take(r, j, mode="clip")

    if batched == "both":
        args, axes = (a, i), (0, 0)
    elif batched == "table":
        args, axes = (a, i[0]), (0, None)
    elif batched == "index":
        args, axes = (a[0], i), (None, 0)
    else:
        args, axes = (a.reshape(2, 3, 50), i.reshape(2, 3, 70)), (0, 0)
    f_got = jax.vmap(harness.lane_take, in_axes=axes)
    f_want = jax.vmap(want_row, in_axes=axes)
    if batched == "nested":
        f_got, f_want = jax.vmap(f_got), jax.vmap(f_want)
    np.testing.assert_array_equal(np.asarray(jax.jit(f_got)(*args)),
                                  np.asarray(f_want(*args)))
