"""Codec-plugin framework: registry completeness + ops-layer satellites.

Covers:
  * the registry-completeness contract CI gates on (every registered codec
    has full hooks and appears in the bench-smoke matrix),
  * the reentrant ``ops.count_dispatches`` (nested contexts),
  * the ``ops.words_view`` zero-padding fix for odd-width rows.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, encoders as enc, format as fmt, registry
from repro.kernels import ops

RNG = np.random.default_rng(23)


# --------------------------------------------------------------------------
# registry completeness
# --------------------------------------------------------------------------


def test_registry_covers_builtin_codecs():
    # superset, not equality: third-party plugins may register extra codecs
    assert set(fmt.CODECS) <= set(registry.names())


@pytest.mark.parametrize("name", registry.names())
def test_registered_codec_is_complete(name):
    """Every codec declares the full plugin surface the system relies on."""
    codec = registry.get(name)
    assert codec.name == name
    assert callable(codec.encode)
    spec = codec.decode
    assert callable(spec.body)
    # demo_data drives the bench matrices and the batch-coverage test
    assert codec.demo_data is not None
    arr = codec.demo_data(512, RNG)
    assert isinstance(arr, np.ndarray) and arr.size == 512
    # the declared hooks actually round-trip
    ca = api.compress(arr, name, chunk_bytes=777)
    assert np.array_equal(api.decompress(ca), arr)


def test_bench_smoke_matrices_cover_registry():
    """CI gate: a registered codec missing from the bench-smoke matrix
    fails here (and in scripts/check_registry.py)."""
    from benchmarks import batched
    assert set(batched.codec_matrix()) == set(registry.names())


def test_unknown_codec_raises():
    with pytest.raises(ValueError, match="unknown codec"):
        registry.get("no_such_codec")
    with pytest.raises(ValueError, match="unknown codec"):
        enc.compress(np.zeros(4, np.uint32), "no_such_codec")


def test_group_key_uses_registry_static_bits():
    b9 = enc.compress(RNG.integers(0, 2 ** 9, 256).astype(np.uint32),
                      fmt.BITPACK, 512, bits=9)
    b7 = enc.compress(RNG.integers(0, 2 ** 7, 256).astype(np.uint32),
                      fmt.BITPACK, 512, bits=7)
    assert fmt.group_key(b9) != fmt.group_key(b7)
    d = enc.compress(RNG.integers(0, 99, 256).astype(np.uint32), fmt.DBP, 512)
    assert fmt.group_key(d) == (fmt.DBP, 4, 128, 0)


# --------------------------------------------------------------------------
# ops.count_dispatches reentrancy (satellite)
# --------------------------------------------------------------------------


def _decode_once():
    blob = enc.compress(np.repeat(np.uint32(5), 600), fmt.RLE_V1, 512)
    return ops.decode_table(blob)


def test_count_dispatches_nested():
    """Nested contexts each see their own window of dispatches, and exiting
    the inner one must not disconnect (or clobber) the outer one."""
    with ops.count_dispatches() as outer:
        _decode_once()
        with ops.count_dispatches() as inner:
            _decode_once()
        assert len(inner) == 1
        _decode_once()          # after inner exit: outer still counting
    assert len(outer) == 3
    assert len(inner) == 1
    # fully unwound: no observer leaks into subsequent dispatches
    _decode_once()
    assert len(outer) == 3


def test_count_dispatches_nested_equal_contents():
    """Immediately-nested contexts hold equal-valued lists; the inner exit
    must detach ITS list (identity, not value equality), and the outer exit
    must not raise."""
    with ops.count_dispatches() as outer:
        with ops.count_dispatches() as inner:
            _decode_once()      # both lists now equal: [rec]
        _decode_once()          # must land in outer only
    assert len(inner) == 1
    assert len(outer) == 2


def test_count_dispatches_overlapping_exit_order():
    """Out-of-LIFO exits (e.g. via ExitStack misuse) stay consistent."""
    c1 = ops.count_dispatches()
    c2 = ops.count_dispatches()
    l1 = c1.__enter__()
    l2 = c2.__enter__()
    _decode_once()
    c1.__exit__(None, None, None)       # close the OUTER first
    _decode_once()
    c2.__exit__(None, None, None)
    assert len(l1) == 1
    assert len(l2) == 2


# --------------------------------------------------------------------------
# ops.words_view odd-width zero-padding (satellite)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("width_bytes", [5, 6, 7, 9, 333])
def test_words_view_pads_odd_row_widths(width_bytes):
    comp = RNG.integers(0, 255, (3, width_bytes)).astype(np.uint8)
    words = np.asarray(ops.words_view(jnp.asarray(comp)))
    padded = np.zeros((3, -(-width_bytes // 4) * 4), np.uint8)
    padded[:, :width_bytes] = comp
    expect = padded.view("<u4")
    np.testing.assert_array_equal(words, expect)


def test_words_view_on_oddly_padded_blob():
    """Regression: a blob whose host comp table has a non-multiple-of-4 row
    width must decode through the word view, not fail in reshape."""
    arr = np.frombuffer(b"abcabcabc" * 37, np.uint8).copy()
    blob = enc.compress(arr, fmt.TDEFLATE, 512)
    if blob.comp.shape[1] % 4 == 0:    # force an odd row width
        blob.comp = np.pad(blob.comp, ((0, 0), (0, 1)))
    assert blob.comp.shape[1] % 4 != 0
    dev = {"comp": jnp.asarray(blob.comp),
           "comp_lens": jnp.asarray(blob.comp_lens),
           "out_lens": jnp.asarray(blob.out_lens)}
    dev.update({k: jnp.asarray(v) for k, v in blob.extras.items()})
    # no comp_words in the pytree -> the words_view fallback path runs
    out = ops.decode(dev, codec=fmt.TDEFLATE, width=blob.width,
                     chunk_elems=blob.chunk_elems)
    flat = np.asarray(out).reshape(-1)[:blob.total_elems]
    np.testing.assert_array_equal(flat, arr)
