"""Hypothesis property tests (system invariant: decode(encode(x)) == x).

Split from test_codecs.py so the deterministic suite collects and runs even
where hypothesis is not installed — here the whole module skips gracefully.

The codec strategies sample from ``registry.names()`` so every registered
plugin (including ``dbp`` and any future codec) is property-tested with no
edits here.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as hst  # noqa: E402

from repro.core import api, format as fmt, registry  # noqa: E402
from repro.core.engine import CodagEngine, EngineConfig  # noqa: E402

_eng = CodagEngine(EngineConfig())

ALL_CODECS = registry.names()


@settings(max_examples=25, deadline=None)
@given(hst.lists(hst.integers(0, 255), min_size=0, max_size=2000),
       hst.sampled_from(ALL_CODECS),
       hst.sampled_from([64, 333, 1024]))
def test_roundtrip_property_u8(data, codec, chunk_bytes):
    arr = np.asarray(data, np.uint8)
    ca = api.compress(arr, codec, chunk_bytes=chunk_bytes)
    assert np.array_equal(api.decompress(ca, _eng), arr)


@settings(max_examples=25, deadline=None)
@given(hst.lists(
    hst.tuples(hst.integers(0, 2 ** 32 - 1), hst.integers(1, 40)),
    min_size=1, max_size=60),
    hst.sampled_from([c for c in ALL_CODECS if c != fmt.TDEFLATE]))
def test_roundtrip_property_runs_u32(runs, codec):
    arr = np.concatenate([np.repeat(np.uint32(v), l) for v, l in runs])
    ca = api.compress(arr, codec, chunk_bytes=512)
    assert np.array_equal(api.decompress(ca, _eng), arr)


@settings(max_examples=20, deadline=None)
@given(hst.integers(0, 2 ** 31), hst.integers(-500, 500),
       hst.integers(4, 300),
       hst.sampled_from([fmt.RLE_V2, fmt.DBP]))
def test_roundtrip_property_arithmetic(base, delta, n, codec):
    arr = (base + delta * np.arange(n, dtype=np.int64)).astype(np.uint32)
    ca = api.compress(arr, codec, chunk_bytes=512)
    assert np.array_equal(api.decompress(ca, _eng), arr)


@settings(max_examples=20, deadline=None)
@given(hst.lists(hst.integers(0, 2 ** 16 - 1), min_size=1, max_size=1500),
       hst.integers(1, 17))
def test_bitpack_property(vals, bits):
    arr = (np.asarray(vals, np.uint32) & ((1 << bits) - 1))
    ca = api.compress(arr, fmt.BITPACK, chunk_bytes=777, bits=bits)
    assert np.array_equal(api.decompress(ca, _eng), arr)


@settings(max_examples=15, deadline=None)
@given(hst.binary(min_size=1, max_size=3000))
def test_tdeflate_property_bytes(data):
    arr = np.frombuffer(data, np.uint8).copy()
    ca = api.compress(arr, fmt.TDEFLATE, chunk_bytes=800)
    assert api.decompress(ca, _eng).tobytes() == data


@settings(max_examples=10, deadline=None)
@given(hst.lists(
    hst.tuples(hst.sampled_from(ALL_CODECS),
               hst.lists(hst.integers(0, 255), min_size=1, max_size=400)),
    min_size=0, max_size=6))
def test_batched_matches_per_blob_property(items):
    """Batched decode (core.plan) is bit-exact vs per-array decompress."""
    arrays = [np.asarray(data, np.uint8) for _, data in items]
    cas = api.compress_many(arrays, [c for c, _ in items], chunk_bytes=256)
    outs = api.decompress_many(cas, _eng)
    for arr, out in zip(arrays, outs):
        assert np.array_equal(out, arr)


# --------------------------------------------------------------------------
# adversarial fuzz pass (ISSUE-3): worst-case shapes for every registry
# codec — degenerate run structure, saturated values, single-element and
# empty chunks, odd tails.  A bounded subset runs in the fast CI tier; the
# deep sweep (more examples, pathological chunk sizes) is nightly.
# --------------------------------------------------------------------------

_WIDTH_DTYPES = {1: np.uint8, 2: np.uint16, 4: np.uint32}


@hst.composite
def adversarial_arrays(draw):
    """Arrays built to stress decode paths, not to look like data:

    * all_runs     — one value repeated (maximal run coalescing)
    * no_runs      — neighbors always differ (zero run coverage)
    * max_vals     — every element at the dtype's max (widest literals,
                     bitpack at full bit width)
    * alternating  — period-2 flip (run length exactly 1, twice)
    * ramp         — arithmetic progression with wraparound (dbp deltas)
    * empty/single — degenerate chunk tables
    """
    width = draw(hst.sampled_from(sorted(_WIDTH_DTYPES)))
    dt = _WIDTH_DTYPES[width]
    top = int(np.iinfo(dt).max)
    pattern = draw(hst.sampled_from(
        ["all_runs", "no_runs", "max_vals", "alternating", "ramp",
         "empty", "single"]))
    if pattern == "empty":
        return np.zeros(0, dt)
    if pattern == "single":
        return np.asarray([draw(hst.integers(0, top))], dt)
    n = draw(hst.integers(1, 800))
    if pattern == "all_runs":
        return np.full(n, draw(hst.integers(0, top)), dt)
    if pattern == "max_vals":
        return np.full(n, top, dt)
    if pattern == "no_runs":
        # Weyl sequence: consecutive elements are never equal
        step = 2 * draw(hst.integers(0, top // 2)) + 1
        start = draw(hst.integers(0, top))
        return ((start + step * np.arange(n, dtype=np.uint64))
                % (top + 1)).astype(dt)
    if pattern == "alternating":
        a, b = draw(hst.integers(0, top)), draw(hst.integers(0, top))
        return np.where(np.arange(n) % 2 == 0, a, b).astype(dt)
    # ramp
    start = draw(hst.integers(0, top))
    step = draw(hst.integers(-300, 300))
    return ((start + step * np.arange(n, dtype=np.int64))
            % (top + 1)).astype(dt)


# chunk sizes chosen so vectors land on single-element chunks (width==
# chunk_bytes), odd tails (chunk_elems not dividing n), and multi-chunk
# tables; the fast subset keeps chunk counts bounded.
_FAST_CHUNK_BYTES = [97, 250, 513]
_DEEP_CHUNK_BYTES = [4, 17, 97, 250, 513, 4096]


@settings(max_examples=25, deadline=None)
@given(adversarial_arrays(), hst.sampled_from(ALL_CODECS),
       hst.sampled_from(_FAST_CHUNK_BYTES))
def test_adversarial_roundtrip(arr, codec, chunk_bytes):
    ca = api.compress(arr, codec, chunk_bytes=chunk_bytes)
    got = api.decompress(ca, _eng)
    assert got.dtype == arr.dtype and got.shape == arr.shape
    assert np.array_equal(got, arr)


@pytest.mark.slow
@settings(max_examples=120, deadline=None)
@given(adversarial_arrays(), hst.sampled_from(ALL_CODECS),
       hst.sampled_from(_DEEP_CHUNK_BYTES))
def test_adversarial_roundtrip_deep(arr, codec, chunk_bytes):
    """Nightly sweep: pathological chunk sizes (1-4 elems/chunk) included."""
    ca = api.compress(arr, codec, chunk_bytes=chunk_bytes)
    assert np.array_equal(api.decompress(ca, _eng), arr)


@settings(max_examples=15, deadline=None)
@given(hst.integers(1, 32), hst.integers(0, 2 ** 63), hst.integers(0, 900),
       hst.sampled_from(_FAST_CHUNK_BYTES))
def test_bitpack_adversarial_full_width(bits, seed, n, chunk_bytes):
    """Explicit bit widths up to the full 32, saturated values included."""
    rng = np.random.default_rng(seed)
    mask = np.uint64((1 << bits) - 1)
    arr = (rng.integers(0, 2 ** 32, n, dtype=np.uint64)
           & mask).astype(np.uint32)
    ca = api.compress(arr, fmt.BITPACK, chunk_bytes=chunk_bytes, bits=bits)
    assert np.array_equal(api.decompress(ca, _eng), arr)


_fuzz_service = None


def _cached_service():
    """One module-lived service WITH the content-hash cache on, so the
    fuzz pass exercises cache hits/dedupe (the default service keeps its
    cache off for exact dispatch accounting)."""
    global _fuzz_service
    if _fuzz_service is None or _fuzz_service.closed:
        from repro.core.server import DecompressionService
        _fuzz_service = DecompressionService(max_delay_ms=5,
                                             cache_bytes=16 << 20)
    return _fuzz_service


@settings(max_examples=10, deadline=None)
@given(hst.lists(hst.tuples(hst.sampled_from(ALL_CODECS),
                            adversarial_arrays()),
                 min_size=0, max_size=5))
def test_service_adversarial_matches_direct(items):
    """The DecompressionService paths (default engine-less routing AND an
    explicitly-cached service: micro-batch window + content-hash cache +
    in-window dedupe) stay bit-exact on adversarial inputs — including
    repeated/identical payloads, which exercise cache hits and dedupe."""
    arrays = [arr for _, arr in items]
    cas = api.compress_many(arrays, [c for c, _ in items], chunk_bytes=250)
    outs = api.decompress_many(cas)           # default-service path
    cached = api.decompress_many(cas, service=_cached_service())
    direct = api.decompress_many(cas, _eng)   # synchronous DecodePlan path
    for arr, out, hit, ref in zip(arrays, outs, cached, direct):
        assert np.array_equal(out, arr)
        assert np.array_equal(hit, arr)
        assert np.array_equal(out, ref)
