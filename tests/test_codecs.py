"""Codec roundtrips: every REGISTERED codec x backend x dtype.

The codec matrix is pulled from ``repro.core.registry`` so a new plugin
(e.g. ``dbp``) is covered automatically — including empty chunks,
single-element chunks, and all supported widths.

Hypothesis property tests live in test_codecs_properties.py (guarded with
``pytest.importorskip`` so the deterministic suite here never depends on
hypothesis being installed).
"""
import numpy as np
import pytest

from repro.core import api, encoders as enc, format as fmt, registry
from repro.core.engine import CodagEngine, EngineConfig

RNG = np.random.default_rng(7)

ALL_CODECS = registry.names()


def datasets():
    return {
        "long_runs_u32": np.repeat(RNG.integers(0, 50, 40),
                                   RNG.integers(1, 200, 40)).astype(np.uint32),
        "rand_u8": RNG.integers(0, 255, 777).astype(np.uint8),
        "delta_u16": (np.arange(500) * 7 + 3).astype(np.uint16),
        "mixed_u32": np.concatenate(
            [np.repeat(np.uint32(5), 100),
             RNG.integers(0, 9, 53).astype(np.uint32),
             np.arange(200, dtype=np.uint32) * 3]),
        "runs_u64": np.repeat(RNG.integers(0, 2 ** 40, 30).astype(np.uint64),
                              RNG.integers(1, 60, 30)),
        "text": np.frombuffer(b"the quick brown fox " * 40
                              + b"abcabcabc" * 25, np.uint8).copy(),
        # registry-mandated edge cases
        "empty_u32": np.zeros(0, np.uint32),
        "single_u8": np.asarray([200], np.uint8),
        "single_u16": np.asarray([40000], np.uint16),
        "single_u32": np.asarray([2 ** 31 + 7], np.uint32),
    }


ENGINES = {
    "warp_xla": EngineConfig(backend="xla"),
    "warp_pallas": EngineConfig(backend="pallas"),
    "oracle": EngineConfig(backend="oracle"),
}


# warp_xla + oracle stay in the fast tier; the interpret-mode Pallas engine
# is several seconds per case -> nightly.
_FAST_ENGINES = ("warp_xla", "oracle")


@pytest.mark.parametrize("codec", ALL_CODECS)
@pytest.mark.parametrize("engine_name", [
    e if e in _FAST_ENGINES else pytest.param(e, marks=pytest.mark.slow)
    for e in ENGINES])
def test_roundtrip_all_backends(codec, engine_name):
    eng = CodagEngine(ENGINES[engine_name])
    for name, arr in datasets().items():
        ca = api.compress(arr, codec, chunk_bytes=600)
        got = api.decompress(ca, eng)
        assert got.dtype == arr.dtype and got.shape == arr.shape, \
            f"{codec}/{engine_name}/{name}"
        assert np.array_equal(got, arr), f"{codec}/{engine_name}/{name}"


@pytest.mark.parametrize("codec", ALL_CODECS)
@pytest.mark.parametrize("width_dtype", [np.uint8, np.uint16, np.uint32])
def test_roundtrip_all_widths(codec, width_dtype):
    """Every registered codec round-trips each supported element width."""
    info = np.iinfo(width_dtype)
    arr = np.concatenate([
        np.repeat(width_dtype(3), 70),
        RNG.integers(0, info.max, 90, endpoint=True).astype(width_dtype),
        (np.arange(80) % 250).astype(width_dtype)])
    ca = api.compress(arr, codec, chunk_bytes=333)
    got = api.decompress(ca)
    assert got.dtype == arr.dtype
    assert np.array_equal(got, arr)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_bitpack_roundtrip(backend):
    vals = RNG.integers(0, 2 ** 11, 5000).astype(np.uint32)
    ca = api.compress(vals, fmt.BITPACK, chunk_bytes=2048, bits=11)
    got = api.decompress(ca, CodagEngine(EngineConfig(backend=backend)))
    assert np.array_equal(got, vals)
    assert ca.ratio < 0.40     # 11/32 + padding


def test_ratio_on_runs():
    arr = np.repeat(np.uint32(9), 100_000)
    for codec, bound in [(fmt.RLE_V1, 0.01), (fmt.RLE_V2, 0.001)]:
        ca = api.compress(arr, codec)
        assert ca.ratio < bound, codec


def test_delta_beats_rle_v1_on_arithmetic():
    arr = np.arange(100_000, dtype=np.uint32) * 3
    r1 = api.compress(arr, fmt.RLE_V1).ratio
    r2 = api.compress(arr, fmt.RLE_V2).ratio
    # delta groups cap at 66 elems: 9B header+base+delta per 264B ~ 0.034
    assert r2 < 0.05 and r2 < r1 / 20


def test_dbp_compresses_sorted_ids():
    """dbp's target workload: sorted ids / timestamps (small FOR ranges)."""
    arr = np.cumsum(RNG.integers(0, 16, 100_000)).astype(np.uint32)
    r_dbp = api.compress(arr, fmt.DBP).ratio
    r_rle1 = api.compress(arr, fmt.RLE_V1).ratio
    # ~11 bits/elem of offsets + headers vs RLE v1 literal fallback (~1.0)
    assert r_dbp < 0.5 and r_dbp < r_rle1 / 2


def test_tdeflate_compresses_text():
    data = np.frombuffer(b"hello world, " * 5000, np.uint8).copy()
    ca = api.compress(data, fmt.TDEFLATE)
    assert api.decompress(ca).tobytes() == data.tobytes()
    assert ca.ratio < 0.1


def test_compressed_symbol_structure_table_v():
    """Table V analogue: avg compressed symbol length behaves as expected —
    run-heavy data has long symbols, random data degenerates to literals."""
    runs = np.repeat(RNG.integers(0, 9, 64).astype(np.uint8), 120)
    rand = RNG.integers(0, 255, 8000).astype(np.uint8)
    blob_runs = enc.compress(runs, fmt.RLE_V1, 1 << 14)
    blob_rand = enc.compress(rand, fmt.RLE_V1, 1 << 14)
    assert blob_runs.ratio < 0.05
    assert 0.95 < blob_rand.ratio < 1.05
