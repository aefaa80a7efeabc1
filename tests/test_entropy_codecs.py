"""Entropy-coded codec family (huffman + lzss): the edge cases the generic
registry matrix cannot force.

The registry-parametrized suites (test_codecs.py, test_conformance.py,
test_codecs_properties.py) already cover both codecs on every backend,
width, and chunk shape.  This file pins the failure modes specific to
variable-length symbol streams:

  * huffman — degenerate single-symbol alphabets (code length 1, no
    sibling), length-limited canonical codes when the Kraft fixup binds
    (skewed Fibonacci frequencies would want > MAX_CODE_BITS), and gap
    array segment boundaries (chunk lengths straddling SUB);
  * lzss — overlapping back-references (dist < length: dist=1 constant
    runs, period-3 tiles), where a naive vector copy reads bytes the same
    copy has not produced yet;
  * both — the tuned-knob candidates (sub_unroll / dbl_unroll) and the
    pipelined Pallas wrapper must stay bit-exact vs the XLA reference.
"""
import numpy as np
import pytest

from repro.core import api, encoders as enc, registry, tuning
from repro.core.engine import CodagEngine, EngineConfig
from repro.kernels import huffman as hf
from repro.kernels import lzss as lz

RNG = np.random.default_rng(17)

# xla and oracle cover the production decode and its reference cheaply;
# the interpret-mode Pallas engine is exercised once per codec in the
# tuned-knob test below.
ENGINES = {
    "xla": EngineConfig(backend="xla"),
    "oracle": EngineConfig(backend="oracle"),
}


def _roundtrip_all(arr, codec, chunk_bytes):
    ca = api.compress(arr, codec, chunk_bytes=chunk_bytes)
    for name, cfg in ENGINES.items():
        got = api.decompress(ca, CodagEngine(cfg))
        assert got.dtype == arr.dtype, f"{codec}/{name}"
        assert np.array_equal(got, arr), f"{codec}/{name}"
    return ca


# --------------------------------------------------------------------------
# huffman
# --------------------------------------------------------------------------


def test_huffman_single_symbol_alphabet():
    """One active symbol: the canonical code is a single 1-bit codeword —
    no sibling to pair with, so the tree-build degenerate path runs."""
    hist = np.bincount(np.full(64, 9, np.uint8), minlength=256)
    lens = enc.limited_huffman_lengths(hist, enc.MAX_CODE_BITS)
    assert lens[9] == 1 and np.count_nonzero(lens) == 1
    for n in (1, 64, 1000):
        _roundtrip_all(np.full(n, 9, np.uint8), hf.HUFFMAN, chunk_bytes=600)


def test_huffman_max_code_length_kraft_fixup():
    """Fibonacci-skewed frequencies want codes deeper than MAX_CODE_BITS;
    the length-limit fixup must bind (some code AT the cap, none over) and
    the limited code must still round-trip everywhere."""
    counts = [1, 1]
    while len(counts) < 24:
        counts.append(counts[-1] + counts[-2])
    data = np.repeat(np.arange(len(counts), dtype=np.uint8),
                     counts).astype(np.uint8)
    RNG.shuffle(data)
    hist = np.bincount(data, minlength=256)
    lens = enc.limited_huffman_lengths(hist, enc.MAX_CODE_BITS)
    active = lens[lens > 0]
    assert active.max() == enc.MAX_CODE_BITS     # the cap binds...
    assert np.sum(0.5 ** active.astype(np.float64)) <= 1.0   # ...Kraft holds
    _roundtrip_all(data, hf.HUFFMAN, chunk_bytes=4096)
    _roundtrip_all(data, hf.HUFFMAN, chunk_bytes=777)   # multi-chunk + tail


@pytest.mark.parametrize("n", [hf.SUB - 1, hf.SUB, hf.SUB + 1,
                               2 * hf.SUB, 5 * hf.SUB + 3])
def test_huffman_gap_segment_boundaries(n):
    """Chunk lengths straddling the SUB-symbol gap-array granularity: the
    last segment may hold 1..SUB symbols and its count byte must agree."""
    data = np.minimum(RNG.geometric(0.3, n) - 1, 255).astype(np.uint8)
    ca = _roundtrip_all(data, hf.HUFFMAN, chunk_bytes=1 << 14)
    row = np.asarray(ca.blobs[0].comp[0])
    n_seg = hf.CODEC.count_groups(row, 1)
    assert n_seg == -(-n // hf.SUB)              # gap table is recoverable


# --------------------------------------------------------------------------
# lzss
# --------------------------------------------------------------------------


def test_lzss_overlapping_backref_dist1():
    """dist=1, length up to MAX_MATCH: every copied element is produced by
    the same copy — the pointer-doubling resolution's worst case."""
    for width, dt in ((1, np.uint8), (2, np.uint16), (4, np.uint32)):
        arr = np.full(500, 7, dt)
        tok = lz.encode_lzss_chunk(arr, width)
        # literal control for element 0, then a match token with dist=1
        assert tok[0] == 0 and tok[1 + width] >= 128
        assert int.from_bytes(tok[2 + width:4 + width], "little") == 1
        _roundtrip_all(arr, lz.LZSS, chunk_bytes=600)


def test_lzss_overlapping_backref_period3():
    """Period-3 tiles: dist=3 < match length, chains of matches pointing
    into earlier matches (multi-hop pointer doubling)."""
    for dt in (np.uint8, np.uint32):
        arr = np.tile(np.asarray([11, 250, 3], dt), 700)
        _roundtrip_all(arr, lz.LZSS, chunk_bytes=777)
    # noisy variant: literals interrupt the chains mid-stream
    arr = np.tile(np.asarray([11, 250, 3], np.uint32), 700)
    idx = RNG.integers(0, arr.size, 40)
    arr[idx] = RNG.integers(0, 1 << 16, 40)
    _roundtrip_all(arr, lz.LZSS, chunk_bytes=913)


# --------------------------------------------------------------------------
# tuned knobs + pipelined wrapper stay bit-exact
# --------------------------------------------------------------------------


@pytest.mark.parametrize("codec", [hf.HUFFMAN, lz.LZSS])
def test_tuned_knob_candidates_bit_exact(codec):
    """Every candidate of every codec tunable (sub_unroll / dbl_unroll)
    must decode identically — knobs trade speed, never values — including
    through the multi-stage pipelined Pallas wrapper."""
    c = registry.get(codec)
    arr = c.demo_data(3000, np.random.default_rng(5))
    ca = api.compress(arr, codec, chunk_bytes=512)
    with tuning.override(None):
        ref = api.decompress(ca, CodagEngine(EngineConfig(backend="xla")))
        np.testing.assert_array_equal(ref, arr)
        for t in c.decode.tunables:
            for v in t.candidates:
                got = api.decompress(ca, CodagEngine(EngineConfig(
                    backend="pallas", interpret=True,
                    tune=((t.name, v), ("interpret_pipeline", 1),
                          ("num_stages", 3)))))
                np.testing.assert_array_equal(
                    got, arr, err_msg=f"{t.name}={v}")
