"""Batch decompression scheduler: grouping, dispatch accounting, scatter-back.

Covers the ISSUE-1 acceptance criterion: ``api.decompress_many`` over >= 8
mixed-codec blobs is bit-exact vs per-blob ``api.decompress`` and issues
exactly one ``ops.decode`` dispatch per (codec, width, chunk_elems, bits)
group, verified by monkeypatching ``ops.decode``.
"""
import numpy as np
import pytest

from repro.core import api, encoders as enc, format as fmt, registry
from repro.core import plan as plan_mod
from repro.core.engine import CodagEngine, EngineConfig
from repro.kernels import ops

RNG = np.random.default_rng(11)


def _runs_u32(n):
    vals = RNG.integers(0, 90, max(4, n // 40)).astype(np.uint32)
    return np.repeat(vals, RNG.integers(1, 80, len(vals)))[:n]


def mixed_arrays():
    """>= 8 arrays spanning EVERY registered codec and three widths."""
    items = [
        (_runs_u32(900), fmt.RLE_V1),
        (RNG.integers(0, 250, 400).astype(np.uint8), fmt.RLE_V1),
        (_runs_u32(700), fmt.RLE_V2),
        ((np.arange(500) * 5 + 2).astype(np.uint16), fmt.RLE_V2),
        (np.repeat(RNG.integers(0, 2 ** 40, 15).astype(np.uint64),
                   RNG.integers(1, 40, 15)), fmt.RLE_V2),
        (np.frombuffer(b"batched codag streams " * 30, np.uint8).copy(),
         fmt.TDEFLATE),
        (np.frombuffer(b"abcabcabc" * 70, np.uint8).copy(), fmt.TDEFLATE),
        (RNG.integers(0, 2 ** 7, 1200).astype(np.uint32), fmt.BITPACK),
        (RNG.integers(0, 2 ** 7, 600).astype(np.uint32), fmt.BITPACK),
    ]
    # every registered codec rides the batch path (dbp + future plugins)
    covered = {c for _, c in items}
    for name in registry.names():
        if name not in covered:
            items.append((registry.get(name).demo_data(800, RNG), name))
    return items


@pytest.fixture
def counted():
    """List of per-dispatch records from the shared ops.decode counter."""
    with ops.count_dispatches() as calls:
        yield calls


def test_mixed_codec_roundtrip_bit_exact(counted):
    items = mixed_arrays()
    assert len(items) >= 8
    cas = api.compress_many([a for a, _ in items], [c for _, c in items],
                            chunk_bytes=600)
    eng = CodagEngine(EngineConfig())
    batched = api.decompress_many(cas, eng)
    n_batched = len(counted)
    counted.clear()
    per_blob = [api.decompress(ca, eng) for ca in cas]
    n_loop = len(counted)

    for (arr, codec), got_b, got_p in zip(items, batched, per_blob):
        assert got_b.dtype == arr.dtype and got_b.shape == arr.shape, codec
        assert np.array_equal(got_b, arr), codec
        assert np.array_equal(got_b, got_p), codec

    # one dispatch per distinct group key; the loop pays one per blob
    flat = [b for ca in cas for b in ca.blobs]
    n_groups = len({fmt.group_key(b) for b in flat})
    assert n_batched == n_groups
    assert n_loop == len(flat)
    assert n_batched < n_loop


def test_one_dispatch_per_group_key(counted):
    """Exactly one ops.decode call per (codec, width, chunk_elems, bits)."""
    arrays = [_runs_u32(800) for _ in range(5)]        # same key -> 1 dispatch
    arrays += [RNG.integers(0, 200, 640).astype(np.uint8)]  # width 1 -> new key
    cas = api.compress_many(arrays, fmt.RLE_V2, chunk_bytes=512)
    api.decompress_many(cas)
    assert len(counted) == 2
    # the fused dispatch really carries every chunk of its group
    per_key = {}
    for c in counted:
        per_key[(c["codec"], c["width"], c["chunk_elems"])] = c["num_chunks"]
    chunks_u32 = sum(b.num_chunks for ca in cas[:5] for b in ca.blobs)
    assert per_key[(fmt.RLE_V2, 4, 128)] == chunks_u32


def test_scatter_back_ordering():
    """Outputs follow input order even with interleaved group membership."""
    a_u32 = [np.full(100 + i, i, np.uint32) for i in range(4)]
    a_u8 = [np.full(50 + i, 7 + i, np.uint8) for i in range(4)]
    arrays = [x for pair in zip(a_u32, a_u8) for x in pair]  # interleave keys
    cas = api.compress_many(arrays, fmt.RLE_V1, chunk_bytes=256)
    outs = api.decompress_many(cas)
    for arr, out in zip(arrays, outs):
        assert np.array_equal(out, arr)


def test_empty_and_single_blob_edges(counted):
    assert api.decompress_many([]) == []
    assert plan_mod.decompress_blobs([]) == []
    assert len(counted) == 0

    arr = _runs_u32(512)
    (out,) = api.decompress_many([api.compress(arr, fmt.RLE_V2,
                                               chunk_bytes=512)])
    assert np.array_equal(out, arr)
    assert len(counted) == 1


def test_plan_structure_and_merged_table():
    blobs = [enc.compress(_runs_u32(600), fmt.RLE_V1, 512) for _ in range(3)]
    blobs.append(enc.compress(RNG.integers(0, 9, 300).astype(np.uint8),
                              fmt.RLE_V1, 512))
    plan = plan_mod.DecodePlan.build(blobs)
    assert plan.num_dispatches == 2
    g = plan.groups[0]
    assert g.blob_ids == (0, 1, 2)
    assert g.row_offsets == (0, blobs[0].num_chunks,
                             blobs[0].num_chunks + blobs[1].num_chunks)
    assert g.merged.num_chunks == sum(b.num_chunks for b in blobs[:3])
    assert g.merged.total_elems == sum(b.total_elems for b in blobs[:3])
    # merged comp rows preserve each blob's bytes
    row = blobs[0].num_chunks
    np.testing.assert_array_equal(
        g.merged.comp[row:row + blobs[1].num_chunks, :blobs[1].comp.shape[1]],
        blobs[1].comp)


def test_concat_blobs_rejects_mixed_keys():
    b1 = enc.compress(_runs_u32(600), fmt.RLE_V1, 512)
    b2 = enc.compress(_runs_u32(600), fmt.RLE_V2, 512)
    with pytest.raises(ValueError, match="group key"):
        fmt.concat_blobs([b1, b2])


def test_heterogeneous_comp_widths_merge():
    """Blobs whose comp tables have different max row lengths still fuse."""
    nearly_raw = RNG.integers(0, 255, 2048).astype(np.uint8)   # wide rows
    runs = np.repeat(np.uint8(3), 2048)                        # narrow rows
    cas = api.compress_many([nearly_raw, runs], fmt.RLE_V1, chunk_bytes=512)
    outs = api.decompress_many(cas)
    assert np.array_equal(outs[0], nearly_raw)
    assert np.array_equal(outs[1], runs)


def test_batched_engine_config_respected(counted):
    """The scheduler funnels through whatever engine it is handed."""
    arrays = [_runs_u32(700), _runs_u32(900)]
    cas = api.compress_many(arrays, fmt.RLE_V2, chunk_bytes=512)
    outs = api.decompress_many(cas, CodagEngine(EngineConfig(
        backend="oracle")))
    for arr, out in zip(arrays, outs):
        assert np.array_equal(out, arr)
    assert [c["backend"] for c in counted] == ["oracle"]


def test_mixed_arrays_cover_registry():
    """The batch matrix spans the full registry (completeness guard)."""
    assert {c for _, c in mixed_arrays()} == set(registry.names())


def test_dbp_batched_single_dispatch_group(counted):
    """ISSUE-2 acceptance: several dbp blobs fuse into ONE dispatch group
    through ``api.decompress_many``, bit-exactly."""
    arrays = [np.cumsum(RNG.integers(0, 9, 700 + 37 * i)).astype(np.uint32)
              for i in range(4)]
    cas = api.compress_many(arrays, fmt.DBP, chunk_bytes=512)
    outs = api.decompress_many(cas)
    for arr, out in zip(arrays, outs):
        assert np.array_equal(out, arr)
    assert len(counted) == 1
    assert counted[0]["codec"] == fmt.DBP
    assert counted[0]["num_chunks"] == sum(
        b.num_chunks for ca in cas for b in ca.blobs)


def test_tdeflate_per_chunk_luts_travel_with_merge():
    """tdeflate extras are per-chunk tables; merging must keep row alignment."""
    texts = [(b"x" * 37 + bytes([i])) * 60 for i in range(6)]
    arrays = [np.frombuffer(t, np.uint8).copy() for t in texts]
    cas = api.compress_many(arrays, fmt.TDEFLATE, chunk_bytes=512)
    outs = api.decompress_many(cas)
    for arr, out in zip(arrays, outs):
        assert out.tobytes() == arr.tobytes()
