"""Device-resident decode (ISSUE-4): on-device reassembly, fused epilogues,
zero-host-transfer batched decode, and the rewired consumers.

The acceptance spine:

  * ``reassemble_device`` / ``combine_planes_device`` are bit-exact vs the
    host path for every registered codec, including the edge geometries
    (odd tails, single-element final chunk, zero-length blobs, 64-bit plane
    recombination).
  * ``api.decompress_many(..., device_out=True)`` → ``dequant_matmul``
    completes under ``transfers.no_host_transfers()`` (which stacks
    ``jax.transfer_guard("disallow")`` on the repo's d2h funnel) — the CI
    ``no-host-transfer`` gate runs ``test_no_host_transfer_gate``.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, registry, transfers
from repro.core import format as fmt
from repro.core import plan as plan_mod
from repro.core.engine import CodagEngine, EngineConfig
from repro.core.server import DecompressionService
from repro.kernels import dequant_matmul as dqm
from repro.kernels import ops
from repro.kernels.harness import Epilogue

ENGINE = CodagEngine(EngineConfig())

# odd tail / single-element final chunk / zero-length / multi-chunk exact
EDGE_SIZES = (0, 1, 1025, 4096, 4097)


def _demo(codec_name: str, n: int, seed: int = 0) -> np.ndarray:
    codec = registry.get(codec_name)
    if n == 0:
        return np.zeros(0, np.uint8 if codec.byte_stream else np.uint32)
    return codec.demo_data(n, np.random.default_rng(seed))[:n]


# --------------------------------------------------------------------------
# reassembly: device path bit-exact vs host path (ISSUE-4 satellite)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("codec", registry.names())
@pytest.mark.parametrize("n", EDGE_SIZES)
def test_reassemble_device_matches_host(codec, n):
    ca = api.compress(_demo(codec, n), codec, chunk_bytes=1024)
    host = api.decompress(ca, ENGINE)
    [dev] = api.decompress_many([ca], ENGINE, device_out=True)
    assert isinstance(dev, jnp.ndarray)
    out = np.asarray(dev)
    assert out.dtype == host.dtype and out.shape == host.shape
    assert np.array_equal(out, host)


@pytest.mark.parametrize("codec", registry.names())
def test_reassemble_device_blobwise(codec):
    """Single-blob helper path (engine.decompress_device) incl. odd tail."""
    ca = api.compress(_demo(codec, 777), codec, chunk_bytes=512)
    for blob in ca.blobs:
        host = fmt.reassemble(blob, ENGINE.decompress_table(blob))
        dev = ENGINE.decompress_device(blob)
        assert np.array_equal(np.asarray(dev), host)


@pytest.mark.parametrize("codec", ["rle_v2", "tdeflate"])
@pytest.mark.parametrize("dtype", ["int64", "uint64", "float64"])
def test_64bit_plane_recombine_device(codec, dtype):
    """8-byte dtypes: plane split (rle_v2) and u32-pair view (tdeflate byte
    stream) both recombine on device bit-exactly, under 64-bit jax types."""
    rng = np.random.default_rng(3)
    if dtype == "float64":
        arr = np.round(rng.normal(size=1003), 2).astype(np.float64)
    else:
        arr = rng.integers(0, 5000, 1003).astype(dtype)
    ca = api.compress(arr, codec, chunk_bytes=1024)
    host = api.decompress(ca, ENGINE)
    assert host.dtype == np.dtype(dtype)
    with jax.enable_x64(True):
        [dev] = api.decompress_many([ca], ENGINE, device_out=True)
        assert str(dev.dtype) == dtype
        assert np.array_equal(np.asarray(dev), host)


def test_64bit_device_without_x64_raises():
    arr = np.arange(100, dtype=np.int64)
    ca = api.compress(arr, "rle_v2", chunk_bytes=512)
    with pytest.raises(ValueError, match="64-bit"):
        api.decompress_many([ca], ENGINE, device_out=True)


def test_ragged_scatter_indices():
    """The precomputed per-row-destination gather handles layouts the
    contiguous reshape+trim cannot: ragged rows, interior zero-length
    chunks.  (Standard blobs return indices=None — the fast path.)"""
    out_lens = np.array([8, 3, 0, 5], np.int32)
    chunk_elems, total = 8, int(out_lens.sum())
    blob = fmt.CompressedBlob(
        codec="rle_v1", width=4, chunk_elems=chunk_elems, total_elems=total,
        orig_dtype="uint32", orig_shape=(total,),
        comp=np.zeros((4, 1), np.uint8), comp_lens=np.ones(4, np.int32),
        out_lens=out_lens)
    idx = fmt.reassemble_indices(blob)
    assert idx is not None and idx.shape == (total,)
    table = np.arange(4 * chunk_elems, dtype=np.uint32).reshape(4, -1)
    want = np.concatenate([row[:l] for row, l in zip(table, out_lens)])
    got = fmt.reassemble_device(blob, jnp.asarray(table))
    assert np.array_equal(np.asarray(got), want)
    # the standard layout takes the index-free path
    ca = api.compress(np.arange(1025, dtype=np.uint32), "rle_v2",
                      chunk_bytes=1024)
    assert fmt.reassemble_indices(ca.blobs[0]) is None


def test_batchplan_carries_scatter():
    blobs = [api.compress(_demo("rle_v2", n), "rle_v2",
                          chunk_bytes=1024).blobs[0] for n in (1025, 4097)]
    plan = plan_mod.DecodePlan.build(blobs)
    assert all(len(g.scatter) == len(g.blob_ids) for g in plan.groups)
    plan.stage()
    outs = plan.execute_device(ENGINE)
    for blob, out in zip(blobs, outs):
        assert np.array_equal(np.asarray(out),
                              fmt.reassemble(blob, ENGINE.decompress_table(blob)))


# --------------------------------------------------------------------------
# fused epilogues
# --------------------------------------------------------------------------


def test_epilogue_cast_and_view():
    arr = _demo("rle_v2", 2050)
    ca = api.compress(arr, "rle_v2", chunk_bytes=1024)
    [f32] = api.decompress_many([ca], ENGINE, device_out=True,
                                epilogue=Epilogue(out_dtype="float32"))
    assert f32.dtype == jnp.float32 and f32.shape == arr.shape
    assert np.array_equal(np.asarray(f32), arr.astype(np.float32))
    [i32] = api.decompress_many([ca], ENGINE, device_out=True,
                                epilogue=Epilogue(view_dtype="int32"))
    assert i32.dtype == jnp.int32
    assert np.array_equal(np.asarray(i32), arr.view(np.int32))


def test_epilogue_dequant_scale_zero():
    arr = _demo("bitpack", 1500)
    ca = api.compress(arr, "bitpack", chunk_bytes=1024)
    epi = Epilogue(scale_key="epi_s", zero_key="epi_z")
    operands = {"epi_s": np.float32(0.25), "epi_z": np.uint32(3)}
    [out] = api.decompress_many([ca], ENGINE, device_out=True, epilogue=epi,
                                epilogue_operands=operands)
    assert out.dtype == jnp.float32
    want = (arr.astype(np.float32) - 3.0) * 0.25
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


def test_epilogue_on_plane_decomposed_raises():
    """An epilogue over a plane-split 64-bit array must refuse rather than
    silently return the transformed lo plane."""
    arr = np.arange(500, dtype=np.int64)
    ca = api.compress(arr, "rle_v2", chunk_bytes=512)
    assert len(ca.blobs) == 2
    with jax.enable_x64(True):
        with pytest.raises(ValueError, match="plane"):
            api.decompress_many([ca], ENGINE, device_out=True,
                                epilogue=Epilogue(out_dtype="float32"))


def test_epilogue_requires_device_out():
    ca = api.compress(_demo("rle_v2", 100), "rle_v2", chunk_bytes=512)
    with pytest.raises(ValueError, match="device_out"):
        api.decompress_many([ca], ENGINE, epilogue=Epilogue(out_dtype="f4"))


def test_epilogue_custom_fn():
    arr = _demo("rle_v2", 512)
    ca = api.compress(arr, "rle_v2", chunk_bytes=1024)
    epi = Epilogue(out_dtype="int32", fn=lambda out, dev: out + 7)
    [out] = api.decompress_many([ca], ENGINE, device_out=True, epilogue=epi)
    assert np.array_equal(np.asarray(out), arr.astype(np.int32) + 7)


# --------------------------------------------------------------------------
# transfer accounting + the CI gate
# --------------------------------------------------------------------------


def test_to_host_funnel_counts_and_guards():
    x = jnp.arange(16)
    with transfers.count_host_transfers() as c:
        transfers.to_host(x)
    assert c["d2h"] == 1 and c["bytes"] == x.nbytes
    with transfers.no_host_transfers():
        with pytest.raises(RuntimeError, match="no_host_transfers"):
            transfers.to_host(x)
    transfers.to_host(x)    # guard lifted


def test_count_host_transfers_overlapping_contexts():
    """Closing one context must not unregister another holding an
    equal-valued (all-zero) counter dict — removal is by identity."""
    x = jnp.arange(8)
    with transfers.count_host_transfers() as a:
        with transfers.count_host_transfers() as b:
            pass                      # b closes while a == b == zeros
        transfers.to_host(x)
    assert a["d2h"] == 1              # a kept counting
    assert b["d2h"] == 0              # b stopped at close


def test_device_out_decode_zero_host_transfers():
    """Every registered codec decodes device-out with zero d2h crossings."""
    cas = [api.compress(_demo(n, 3000), n, chunk_bytes=2048)
           for n in registry.names()]
    with transfers.count_host_transfers() as c:
        outs = api.decompress_many(cas, ENGINE, device_out=True)
        for o in outs:
            o.block_until_ready()
    assert c["d2h"] == 0
    # while the host path funnels exactly one d2h per fused group
    with transfers.count_host_transfers() as c:
        api.decompress_many(cas, ENGINE)
    assert c["d2h"] == plan_mod.DecodePlan.build(
        [b for ca in cas for b in ca.blobs]).num_dispatches


def test_no_host_transfer_gate():
    """The CI gate (ISSUE-4 acceptance): compressed weights → device decode
    with fused zero-point epilogue → dequant matmul, with the transfer
    guard armed for the steady-state pass.  Any reintroduced host
    materialization (``np.asarray`` on the decode path, an unstaged
    operand) fails loudly."""
    rng = np.random.default_rng(7)
    q = rng.integers(-8, 8, (256, 128)).astype(np.int8)
    s = rng.uniform(0.01, 0.1, (1, 128)).astype(np.float32)
    x = rng.normal(size=(128, 256)).astype(np.float32)
    ca = dqm.compress_weights(q, "bitpack", zero_point=8)
    epi, operands = dqm.weight_epilogue(8)
    operands = {k: jnp.asarray(v) for k, v in operands.items()}  # pre-stage
    x_dev, s_dev = jnp.asarray(x), jnp.asarray(s)

    def consume():
        [qd] = api.decompress_many([ca], ENGINE, device_out=True,
                                   epilogue=epi, epilogue_operands=operands)
        assert qd.dtype == jnp.int8
        return dqm.dequant_matmul(x_dev, qd, s_dev, interpret=True)

    warm = consume()                      # compiles + stages
    warm.block_until_ready()
    with transfers.count_host_transfers() as cnt:
        with transfers.no_host_transfers():
            y = consume()
            y.block_until_ready()
    assert cnt["d2h"] == 0
    want = dqm.ref_dequant_matmul(x_dev, jnp.asarray(q), s_dev)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_staged_plan_reuse_under_guard():
    """A pre-staged DecodePlan replays decode→scatter→epilogue with zero
    transfers in either direction (the steady-state serving pattern)."""
    rng = np.random.default_rng(11)
    q = rng.integers(-8, 8, (128, 128)).astype(np.int8)
    ca = dqm.compress_weights(q, zero_point=8)
    epi, operands = dqm.weight_epilogue(8)
    plan = plan_mod.DecodePlan.build(ca.blobs).stage()
    plan.execute_device(ENGINE, epilogue=epi,
                        epilogue_operands=operands)[0].block_until_ready()
    with transfers.no_host_transfers():
        [qd] = plan.execute_device(ENGINE, epilogue=epi,
                                   epilogue_operands=operands)
        qd.block_until_ready()
    assert np.array_equal(np.asarray(qd), q)


# --------------------------------------------------------------------------
# rewired consumers
# --------------------------------------------------------------------------


def test_dequant_matmul_consumer_end_to_end():
    rng = np.random.default_rng(5)
    q = rng.integers(-8, 8, (256, 128)).astype(np.int8)
    s = rng.uniform(0.01, 0.1, (1, 128)).astype(np.float32)
    x = rng.normal(size=(128, 256)).astype(np.float32)
    ca = dqm.compress_weights(q, zero_point=8)
    xd, sd = jnp.asarray(x), jnp.asarray(s)
    y = dqm.decompress_dequant_matmul(xd, ca, sd, zero_point=8,
                                      engine=ENGINE, interpret=True)
    want = dqm.ref_dequant_matmul(xd, jnp.asarray(q), sd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # steady state: the staged plan is cached on ca — repeat calls run the
    # whole decode→consume path with zero transfers in either direction
    with transfers.no_host_transfers():
        y2 = dqm.decompress_dequant_matmul(xd, ca, sd, zero_point=8,
                                           engine=ENGINE, interpret=True)
        y2.block_until_ready()
    np.testing.assert_allclose(np.asarray(y2), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_service_device_out():
    """The service serves device-resident results; its cache keeps host
    bytes and hands device requesters views of them on a hit."""
    arr = _demo("rle_v2", 5000)
    blob = api.compress(arr, "rle_v2", chunk_bytes=2048).blobs[0]
    with DecompressionService(CodagEngine(EngineConfig()),
                              cache_bytes=1 << 20) as svc:
        fd = svc.submit(blob, device_out=True)
        fh = svc.submit(blob)
        dev, host = fd.result(), fh.result()
        assert isinstance(dev, jnp.ndarray) and isinstance(host, np.ndarray)
        assert np.array_equal(np.asarray(dev), arr)
        assert np.array_equal(host, arr)
        # second round: cache hit resolves a device view, no new dispatch
        with ops.count_dispatches() as calls:
            hit = svc.submit(blob, device_out=True).result()
        assert isinstance(hit, jnp.ndarray)
        assert np.array_equal(np.asarray(hit), arr)
        assert len(calls) == 0
        assert svc.stats().cache_hits >= 1


def test_service_device_window_no_d2h():
    """An all-device window on a cache-less service never materializes the
    group table on the host (zero funnel crossings on the worker)."""
    cas = [api.compress(_demo("rle_v2", n, seed=n), "rle_v2",
                        chunk_bytes=1024) for n in (900, 1800)]
    with DecompressionService(CodagEngine(EngineConfig()),
                              cache_bytes=0) as svc:
        with transfers.count_host_transfers() as c:
            outs = svc.decode_arrays(cas, device_out=True)
            for o in outs:
                o.block_until_ready()
        assert c["d2h"] == 0
        for ca, out in zip(cas, outs):
            assert np.array_equal(np.asarray(out), api.decompress(ca, ENGINE))


def test_checkpoint_restore_device(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    rng = np.random.default_rng(9)
    state = {"w": rng.normal(size=(64, 64)).astype(np.float32),
             "m": rng.integers(0, 200, (128, 32)).astype(np.int32),
             "small": np.float32(1.5)}
    ckpt.save(str(tmp_path), 3, state, codec="rle_v2")
    out = ckpt.restore(str(tmp_path), 3, state, device_out=True)
    for k, v in state.items():
        assert isinstance(out[k], jnp.ndarray), (k, type(out[k]))
        assert str(out[k].dtype) == str(np.asarray(v).dtype)
        assert np.array_equal(np.asarray(out[k]), v)


def test_pipeline_device_shards():
    from repro.data import pipeline as pl
    toks = pl.synthetic_corpus(40000, 500, seed=2)
    store = pl.CompressedTokenStore.build(toks, 500, shard_tokens=8192,
                                          chunk_bytes=2048)
    host = list(store.decoded_shards(ENGINE, window=2))
    dev = list(store.decoded_shards(ENGINE, window=2, device_out=True))
    assert len(host) == len(dev)
    for h, d in zip(host, dev):
        assert isinstance(d, jnp.ndarray) and d.dtype == jnp.int32
        assert np.array_equal(np.asarray(d), h)
    loader = pl.CompressedLoader(store, batch=2, seq=128, engine=ENGINE,
                                 prefetch=False, device_out=True)
    b = next(iter(loader))
    assert isinstance(b["tokens"], jnp.ndarray)
    assert b["tokens"].shape == (2, 128)
    # identical token stream to the host loader
    hb = next(iter(pl.CompressedLoader(store, batch=2, seq=128,
                                       engine=ENGINE, prefetch=False)))
    assert np.array_equal(np.asarray(b["tokens"]), np.asarray(hb["tokens"]))


# --------------------------------------------------------------------------
# observer TOCTOU regression (ISSUE-4 satellite)
# --------------------------------------------------------------------------


def test_observer_register_dispatch_race():
    """Regression: ``ops.decode``'s observer fan-out ran its truthiness
    check outside ``_observers_lock`` (check-then-act).  With the fan-out
    fully under the lock, a context open for the whole run records EVERY
    dispatch exactly once, and a context records nothing after it closes —
    under a racing register/unregister thread pair."""
    arr = _demo("rle_v2", 600)
    blob = api.compress(arr, "rle_v2", chunk_bytes=512).blobs[0]
    dev, bits = ops.table_inputs(blob)
    n_dispatch, errors = 120, []
    stop = threading.Event()

    def dispatcher():
        try:
            for _ in range(n_dispatch):
                ops.decode(dev, codec=blob.codec, width=blob.width,
                           chunk_elems=blob.chunk_elems, bits=bits)
        except BaseException as e:  # pragma: no cover
            errors.append(e)
        finally:
            stop.set()

    closed_lens = []

    def churner():
        while not stop.is_set():
            with ops.count_dispatches() as calls:
                pass
            closed_lens.append((calls, len(calls)))

    with ops.count_dispatches() as outer:
        threads = [threading.Thread(target=dispatcher)] + \
                  [threading.Thread(target=churner) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    assert len(outer) == n_dispatch          # no lost or duplicated records
    # nothing was appended to any context after it closed
    for calls, len_at_close in closed_lens:
        assert len(calls) == len_at_close
