"""The unified DecodePlan IR (ISSUE-5 tentpole, single-device half).

Covers the lowering gate (no ``ops.decode`` call site outside the plan
executor; every dispatch from every entry path originates in
``plan.dispatch``), the digest-keyed epilogue-operand staging cache, the
``blob_digest`` / ``pad_table_to_bucket`` move into ``core.format``, and
the service's round-robin device accounting (single-device degenerate
case — the true multi-device behavior runs in ``test_plan_sharded.py``).
"""
import ast
import inspect

import jax
import numpy as np
import pytest

from repro.core import api, format as fmt, server, transfers
from repro.core import engine as engine_mod
from repro.core import plan as plan_mod
from repro.core.engine import CodagEngine, EngineConfig
from repro.kernels import ops
from repro.kernels.harness import Epilogue

ENGINE = CodagEngine(EngineConfig())
RNG = np.random.default_rng(21)


def _runs(n, seed=0):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 90, max(4, n // 40)).astype(np.uint32)
    return np.repeat(vals, rng.integers(1, 80, len(vals)))[:n]


# --------------------------------------------------------------------------
# the lowering gate
# --------------------------------------------------------------------------


def _ops_decode_calls(module):
    """AST walk: calls to ops.decode / ops.decode_table* in a module."""
    tree = ast.parse(inspect.getsource(module))
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (isinstance(f, ast.Attribute)
                and f.attr in ("decode", "decode_table",
                               "decode_table_device", "decode_blob")
                and isinstance(f.value, ast.Name)
                and f.value.id == "ops"):
            hits.append(f"{module.__name__}:{node.lineno}")
    return hits


def test_no_ops_decode_call_sites_outside_plan():
    """Acceptance gate: engine/api/server contain ZERO ``ops.decode*``
    call sites — the plan executor is the only module that lowers to the
    kernel dispatch layer."""
    for mod in (engine_mod, api, server):
        assert _ops_decode_calls(mod) == [], mod.__name__
    # and plan.py itself still has them (the gate is not vacuous)
    assert _ops_decode_calls(plan_mod)


@pytest.mark.parametrize("entry", ["api_many", "api_one", "engine_host",
                                   "engine_device", "batch_plan", "service"])
def test_every_entry_path_lowers_through_plan(entry):
    """Each public decode entry path's kernel dispatches all originate in
    ``plan.dispatch`` — equal ``count_lowered`` / ``count_dispatches``."""
    # unique total_elems per entry: device-path executors record at trace
    # time only, so each case must miss the jit cache to count dispatches
    arr = _runs(900 + 7 * len(entry), seed=3)
    ca = api.compress(arr, fmt.RLE_V2, chunk_bytes=512)
    with plan_mod.count_lowered() as lowered, \
            ops.count_dispatches() as dispatched:
        if entry == "api_many":
            [out] = api.decompress_many([ca], ENGINE)
        elif entry == "api_one":
            out = api.decompress(ca, ENGINE)
        elif entry == "engine_host":
            out = ENGINE.decompress(ca.blobs[0])
        elif entry == "engine_device":
            out = np.asarray(ENGINE.decompress_device(ca.blobs[0]))
        elif entry == "batch_plan":
            out = plan_mod.DecodePlan.build(ca.blobs).execute(ENGINE)[0]
        else:
            with server.DecompressionService(ENGINE) as svc:
                out = svc.decode(ca.blobs[0])
    assert np.array_equal(np.asarray(out).reshape(arr.shape), arr)
    assert len(dispatched) >= 1
    assert len(lowered) == len(dispatched)
    assert [c["codec"] for c in lowered] == \
           [c["codec"] for c in dispatched]


# --------------------------------------------------------------------------
# satellite: digest-keyed epilogue-operand staging cache
# --------------------------------------------------------------------------


def test_operand_cache_alternating_dicts_transfer_free():
    """Regression (ISSUE-5 satellite): the old single-slot identity cache
    re-uploaded operands every call when a consumer alternated between two
    operand dicts.  The digest-keyed cache stages each distinct content
    once — zero host→device transfers afterward, even through fresh dict
    objects."""
    arr = RNG.integers(0, 127, 1500).astype(np.uint32)
    ca = api.compress(arr, fmt.BITPACK, chunk_bytes=1024)
    plan = plan_mod.DecodePlan.build(ca.blobs).stage()
    epi = Epilogue(scale_key="epi_s", zero_key="epi_z")
    op_a = {"epi_s": np.float32(0.25), "epi_z": np.uint32(3)}
    op_b = {"epi_s": np.float32(0.5), "epi_z": np.uint32(1)}
    for op in (op_a, op_b):         # warm both contents (and compile)
        plan.execute_device(ENGINE, epilogue=epi, epilogue_operands=op)
    with transfers.count_host_transfers() as c:
        for _ in range(3):          # alternate via FRESH dict objects
            a = plan.execute_device(ENGINE, epilogue=epi,
                                    epilogue_operands=dict(op_a))[0]
            b = plan.execute_device(ENGINE, epilogue=epi,
                                    epilogue_operands=dict(op_b))[0]
    assert c["h2d"] == 0, c
    np.testing.assert_allclose(np.asarray(a),
                               (arr.astype(np.float32) - 3) * 0.25, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b),
                               (arr.astype(np.float32) - 1) * 0.5, rtol=1e-6)


def test_operand_cache_device_arrays_key_by_identity():
    """Operands already on device must NOT be content-hashed (hashing a
    jax array forces an implicit d2h sync that bypasses the funnel and
    trips the transfer guard on real accelerators) — they key by identity,
    and the cache holds a strong ref so the id stays valid."""
    import jax.numpy as jnp
    arr = RNG.integers(0, 127, 800).astype(np.uint32)
    ca = api.compress(arr, fmt.BITPACK, chunk_bytes=1024)
    plan = plan_mod.DecodePlan.build(ca.blobs).stage()
    epi = Epilogue(scale_key="epi_s")
    ops_dev = {"epi_s": jnp.float32(0.125)}          # device-resident
    plan.execute_device(ENGINE, epilogue=epi, epilogue_operands=ops_dev)
    assert len(plan._staged_operands) == 1
    with transfers.count_host_transfers() as c, transfers.no_host_transfers():
        out = plan.execute_device(ENGINE, epilogue=epi,
                                  epilogue_operands=ops_dev)[0]
        out.block_until_ready()
    assert c["h2d"] == 0 and c["d2h"] == 0
    assert len(plan._staged_operands) == 1           # identity hit, no growth
    np.testing.assert_allclose(np.asarray(out),
                               arr.astype(np.float32) * 0.125, rtol=1e-6)


def test_operand_cache_bounded():
    """The staging cache is an LRU bounded to OPERAND_CACHE_SLOTS."""
    arr = RNG.integers(0, 127, 600).astype(np.uint32)
    ca = api.compress(arr, fmt.BITPACK, chunk_bytes=1024)
    plan = plan_mod.DecodePlan.build(ca.blobs).stage()
    epi = Epilogue(scale_key="epi_s")
    for i in range(plan_mod.OPERAND_CACHE_SLOTS + 5):
        plan.execute_device(ENGINE, epilogue=epi,
                            epilogue_operands={"epi_s": np.float32(i + 1)})
    assert len(plan._staged_operands) == plan_mod.OPERAND_CACHE_SLOTS


# --------------------------------------------------------------------------
# satellite: blob_digest / pad_table_to_bucket live in core.format
# --------------------------------------------------------------------------


def test_digest_and_bucket_moved_to_format():
    """One definition each; server re-exports the same objects."""
    assert server.blob_digest is fmt.blob_digest
    assert server.pad_table_to_bucket is fmt.pad_table_to_bucket
    blob = api.compress(_runs(700), fmt.RLE_V2, chunk_bytes=512).blobs[0]
    assert fmt.blob_digest(blob) == server.blob_digest(blob)


def test_pad_table_rows_decodes_bit_exact():
    """The shared row-padding helper (bucketing + per-device uniform
    padding both use it): padded tables decode the real rows unchanged."""
    blobs = [api.compress(_runs(700, seed=60 + i), fmt.RLE_V2,
                          chunk_bytes=512).blobs[0] for i in range(3)]
    merged = fmt.concat_blobs(blobs)
    padded = fmt.pad_table_rows(merged, merged.num_chunks + 5)
    assert padded.num_chunks == merged.num_chunks + 5
    table = ENGINE.decompress_table(padded)
    np.testing.assert_array_equal(table[:merged.num_chunks],
                                  ENGINE.decompress_table(merged))
    assert not table[merged.num_chunks:].any()   # pad rows decode to zeros
    with pytest.raises(ValueError, match="pad"):
        fmt.pad_table_rows(merged, merged.num_chunks - 1)


def test_bucketed_plan_build():
    """Plan-level bucketing (the service window path) pads to pow2 rows
    without disturbing per-blob row ranges."""
    blobs = [api.compress(_runs(900, seed=i), fmt.RLE_V2,
                          chunk_bytes=512).blobs[0] for i in range(3)]
    plan = plan_mod.DecodePlan.build(blobs, bucket=True)
    (g,) = plan.groups
    assert g.merged.num_chunks & (g.merged.num_chunks - 1) == 0    # pow2
    for blob, out in zip(blobs, plan.execute(ENGINE)):
        assert np.array_equal(out, ENGINE.decompress(blob))


# --------------------------------------------------------------------------
# place stage + service device accounting (single-device degenerate cases)
# --------------------------------------------------------------------------


def test_place_stage_single_device_sharding():
    """Outputs are committed under a caller-supplied sharding (the place
    stage) — degenerate 1-device mesh in the fast in-process tier."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))
    arr = _runs(2048, seed=9)[:2048]
    ca = api.compress(arr, fmt.RLE_V2, chunk_bytes=1024)
    [out] = api.decompress_many([ca], ENGINE, device_out=True,
                                out_shardings=sh)
    assert out.sharding.is_equivalent_to(sh, out.ndim)
    assert np.array_equal(np.asarray(out), arr)


def test_placeable_divisibility():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))
    assert plan_mod.placeable((8,), sh)
    assert not plan_mod.placeable((), sh)          # spec longer than rank
    sh2 = NamedSharding(mesh, P(None, "data"))
    assert plan_mod.placeable((3, 7), sh2)         # 1-device axis divides


def test_service_round_robin_single_device_accounting():
    """ServiceStats.device_dispatches: with an explicit device list every
    fused dispatch is attributed to its assigned device (true round-robin
    spread is exercised on the 8-device mesh in test_plan_sharded.py)."""
    dev = jax.devices()[0]
    arrays = [_runs(700, seed=70 + i) for i in range(3)]
    arrays.append(RNG.integers(0, 200, 500).astype(np.uint8))
    blobs = [api.compress(a, fmt.RLE_V1, chunk_bytes=512).blobs[0]
             for a in arrays]
    with server.DecompressionService(ENGINE, devices=[dev],
                                     cache_bytes=0,
                                     bucket_shapes=False) as svc:
        futs = svc.submit_many(blobs)
        outs = [f.result(timeout=120) for f in futs]
        st = svc.stats()
    for a, o in zip(arrays, outs):
        assert np.array_equal(a, o)
    assert st.device_dispatches == {str(dev): st.dispatches}
    assert st.dispatches == 2                      # u32 group + u8 group


def test_service_without_devices_has_empty_accounting():
    blob = api.compress(_runs(400), fmt.RLE_V2, chunk_bytes=512).blobs[0]
    with server.DecompressionService(ENGINE) as svc:
        svc.decode(blob)
        assert svc.stats().device_dispatches == {}
