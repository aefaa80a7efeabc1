"""Mesh-sharded decode executor (ISSUE-5 tentpole, multi-device half).

Runs in subprocesses under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(conftest keeps the main pytest process at 1 device).  Fast tier: each test
is ONE subprocess that batches many assertions — bit-exactness vs
single-device decode for every registered codec (including ragged group
splits, odd tails, and 64-bit planes), checkpoint restore leaves committed
under their requested ``NamedSharding`` with zero ``to_host`` crossings,
sharded token-shard pipelines, and the service's round-robin multi-device
scheduling.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice   # dedicated CI step (8 CPU devices)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, ndev: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_sharded_executor_bit_exact_all_codecs():
    """Every registry codec, on an 8-device mesh: execute_sharded ==
    single-device host decode, covering ragged group splits (chunk counts
    not divisible by the device count, single-chunk blobs), odd tails, and
    mixed-geometry fused groups.  Staged steady state re-executes with
    zero transfers in either direction."""
    out = run_py("""
        import numpy as np, jax
        from repro.core import api, registry, transfers
        from repro.core import plan as plan_mod
        from repro.core.engine import CodagEngine, EngineConfig
        from repro.launch import mesh as mesh_lib

        assert len(jax.devices()) == 8
        mesh = mesh_lib.make_decode_mesh()
        eng = CodagEngine(EngineConfig())
        rng = np.random.default_rng(0)

        def demo(name, n, seed=0):
            codec = registry.get(name)
            if n == 0:
                return np.zeros(0, np.uint8 if codec.byte_stream
                                else np.uint32)
            return codec.demo_data(n, np.random.default_rng(seed))[:n]

        for name in registry.names():
            # sizes chosen for ragged splits: single chunk, odd tails,
            # chunk counts that do NOT divide by 8
            cas = [api.compress(demo(name, n, seed=n), name,
                                chunk_bytes=1024)
                   for n in (1, 777, 1025, 4097)]
            host = [api.decompress(ca, eng) for ca in cas]
            outs = api.decompress_many(cas, eng, mesh=mesh)
            for h, o in zip(host, outs):
                o = np.asarray(o)
                assert o.dtype == h.dtype and o.shape == h.shape, name
                assert np.array_equal(o, h), name
            print("OK", name)

        # staged steady state: zero transfers either direction
        blobs = [b for n in ("rle_v2", "bitpack")
                 for b in api.compress(demo(n, 4097, seed=1), n,
                                       chunk_bytes=1024).blobs]
        plan = plan_mod.DecodePlan.build(blobs)
        plan.execute_sharded(mesh, engine=eng)
        with transfers.count_host_transfers() as c, \\
                transfers.no_host_transfers():
            for o in plan.execute_sharded(mesh, engine=eng):
                o.block_until_ready()
        assert c["d2h"] == 0 and c["h2d"] == 0, c
        print("STEADY", c["d2h"], c["h2d"])
        print("PASS")
    """)
    assert "PASS" in out


def test_sharded_64bit_planes():
    out = run_py("""
        import numpy as np, jax
        from repro.core import api
        from repro.core.engine import CodagEngine, EngineConfig
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_decode_mesh()
        eng = CodagEngine(EngineConfig())
        rng = np.random.default_rng(3)

        # 64-bit planes: lo/hi u32 blobs share one group; rows split
        # across devices and recombine on device
        for dtype in ("int64", "uint64", "float64"):
            if dtype == "float64":
                arr = np.round(rng.normal(size=1003), 2).astype(np.float64)
            else:
                arr = rng.integers(0, 5000, 1003).astype(dtype)
            ca = api.compress(arr, "rle_v2", chunk_bytes=1024)
            host = api.decompress(ca, eng)
            with jax.enable_x64(True):
                [dev] = api.decompress_many([ca], eng, mesh=mesh)
                assert str(dev.dtype) == dtype
                assert np.array_equal(np.asarray(dev), host)
            print("OK", dtype)
        print("PASS")
    """)
    assert "PASS" in out


def test_sharded_restore_places_leaves(tmp_path):
    """restore(shardings=..., device_out=True): compressed leaves decode
    across the shardings' mesh and come back committed under each leaf's
    requested NamedSharding — with zero to_host funnel crossings."""
    out = run_py(f"""
        import numpy as np, jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import transfers
        from repro.checkpoint import checkpoint as ckpt

        rng = np.random.default_rng(9)
        state = {{"w": rng.normal(size=(64, 64)).astype(np.float32),
                  "m": rng.integers(0, 200, (128, 32)).astype(np.int32),
                  "small": np.float32(1.5)}}
        ckpt.save("{tmp_path}", 3, state, codec="rle_v2")

        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        shs = {{"w": NamedSharding(mesh, P("data", "model")),
                "m": NamedSharding(mesh, P("data", None)),
                "small": NamedSharding(mesh, P())}}
        with transfers.count_host_transfers() as c:
            out = ckpt.restore("{tmp_path}", 3, state, shardings=shs,
                               device_out=True)
        assert c["d2h"] == 0, c
        for k, v in state.items():
            got = out[k]
            assert got.sharding.is_equivalent_to(shs[k], got.ndim), \\
                (k, got.sharding)
            assert str(got.dtype) == str(np.asarray(v).dtype)
            assert np.array_equal(np.asarray(got), v), k
        print("PASS")
    """)
    assert "PASS" in out


def test_sharded_pipeline_and_service_round_robin():
    out = run_py("""
        import numpy as np, jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import api
        from repro.core.engine import CodagEngine, EngineConfig
        from repro.core.server import DecompressionService
        from repro.data import pipeline as pl
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_decode_mesh()
        eng = CodagEngine(EngineConfig())

        # token shards born sharded over the data axis, bit-exact
        toks = pl.synthetic_corpus(32768, 500, seed=2)
        store = pl.CompressedTokenStore.build(toks, 500, shard_tokens=8192,
                                              chunk_bytes=2048)
        want_sh = NamedSharding(mesh, P("data"))
        host = list(store.decoded_shards(eng, window=2))
        dev = list(store.decoded_shards(eng, window=2, mesh=mesh))
        assert len(host) == len(dev) >= 2
        for h, d in zip(host, dev):
            assert d.sharding.is_equivalent_to(want_sh, d.ndim), d.sharding
            assert np.array_equal(np.asarray(d), h)
        loader = pl.CompressedLoader(store, batch=2, seq=128, engine=eng,
                                     prefetch=False, mesh=mesh)
        b = next(iter(loader))
        hb = next(iter(pl.CompressedLoader(store, batch=2, seq=128,
                                           engine=eng, prefetch=False)))
        assert np.array_equal(np.asarray(b["tokens"]),
                              np.asarray(hb["tokens"]))
        print("pipeline OK")

        # service: round-robin group->device assignment across all 8
        rng = np.random.default_rng(0)
        arrays = ([np.repeat(rng.integers(0, 50, 20).astype(np.uint32),
                             50 + i) for i in range(4)] +
                  [rng.integers(0, 200, 600 + i).astype(np.uint8)
                   for i in range(4)] +
                  [rng.integers(0, 127, 900 + i).astype(np.uint32)
                   for i in range(4)])
        codecs = ["rle_v2"] * 4 + ["rle_v1"] * 4 + ["bitpack"] * 4
        blobs = [api.compress(a, c, chunk_bytes=512).blobs[0]
                 for a, c in zip(arrays, codecs)]
        with DecompressionService(eng, devices=jax.devices(),
                                  cache_bytes=0, bucket_shapes=False,
                                  max_batch_blobs=4) as svc:
            futs = svc.submit_many(blobs[:4]) + svc.submit_many(blobs[4:8]) \\
                + svc.submit_many(blobs[8:])
            outs = [f.result(timeout=300) for f in futs]
            st = svc.stats()
        for a, o in zip(arrays, outs):
            assert np.array_equal(a, o)
        assert sum(st.device_dispatches.values()) == st.dispatches >= 3
        # round-robin spread: more than one device did work
        assert len(st.device_dispatches) >= 2, st.device_dispatches
        print("service OK", st.device_dispatches)
        print("PASS")
    """)
    assert "PASS" in out
