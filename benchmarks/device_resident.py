"""Host-round-trip vs device-resident decode→consume (the ISSUE-4 metric).

The workload is quantized serving: N layers of (K, N) int8 weights stored
compressed, consumed by the fused dequant matmul.  Two pipelines over the
SAME blobs:

  * host round-trip — the pre-tentpole path: batched decode, ``np``
    reassembly on host, re-upload, then matmul.  Pays the uncompressed
    output bandwidth twice plus a blocking sync per group.
  * device-resident — ``api.decompress_many(..., device_out=True)`` with
    the zero-point epilogue fused into the decode dispatch, fed straight
    into ``dequant_matmul``.  Host transfers on the decode path: zero,
    counted via ``transfers.count_host_transfers`` (the funnel every
    sanctioned d2h materialization crosses) and verified by running the
    steady-state pass inside ``jax.transfer_guard("disallow")``
    (``transfers.no_host_transfers``).

    PYTHONPATH=src python -m benchmarks.device_resident [--smoke] [--out F]

Emits ``name,value,derived`` CSV rows (benchmarks/run.py convention) and,
with --out, the CI artifact BENCH_device.json (shared schema).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import write_bench_json
from repro.core import api, transfers
from repro.core import plan as plan_mod
from repro.core.engine import CodagEngine, EngineConfig
from repro.kernels import dequant_matmul as dqm

ZERO_POINT = 8


def build_weights(n_layers: int, m: int, k: int, n: int, seed: int = 0):
    """Quantized weight stack + activations: low-magnitude int8 (|q| < 8,
    the post-training-quantization shape bitpack exploits: 5 bits/weight)."""
    rng = np.random.default_rng(seed)
    qs = [rng.integers(-ZERO_POINT, ZERO_POINT, (k, n)).astype(np.int8)
          for _ in range(n_layers)]
    scales = [rng.uniform(0.01, 0.1, (1, n)).astype(np.float32)
              for _ in range(n_layers)]
    x = rng.normal(size=(m, k)).astype(np.float32)
    cas = [dqm.compress_weights(q, "bitpack", zero_point=ZERO_POINT)
           for q in qs]
    return qs, scales, x, cas


def _median(fn, iters: int) -> float:
    fn()  # warmup (jit trace / staging)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def run(n_layers: int = 4, m: int = 128, k: int = 256, n: int = 256,
        iters: int = 3, seed: int = 0):
    qs, scales, x, cas = build_weights(n_layers, m, k, n, seed)
    engine = CodagEngine(EngineConfig())
    x_dev = jnp.asarray(x)
    s_dev = [jnp.asarray(s) for s in scales]
    weight_bytes = sum(q.nbytes for q in qs)
    comp_bytes = sum(ca.compressed_bytes for ca in cas)

    def host_round_trip():
        outs = []
        # decode lands on host (stored uint8), zero-point correction and
        # re-upload happen per layer — the pre-tentpole consumer shape
        for ca, s in zip(cas, s_dev):
            stored = api.decompress(ca, engine)            # device -> host
            q = (stored.astype(np.int16) - ZERO_POINT).astype(np.int8)
            outs.append(dqm.dequant_matmul(
                x_dev, jnp.asarray(q), s))                 # host -> device
        return jax.block_until_ready(outs)

    epi, operands = dqm.weight_epilogue(ZERO_POINT)
    plan = plan_mod.DecodePlan.build([b for ca in cas for b in ca.blobs]).stage()

    def device_resident():
        dev_qs = plan.execute_device(engine, epilogue=epi,
                                     epilogue_operands=operands)
        return jax.block_until_ready(
            [dqm.dequant_matmul(x_dev, q, s)
             for q, s in zip(dev_qs, s_dev)])

    # correctness first: both paths equal the uncompressed oracle
    want = [np.asarray(dqm.ref_dequant_matmul(
        x_dev, jnp.asarray(q), s)) for q, s in zip(qs, s_dev)]
    for w, a, b in zip(want, host_round_trip(), device_resident()):
        np.testing.assert_allclose(w, np.asarray(a), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(w, np.asarray(b), rtol=1e-4, atol=1e-5)

    with transfers.count_host_transfers() as host_cnt:
        t_host = _median(host_round_trip, iters)
    with transfers.count_host_transfers() as dev_cnt:
        t_dev = _median(device_resident, iters)
    # the acceptance check: the steady-state device pass completes with the
    # transfer guard armed (raises on any host materialization)
    with transfers.no_host_transfers():
        device_resident()

    per_host = host_cnt["d2h"] / (iters + 1)     # +1 warmup
    rows = [
        ("device/n_layers", n_layers, ""),
        ("device/weight_MB", weight_bytes / 1e6, ""),
        ("device/compression_ratio", comp_bytes / max(1, weight_bytes), ""),
        ("device/host_transfers_per_iter/host_path", per_host, ""),
        ("device/host_transfers_per_iter/device_path",
         dev_cnt["d2h"] / (iters + 1), "guard-verified 0"),
        ("device/host_bytes_per_iter/host_path",
         host_cnt["bytes"] / (iters + 1), ""),
        ("device/latency_ms/host_path", t_host * 1e3, ""),
        ("device/latency_ms/device_path", t_dev * 1e3, ""),
        ("device/throughput_MBps/host_path",
         weight_bytes / t_host / 1e6, ""),
        ("device/throughput_MBps/device_path",
         weight_bytes / t_dev / 1e6, f"{t_host / t_dev:.2f}x host path"),
        ("device/speedup", t_host / t_dev, ""),
    ]
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI: finishes in well under a minute")
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=None, help="also write a JSON artifact")
    args = ap.parse_args()
    if args.smoke:
        args.n_layers, args.k, args.n, args.iters = 2, 128, 128, 1

    rows = run(args.n_layers, args.m, args.k, args.n, args.iters)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value},{derived}")

    if args.out:
        cfg = {"n_layers": args.n_layers, "m": args.m, "k": args.k,
               "n": args.n, "iters": args.iters, "smoke": bool(args.smoke)}
        print(f"# wrote {write_bench_json(args.out, 'device', cfg, rows)}")


if __name__ == "__main__":
    main()
