"""Open-loop multi-tenant serving benchmark for the DecompressionService.

The scenario the batch scheduler cannot cover: requests do NOT arrive
together.  ``n_tenants`` producer threads each replay an open-loop Poisson
arrival process (exponential inter-arrivals, submission times fixed up
front, so a slow service cannot slow the offered load — no coordinated
omission) over a shared pool of mixed-codec blobs.  The service coalesces
whatever lands inside each micro-batch window into fused dispatches, and
its content-keyed cache absorbs repeated blobs.

Headline numbers (the ISSUE-3 acceptance metric is the first one):

  * dispatch amplification — engine dispatches / blobs served.  The
    one-dispatch-per-blob baseline is exactly 1.0; coalescing + cache must
    push it below 1.0.
  * request latency p50/p99 (measured from the scheduled arrival time).
  * cache hit rate, blobs/window, dispatches/window.
  * decoded throughput vs. the synchronous per-blob loop.

    PYTHONPATH=src python -m benchmarks.serving [--smoke] [--out FILE.json]

Emits ``name,value,derived`` CSV rows (benchmarks/run.py convention) and,
with --out, the CI artifact BENCH_serving.json.
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np

from bench import compile_meter
from benchmarks.common import codec_matrix, demo_elems, write_bench_json
from repro.core import api, registry
from repro.core.engine import CodagEngine, EngineConfig
from repro.core.server import DecompressionService
from repro.kernels import ops


def build_pool(n_unique: int, kb_per_blob: int, chunk_bytes: int, seed: int):
    """Unique mixed-codec blobs (every registered codec contributes)."""
    rng = np.random.default_rng(seed)
    codecs = codec_matrix()
    arrays, blobs = [], []
    for i in range(n_unique):
        name = codecs[i % len(codecs)]
        codec = registry.get(name)
        arr = codec.demo_data(demo_elems(codec, kb_per_blob * 1024), rng)
        ca = api.compress(arr, name, chunk_bytes=chunk_bytes)
        arrays.append(arr)
        blobs.append(ca.blobs[0])
    return arrays, blobs


def build_trace(n_requests: int, n_tenants: int, n_unique: int,
                rate_per_tenant: float, seed: int):
    """Per-tenant (arrival_time, blob_idx) schedules; arrivals are a Poisson
    process per tenant, blob choice uniform over the shared pool (requests >
    unique blobs => repeats => cache hits)."""
    rng = np.random.default_rng(seed + 1)
    per = [n_requests // n_tenants] * n_tenants
    for i in range(n_requests - sum(per)):
        per[i] += 1
    traces = []
    for n in per:
        gaps = rng.exponential(1.0 / rate_per_tenant, n)
        arrivals = np.cumsum(gaps)
        idxs = rng.integers(0, n_unique, n)
        traces.append(list(zip(arrivals.tolist(), idxs.tolist())))
    return traces


def _serve_trace(svc, traces, blobs, arrays):
    """Replay one open-loop pass; returns (lat_ms, dispatches, bytes, secs)."""
    results: list = []
    res_lock = threading.Lock()

    def tenant(trace, t0):
        for t_arr, idx in trace:
            delay = t0 + t_arr - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            rec = {"sched": t0 + t_arr, "idx": idx}
            fut = svc.submit(blobs[idx])
            fut.add_done_callback(
                lambda f, rec=rec: rec.__setitem__(
                    "done", time.perf_counter()))
            with res_lock:
                results.append((fut, rec))

    t_start = time.perf_counter()
    with ops.count_dispatches() as dispatch_log:
        t0 = time.perf_counter() + 0.02     # common epoch for all tenants
        threads = [threading.Thread(target=tenant, args=(tr, t0))
                   for tr in traces]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = [(fut.result(), rec) for fut, rec in results]
    t_serve = time.perf_counter() - t_start

    for out, rec in outs:
        assert np.array_equal(out, arrays[rec["idx"]]), "serving not bit-exact"
    lat_ms = np.array([(rec["done"] - rec["sched"]) * 1e3
                       for _, rec in outs])
    served_bytes = sum(arrays[rec["idx"]].nbytes for _, rec in outs)
    return lat_ms, len(dispatch_log), served_bytes, t_serve


def run(n_requests: int = 96, n_tenants: int = 6, n_unique: int = 24,
        kb_per_blob: int = 16, rate_per_tenant: float = 120.0,
        chunk_bytes: int = 4 * 1024, seed: int = 0,
        max_delay_ms: float = 4.0, cache_mb: int = 64):
    arrays, blobs = build_pool(n_unique, kb_per_blob, chunk_bytes, seed)
    traces = build_trace(n_requests, n_tenants, n_unique, rate_per_tenant,
                         seed)
    engine = CodagEngine(EngineConfig())

    # priming pass on a throwaway service: jit caches are process-global,
    # so this pays every window-bucket compilation ONCE while the compile
    # meter (bench.compile_meter) attributes it — compile time becomes its
    # own metric (serving/compile_ms) instead of polluting the cold-pass
    # latency percentiles.  With tuning.enable_compile_cache() active the
    # same number directly shows the persistent cache's cold-start win.
    meter = compile_meter.meter()
    mark = meter.snapshot()
    with DecompressionService(engine, max_delay_ms=max_delay_ms,
                              idle_ms=max_delay_ms / 2,
                              cache_bytes=0) as svc_prime:
        _serve_trace(svc_prime, traces, blobs, arrays)
    compile_ms = compile_meter.delta(meter.snapshot(), mark)["compile_s"] * 1e3

    svc = DecompressionService(engine, max_delay_ms=max_delay_ms,
                               idle_ms=max_delay_ms / 2,
                               cache_bytes=cache_mb << 20)
    # pass 1 is cold for the SERVICE (empty decoded-blob cache) but
    # compile-free after priming; pass 2 replays the same offered load in
    # steady state: shape buckets hit the jit cache and repeated blobs hit
    # the decoded-blob cache.
    mark = meter.snapshot()
    lat_cold, disp_cold, served_bytes, t_cold = _serve_trace(
        svc, traces, blobs, arrays)
    residual_compile_ms = compile_meter.delta(
        meter.snapshot(), mark)["compile_s"] * 1e3
    lat_steady, disp_steady, _, t_steady = _serve_trace(
        svc, traces, blobs, arrays)
    svc_stats = svc.stats()
    svc.close()

    # baseline: synchronous one-dispatch-per-blob loop over the same trace
    flat_idxs = [idx for tr in traces for _, idx in tr]
    for idx in flat_idxs[:1]:
        engine.decompress(blobs[idx])    # warm the per-blob jit path too
    t0 = time.perf_counter()
    for idx in flat_idxs:
        engine.decompress(blobs[idx])
    t_loop = time.perf_counter() - t0

    amp = (disp_cold + disp_steady) / max(1, 2 * n_requests)
    rows = [
        ("serving/n_requests", n_requests, "per pass (2 passes)"),
        ("serving/n_tenants", n_tenants, ""),
        ("serving/unique_blobs", n_unique, ""),
        ("serving/served_MB", served_bytes / 1e6, ""),
        ("serving/dispatches/cold", disp_cold, ""),
        ("serving/dispatches/steady", disp_steady, ""),
        ("serving/dispatch_amplification", amp,
         "vs 1.0 per-blob baseline"),
        ("serving/windows", svc_stats.windows, ""),
        ("serving/blobs_per_window", svc_stats.blobs_per_window, ""),
        ("serving/dispatches_per_window", svc_stats.dispatches_per_window, ""),
        ("serving/cache_hit_rate", svc_stats.cache_hit_rate, ""),
        ("serving/compile_ms", round(compile_ms, 2),
         "backend-compile time of the serving path (priming pass)"),
        ("serving/compile_ms/residual_cold", round(residual_compile_ms, 2),
         "compile leaking into the cold pass after priming"),
        ("serving/latency_p50_ms/cold", float(np.percentile(lat_cold, 50)),
         "compile-free: jit primed, decoded-blob cache empty"),
        ("serving/latency_p99_ms/cold", float(np.percentile(lat_cold, 99)), ""),
        ("serving/latency_p50_ms", float(np.percentile(lat_steady, 50)),
         "steady state"),
        ("serving/latency_p99_ms", float(np.percentile(lat_steady, 99)),
         "steady state"),
        ("serving/throughput_MBps/service", served_bytes / t_steady / 1e6, ""),
        ("serving/throughput_MBps/per_blob", served_bytes / t_loop / 1e6, ""),
        ("serving/speedup_vs_per_blob", t_loop / t_steady, ""),
    ]
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI: finishes in well under a minute")
    ap.add_argument("--n-requests", type=int, default=96)
    ap.add_argument("--n-tenants", type=int, default=6)
    ap.add_argument("--n-unique", type=int, default=24)
    ap.add_argument("--kb-per-blob", type=int, default=16)
    ap.add_argument("--rate", type=float, default=120.0,
                    help="offered load per tenant, requests/s")
    ap.add_argument("--out", default=None, help="also write a JSON artifact")
    args = ap.parse_args()
    if args.smoke:
        args.n_requests, args.n_tenants = 40, 4
        args.n_unique, args.kb_per_blob = 10, 8
        args.rate = 200.0

    rows = run(args.n_requests, args.n_tenants, args.n_unique,
               args.kb_per_blob, args.rate)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value},{derived}")

    if args.out:
        cfg = {"n_requests": args.n_requests, "n_tenants": args.n_tenants,
               "n_unique": args.n_unique, "kb_per_blob": args.kb_per_blob,
               "rate_per_tenant": args.rate, "smoke": bool(args.smoke)}
        print(f"# wrote {write_bench_json(args.out, 'serving', cfg, rows)}")


if __name__ == "__main__":
    main()
