"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--size-mb 1.0] [--only X]
                                            [--all] [--smoke] [--out-dir D]

Prints ``name,value,derived`` CSV rows:
  ratios.py           -> Table V (compression ratios, symbol lengths)
  roofline_report.py  -> §Roofline terms from the dry-run artifacts
  batched.py          -> launches-per-restore + throughput, batched vs
                         per-blob decode (core.plan scheduler)
  serving.py          -> open-loop multi-tenant DecompressionService:
                         dispatch amplification, latency percentiles,
                         cache hit rate
  device_resident.py  -> host-round-trip vs device-resident decode→consume
                         (transfer counts + throughput)
  sharded.py          -> single- vs 8-virtual-device mesh decode
                         (execute_sharded) + per-device dispatch counts
                         (runs in a forced-device-count subprocess)
  store.py            -> tiered-blob-store overlap efficiency: prefetch-
                         streamed vs serial load-then-decode vs all-in-RAM,
                         exactly-once paging + watermark eviction counts

``--all`` additionally writes one ``BENCH_<suite>.json`` per suite (shared
schema ``{name, config, metrics, timestamp}`` — see
``common.write_bench_json``) into ``--out-dir`` (default: repo root), which
CI uploads as a single perf-trajectory artifact.  ``--smoke`` shrinks every
suite to CI-friendly sizes.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path


def build_suites(args) -> dict:
    """{suite: (config_dict, thunk)} — the thunk returns CSV rows."""
    from benchmarks import (autotune, batched, collectives, device_resident,
                            ratios, roofline_report, serving, sharded, store)
    size_mb = 0.05 if args.smoke else args.size_mb
    batched_cfg = ({"n_arrays": 8, "kb_per_array": 8, "iters": 1}
                   if args.smoke else
                   {"n_arrays": 12,
                    "kb_per_array": max(8, int(args.size_mb * 64)),
                    "iters": 3})
    serving_cfg = ({"n_requests": 40, "n_tenants": 4, "n_unique": 10,
                    "kb_per_blob": 8, "rate_per_tenant": 200.0}
                   if args.smoke else
                   {"n_requests": 64, "n_tenants": 4, "n_unique": 16,
                    "kb_per_blob": max(8, int(args.size_mb * 32))})
    device_cfg = ({"n_layers": 2, "k": 128, "n": 128, "iters": 1}
                  if args.smoke else {"n_layers": 4, "iters": 3})
    sharded_cfg = ({"n_arrays": 4, "kb_per_array": 8, "iters": 1, "ndev": 8}
                   if args.smoke else
                   {"n_arrays": 8,
                    "kb_per_array": max(16, int(args.size_mb * 64)),
                    "iters": 3, "ndev": 8})
    autotune_cfg = ({"smoke": True, "size_mb": 0.05, "probe_kb": 8}
                    if args.smoke else
                    {"smoke": False, "size_mb": min(size_mb, 0.25),
                     "probe_kb": 16})
    store_cfg = ({"n_leaves": 15, "kb_per_leaf": 128, "window": 3,
                  "read_delay_ms": 6.0, "iters": 3}
                 if args.smoke else
                 {"n_leaves": 16, "kb_per_leaf": max(128, int(args.size_mb * 512)),
                  "window": 4, "read_delay_ms": 5.0, "iters": 3})
    collectives_cfg = ({"steps": 12, "outer_every": 4, "batch": 2,
                        "seq": 64, "link_rtt_ms": 40.0, "topk_frac": 0.01}
                       if args.smoke else
                       {"steps": 24, "outer_every": 8, "batch": 2,
                        "seq": 64, "link_rtt_ms": 40.0, "topk_frac": 0.01})
    return {
        "ratios": ({"size_mb": size_mb}, lambda: ratios.run(size_mb)),
        "roofline": ({}, roofline_report.run),
        "batched": (batched_cfg, lambda: batched.run(**batched_cfg)),
        "serving": (serving_cfg, lambda: serving.run(**serving_cfg)),
        "device": (device_cfg, lambda: device_resident.run(**device_cfg)),
        "sharded": (sharded_cfg, lambda: sharded.run(**sharded_cfg)),
        "autotune": (autotune_cfg, lambda: autotune.run(**autotune_cfg)),
        "store": (store_cfg, lambda: store.run(**store_cfg)),
        "collectives": (collectives_cfg,
                        lambda: collectives.run(**collectives_cfg)),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=0.25,
                help="per-dataset size; 0.25 keeps the full suite ~10 min on CPU")
    ap.add_argument("--only", default=None,
                    help="ratios|roofline|batched|serving|device|sharded|"
                         "autotune|store|collectives")
    ap.add_argument("--all", action="store_true",
                    help="write one BENCH_<suite>.json per suite "
                         "(shared schema) into --out-dir")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: every suite finishes in seconds")
    ap.add_argument("--out-dir", default=".",
                    help="where --all writes the BENCH_*.json artifacts")
    ap.add_argument("--update-baselines", action="store_true",
                    help="also write each suite's artifact into "
                         "benchmarks/baselines/ (the committed reference "
                         "scripts/check_bench.py gates CI against)")
    ap.add_argument("--compile-cache", nargs="?", const=True, default=None,
                    metavar="DIR",
                    help="enable the persistent jit compilation cache "
                         "(tuning.enable_compile_cache) before any suite "
                         "runs; optional dir, default dir when given bare")
    args = ap.parse_args()

    if args.compile_cache:
        from repro.core import tuning
        path = tuning.enable_compile_cache(
            None if args.compile_cache is True else args.compile_cache)
        print(f"# compile cache: {path}", flush=True)

    from benchmarks.common import write_bench_json
    suites = build_suites(args)
    if args.only:
        suites = {args.only: suites[args.only]}
    baseline_dir = Path(__file__).resolve().parent / "baselines"

    print("name,value,derived")
    ok = True
    for sname, (config, fn) in suites.items():
        t0 = time.time()
        try:
            rows = list(fn())
            for name, value, derived in rows:
                print(f"{name},{value},{derived}")
            if args.all:
                cfg = dict(config, smoke=bool(args.smoke))
                out = write_bench_json(
                    Path(args.out_dir) / f"BENCH_{sname}.json",
                    sname, cfg, rows)
                print(f"# wrote {out}", flush=True)
            if args.update_baselines:
                cfg = dict(config, smoke=bool(args.smoke))
                out = write_bench_json(
                    baseline_dir / f"BENCH_{sname}.json", sname, cfg, rows)
                print(f"# wrote baseline {out}", flush=True)
        except Exception as e:  # pragma: no cover
            ok = False
            print(f"{sname}/ERROR,{type(e).__name__},{e}", file=sys.stderr)
        print(f"_suite/{sname}/seconds,{time.time()-t0:.1f},", flush=True)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
