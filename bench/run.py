"""Run one benchmark cell on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; ``bench/harness.py`` finds its
configuration, traffic mix and metric readers by name.  The run asks JAX for
its devices first and exits non-zero, printing no result, unless it holds
as many TPU chips as the cell asks for.  It keeps JAX's persistent compile
cache in ``JAX_COMPILATION_CACHE_DIR`` where that is set, else in the fixed
``<checkout>/.jax_cache``.  It builds the cell's data from ``--seed``,
warms up every shape the cell's traffic uses (set-up, ``setup_s``),
measures for ``--seconds``, checks every answer against the plain
reference, and prints:

* on standard output, lines of sizes and compression ratios, request
  counts and times, compiles inside the window and, traced, the trace's
  extent; then as the last line one JSON object with
  ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
  ``--trace 1`` also ``busy_s``, ``window_s`` and ``breakdown``) and, last,
  ``checks``: each number compared, with its limit;
* on standard error, last, the same numbers and limits, one per line.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]
    from bench import harness
    try:
        cell = harness.resolve(args.workload)
        devices = harness.check_devices(cell.chips)
    except harness.CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"compile_cache {harness.enable_compile_cache()}", flush=True)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
        devices=devices, log=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} {c['is']}={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
