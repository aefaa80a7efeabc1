"""The control: the plain reference in the program's place, with the
configurations' one guarantee broken.

The configuration states a lossless decode: every value bit-exact, 64-bit
columns included.  The control answers every request from the reference,
but as a decode that gives up exactness would: every value wider than a
byte passes through bfloat16 (8 significant bits) and comes back in its own
dtype.  The run's comparison has to find it not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

runs the cell with the control in the program's place, once per seed, in
one process, and prints each run's numbers compared with their limits.
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def degrade(a):
    import ml_dtypes
    import numpy as np
    if a.dtype.itemsize == 1:
        return a
    return a.astype(np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32).astype(a.dtype)


class Control:
    """A system that answers from the degraded reference."""

    def __init__(self, system):
        self._system = system
        self.items = system.items

    def _answer(self, i: int, device_out: bool) -> list:
        import jax
        outs = [degrade(a) for a in self._system.reference(i)]
        if device_out:
            with jax.enable_x64(True):
                outs = [jax.device_put(a) for a in outs]
        return outs

    def call(self, i: int, device_out: bool):
        return self._answer(i, device_out)

    def decoded_bytes(self, i: int) -> int:
        return self._system.decoded_bytes(i)

    def compressed_bytes(self, i: int) -> int:
        return self._system.compressed_bytes(i)

    def reference(self, i: int) -> list:
        return self._system.reference(i)

    def describe(self) -> str:
        return "control in the program's place; " + self._system.describe()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]
    from bench import harness
    try:
        cell = harness.resolve(args.workload)
        devices = harness.check_devices(cell.chips)
    except harness.CellError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    rows = []
    for seed in args.seeds:
        res = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(), devices=devices,
                               wrap=Control, log=lambda s: None)
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"], "checks": res["checks"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({"workload": args.workload, "control_runs": len(rows),
                      "control_correct": [r["correct"] for r in rows],
                      "mismatched": [r["checks"]["mismatched"]["value"]
                                     for r in rows]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
