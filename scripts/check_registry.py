"""Registry-completeness CI gate.

Fails (exit 1) if any registered codec is missing from:
  * the fast-tier test matrix (tests/test_codecs.py parametrizes over
    ``registry.names()`` — verified here by importing its module-level
    matrix), or
  * the bench-smoke matrix (benchmarks/batched.py), or
  * the golden conformance vectors (tests/vectors/<codec>.json — the
    committed encode/decode fixtures tests/test_conformance.py runs on
    every backend), or
  * the committed tuned-defaults table (src/repro/core/tuned_defaults.json
    — every codec needs an entry, possibly an explicit ``{}``, and knob
    names must be known to core.tuning / the codec's DecodeSpec tunables).

Also validates that every codec's plugin surface is complete enough for
those matrices to actually exercise it (encode/decode hooks + demo data),
and that every codec's decode LOWERS THROUGH THE PLAN IR: each
``ops.decode`` kernel dispatch a round trip issues must originate in
``core.plan.dispatch`` (equal ``plan.count_lowered`` /
``ops.count_dispatches`` records) — a codec wired around the unified
pipeline fails the gate.

    PYTHONPATH=src python scripts/check_registry.py
"""
from __future__ import annotations

import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> int:
    import numpy as np

    from repro.core import api, format as fmt, registry

    problems: list[str] = []
    names = set(registry.names())

    # every built-in must be registered; EXTRA (third-party) codecs are fine
    # as long as they appear in the matrices below.
    if not set(fmt.CODECS) <= names:
        problems.append(
            f"built-ins {sorted(set(fmt.CODECS) - names)} missing from registry")

    # fast-tier test matrix
    def _diff(what: str, matrix: set) -> None:
        """Name exactly which codecs a matrix is missing / has extra."""
        missing, extra = names - matrix, matrix - names
        if missing:
            problems.append(f"{what}: missing codec(s) {sorted(missing)} "
                            f"(parametrize over registry.names())")
        if extra:
            problems.append(f"{what}: unregistered codec(s) {sorted(extra)} "
                            f"(register them or drop them from the matrix)")

    sys.path.insert(0, str(_ROOT / "tests"))
    try:
        import test_codecs
        _diff("tests/test_codecs.py ALL_CODECS", set(test_codecs.ALL_CODECS))
    finally:
        sys.path.pop(0)

    # bench-smoke matrix
    from benchmarks import batched
    _diff("benchmarks.batched.codec_matrix()", set(batched.codec_matrix()))

    # golden conformance vectors: every codec must commit fixtures
    vec_dir = _ROOT / "tests" / "vectors"
    for name in sorted(names):
        vec_file = vec_dir / f"{name}.json"
        if not vec_file.exists():
            problems.append(
                f"{name}: no golden vectors at {vec_file} "
                f"(run scripts/make_vectors.py and commit)")
            continue
        import json
        n_vec = len(json.loads(vec_file.read_text())["vectors"])
        if n_vec < 5:
            problems.append(
                f"{name}: only {n_vec} golden vectors (full matrix expected)")

    # tuned-defaults coverage: every codec must appear in the committed
    # autotune table — an empty {} is the explicit "nothing tuned yet"
    # fallback — and every knob it carries must be one the engine
    # understands (tuning.KNOWN_KNOBS + the codec's own DecodeSpec
    # tunables), so a typo'd knob name cannot silently become a no-op.
    from repro.core import tuning
    tuned = tuning.load_table().get("codecs", {})
    for name in sorted(names):
        if name not in tuned:
            problems.append(
                f"{name}: missing from tuned-defaults table "
                f"({tuning.DEFAULT_TABLE_PATH.name}; an explicit {{}} entry "
                f"counts — run benchmarks.autotune --write-table)")
            continue
        spec = registry.get(name).decode
        allowed = set(tuning.KNOWN_KNOBS) | {
            t.name for t in getattr(spec, "tunables", ())}
        for width_key, kinds in tuned[name].items():
            for kind, knobs in kinds.items():
                unknown = {k for k in knobs if not k.startswith("_")} - allowed
                if unknown:
                    problems.append(
                        f"{name}: unknown tuned knobs {sorted(unknown)} "
                        f"({width_key}/{kind}); allowed: {sorted(allowed)}")

    # plugin surface completeness + a tiny end-to-end round trip per codec,
    # with the plan-lowering gate armed: every kernel dispatch the round
    # trip issues must have been lowered by core.plan.dispatch.
    from repro.core import plan as plan_mod
    from repro.core.engine import CodagEngine, EngineConfig
    from repro.kernels import ops

    engine = CodagEngine(EngineConfig())
    rng = np.random.default_rng(0)
    for name in sorted(names):
        codec = registry.get(name)
        if codec.demo_data is None:
            problems.append(f"{name}: no demo_data (bench matrices need it)")
            continue
        arr = codec.demo_data(256, rng)
        ca = api.compress(arr, name, chunk_bytes=512)
        with plan_mod.count_lowered() as lowered, \
                ops.count_dispatches() as dispatched:
            out = api.decompress(ca, engine)
        if not np.array_equal(out, arr):
            problems.append(f"{name}: demo round trip is not bit-exact")
        if not dispatched:
            problems.append(f"{name}: round trip issued no kernel dispatch")
        elif len(lowered) != len(dispatched):
            problems.append(
                f"{name}: decode bypasses plan lowering "
                f"({len(dispatched)} ops.decode dispatches, only "
                f"{len(lowered)} lowered through core.plan.dispatch)")

    if problems:
        for p in problems:
            print(f"REGISTRY CHECK FAILED: {p}", file=sys.stderr)
        return 1
    print(f"registry complete: {sorted(names)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
