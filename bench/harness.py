"""Cells from ``BENCHMARK.json``, found by name, and one measured run of a cell.

A cell names a configuration and a traffic mix; the harness finds

* ``bench/configs/<config>.json`` (the ``file`` of the configuration entry):
  the deployment as run;
* ``bench/configs/<config>.py``: ``build(cfg, traffic, seed)`` returns the
  system under test, and holds the plain reference;
* ``bench/traffic/<traffic>.json``: the mix (``bench/generator.py``), with
  its loop ``bench/loops/<loop>.py`` and its key choice
  ``bench/keys/<kind>.py``;
* ``bench/metrics/<metric>.py``: ``read(run)`` gives the metric's value from
  a finished run, or None where it finds nothing to read.

A later change adds a cell, a configuration, a mix, a loop, a key choice or
a metric by adding files and entries; no file here names one.

A system (what ``build`` returns) has ``items``; ``call(i, device_out)``,
which returns the outputs, maybe still computing on the device;
``decoded_bytes(i)`` and ``compressed_bytes(i)``; ``reference(i)``, the
expected outputs as host arrays, computed without the program; and
``describe()``, a line of sizes for the log.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import tempfile
import time
from typing import Any, Callable, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(Exception):
    """A cell cannot be run as ``BENCHMARK.json`` describes it."""


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    chips: int
    cfg: dict
    mix: Any                # bench.generator.Mix
    config_module: Any
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: dict           # metric name -> module with read(run)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _need(path: str, what: str, root: str) -> str:
    if not os.path.isfile(path):
        raise CellError(f"{what}: missing file {os.path.relpath(path, root)}")
    return path


def _code(root: str, what: str, kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` of ``root``'s checkout."""
    path = _need(os.path.join(root, "bench", kind, name + ".py"), what, root)
    return load_module(path, f"bench_{kind}_{name}".replace(".", "_"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    from bench import generator
    bench = json.load(open(_need(os.path.join(root, "BENCHMARK.json"),
                                 "benchmark", root)))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; known: "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {workload}: unknown config {w['config']!r}")
    conf = configs[w["config"]]
    what = f"config {conf['name']}"
    cfg = json.load(open(_need(os.path.join(root, conf["file"]), what, root)))
    config_module = _code(root, what, "configs", conf["name"])
    what = f"traffic {w['traffic']}"
    traffic = json.load(open(_need(os.path.join(
        root, "bench", "traffic", w["traffic"] + ".json"), what, root)))
    mix = generator.Mix(
        traffic=traffic, loop=_code(root, what, "loops", traffic["loop"]),
        key_choice=_code(root, what, "keys", traffic["keys"]["kind"]))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    readers = {m["name"]: _code(root, f"metric {m['name']}", "metrics",
                                m["name"]) for m in e2e + layer}
    return Cell(name=workload, config_name=conf["name"],
                chips=int(w["chips"]), cfg=cfg, mix=mix,
                config_module=config_module, end_to_end=e2e,
                per_layer=layer, readers=readers)


def check_devices(chips: int) -> list:
    """The TPU devices, or CellError: the benchmark has no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CellError(f"needs a TPU; JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise CellError(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else the fixed ``<checkout>/.jax_cache``; before any compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a metric reader sees of a finished run."""
    system: Any
    requests: list
    t0: float               # window start (perf_counter)
    setup_s: float
    setup_compile: dict     # compile meter totals over the set-up
    trace: Optional[dict]   # bench.trace.reduce() of the traced window
    device_kind: str


def _same(got, want: np.ndarray) -> bool:
    got = np.asarray(got)
    return (got.dtype == want.dtype and got.shape == want.shape
            and np.array_equal(got.reshape(-1).view(np.uint8),
                               want.reshape(-1).view(np.uint8)))


def _placed(out, device_out: bool, devices) -> bool:
    import jax
    if device_out:
        return isinstance(out, jax.Array) and out.devices() <= set(devices)
    return isinstance(out, np.ndarray)


def _as_list(out) -> list:
    return list(out) if isinstance(out, (list, tuple)) else [out]


def to_host(requests: list, devices) -> None:
    """Judge where each answer lives, then bring it to the host, so that the
    program's state can be freed before the reference runs."""
    for r in requests:
        if r.out is not None:
            outs = _as_list(r.out)
            r.placed = all(_placed(o, r.device_out, devices) for o in outs)
            r.out = [np.asarray(o) for o in outs]


def compare(system, requests: list) -> dict:
    """Every answer due in the window and kept (``generator.KEEP``) against
    the plain reference: bit-exact, and where the request asked for it (HBM
    or host).  A request that failed counts as missing."""
    compared = mismatched = missing = 0
    for r in requests:
        if not r.kept and r.error is None and r.done is not None:
            continue
        if r.error is not None or r.done is None or r.out is None:
            missing += 1
            continue
        want = system.reference(r.item)
        ok = r.placed and len(r.out) == len(want) and all(
            _same(o, w) for o, w in zip(r.out, want))
        compared += 1
        mismatched += not ok
    return {"compared": compared, "mismatched": mismatched,
            "missing": missing}


# number compared -> (limit, "max" or "min")
CHECK_LIMITS = {"mismatched": (0, "max"), "missing": (0, "max"),
                "compared": (1, "min")}


def passes(value, limit: tuple) -> bool:
    bound, kind = limit
    return value <= bound if kind == "max" else value >= bound


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, wrap: Optional[Callable] = None,
             log=print) -> dict:
    """Set up, warm up, measure ``seconds``, check; returns the result line
    (a dict).  ``wrap`` puts another system in the program's place (the
    control)."""
    import jax

    from bench import compile_meter, generator
    from bench import trace as trace_mod

    seed = int(seed) % (1 << 64)
    meter = compile_meter.meter()
    system = cell.config_module.build(cell.cfg, cell.mix.traffic, seed)
    log(f"data {system.describe()}")
    if wrap is not None:
        system = wrap(system)
    warm_failed = cell.mix.warm(system, seed)
    setup_compile = meter.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"setup setup_s={setup_s:.6f} items={len(system.items)} "
        f"warmup_failed={warm_failed} "
        f"compiles={setup_compile['backend_compiles']} "
        f"compile_s={setup_compile['compile_s']:.3f} "
        f"cache_hits={setup_compile['cache_hits']}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        reqs, t0 = cell.mix.run(system, seed, seconds)
        done = [r.done for r in reqs if r.done is not None]
        t_close = max(done) if done else time.perf_counter()
    reduced = None
    if trace_dir:
        jax.profiler.stop_trace()
        try:
            reduced = trace_mod.reduce(trace_mod.load(
                trace_mod.find(trace_dir), generator.SPANS))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace window_s={reduced['window_s']:.6f} "
            f"busy_s={reduced['busy_s']:.6f} "
            f"dropped_s={reduced['dropped_s']:.6f} "
            f"line_busy_s={json.dumps(reduced['line_busy_s'])}")
    window_compile = compile_meter.delta(meter.snapshot(), setup_compile)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    kind = devices[0].device_kind
    run = Run(system=system, requests=reqs, t0=t0, setup_s=setup_s,
              setup_compile=setup_compile, trace=reduced, device_kind=kind)
    chosen = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in chosen:
        v = cell.readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"window requests={len(reqs)} completed={len(done)} "
        f"window_s={t_close - t0:.6f} "
        f"window_compiles={window_compile['backend_compiles']} "
        f"window_compile_s={window_compile['compile_s']:.6f}")
    log("request_s " + " ".join(f"{r.item}:{r.done - r.due:.4f}"
                                  for r in reqs[:200] if r.done is not None))
    errors = [r.error for r in reqs if r.error is not None]
    if errors:
        log(f"errors count={len(errors)} first={errors[0]!r}")
    to_host(reqs, devices)
    checks = compare(system, reqs)
    result = {"correct": all(passes(checks[k], lim)
                             for k, lim in CHECK_LIMITS.items()),
              "attempted": len(reqs), "failed": checks["missing"],
              "metrics": metrics,
              "device": {"platform": devices[0].platform, "kind": kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(mem_peak)}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": checks[k], "limit": lim[0], "is": lim[1]}
                        for k, lim in CHECK_LIMITS.items()}
    return result
