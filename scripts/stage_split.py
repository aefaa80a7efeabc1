"""Split a scan's host call and device time by the program's own stages.

    python scripts/stage_split.py --workload orc_scan.rle --seed 3000000017 \\
        [--queries 8] [--phases untraced,traced,...] [--out FILE.json]

Runs one cell of ``BENCHMARK.json`` (found by ``bench/harness.py``) on the
TPU this process holds.  Set-up and warm-up are those of ``bench/run.py``;
then each phase issues ``--queries`` queries in a closed loop over the
cell's items, each inside the benchmark's ``query.call`` span:

* ``untraced``, ``untraced_after``: no profiler;
* ``traced``: ``jax.profiler.start_trace`` with its default options, as
  the benchmark's ``--trace 1`` run starts it (Python tracer on);
* ``traced_no_python``: the Python tracer off;
* ``traced_host_only``: the Python tracer and the TPU's device tracer off
  (``tpu_trace_mode`` ``TRACE_ONLY_HOST``).

Every query is timed on the host clock, whatever the phase: the host call
(``call_ms``, until ``decompress_many`` returns), the query (``query_s``,
until its results are ready), and inside the call ``DecodePlan.stage``
(``stage_ms``) with its two parts, the padding of
``CompressedBlob.to_device`` (``pad_ms``) and the host-to-device copies of
``transfers.to_device`` (``h2d_ms``), and the garbage collector's pauses
(``gc_ms``).  A pause is also recorded as a host span ``stage_split.gc``.

Each traced phase's trace is reduced per query to the milliseconds of each
of the program's host spans (``codag.plan.build``, ``codag.plan.stage``,
``codag.plan.dispatch``, ``codag.api.combine``) within the call, with the
stats they carry (the stage's ``bytes``, each dispatch's ``codec``,
``width`` and ``chunks``); ``between_ms``, the part of the call outside
every program span; and ``between_by``, the innermost host events on the
calling thread that cover that part (the Python tracer names functions).
The ``traced`` phase is also reduced on the device, inside the traced
window (``bench/trace.py``: up to the profiler's ``Trace Buffers Dropped``
event): ``busy_s``, ``window_s``, ``scope_busy_s`` (the busy union of the
operations under each ``codag.*`` ``jax.named_scope``), ``scoped_share``
(of busy) and ``idle_by_span`` (device idle seconds under each innermost
program span).

The v5e keeps an operation's scopes in the ``tf_op`` stat of its event
*metadata* (the HLO ``op_name``), beside ``program_id``; ``ProfileData``
exposes an event's own stats only, so the metadata is read from the
serialized ``XSpace`` here.  One HLO name recurs in several programs, so an
operation is keyed by the program id of the ``XLA Modules`` event it ran in
and its name.  The compiler drops ``tf_op`` from ``while`` instructions;
such an operation takes the scopes shared by the scoped operations nested
in it.

Prints one JSON line per phase (medians) and writes every number to
``--out``.  Without a TPU it stops at ``bench.harness.check_devices``.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM = "codag."          # prefix of the program's spans and scopes
CALL = "query.call"         # the benchmark loop's span around one call
GC_SPAN = "stage_split.gc"
PHASES = ("untraced", "traced", "traced_no_python", "traced_host_only",
          "untraced_after")
_SCOPE = re.compile(r"codag\.[A-Za-z0-9_.\-]+")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# --------------------------------------------------------------------------
# host-clock timers around the stage and its parts
# --------------------------------------------------------------------------


class Timers:
    """Per-query host-clock milliseconds of wrapped functions and of the
    garbage collector's pauses."""

    def __init__(self):
        self.ms: dict = {}
        self._gc = None

    def wrap(self, owner, attr: str, key: str) -> None:
        fn = getattr(owner, attr)

        def timed(*args, **kwargs):
            t = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                self.ms[key] = (self.ms.get(key, 0.0)
                                + 1e3 * (time.perf_counter() - t))
        setattr(owner, attr, timed)

    def on_gc(self, phase: str, info: dict) -> None:
        import jax
        if phase == "start":
            span = jax.profiler.TraceAnnotation(GC_SPAN)
            span.__enter__()
            self._gc = (time.perf_counter(), span)
        elif self._gc is not None:
            t, span = self._gc
            span.__exit__(None, None, None)
            self._gc = None
            self.ms["gc_ms"] = (self.ms.get("gc_ms", 0.0)
                                + 1e3 * (time.perf_counter() - t))

    def take(self) -> dict:
        out, self.ms = self.ms, {}
        return out


def install_timers() -> Timers:
    from repro.core import format as fmt
    from repro.core import plan as plan_mod
    from repro.core import transfers
    timers = Timers()
    timers.wrap(plan_mod.DecodePlan, "stage", "stage_ms")
    timers.wrap(fmt.CompressedBlob, "to_device", "pad_ms")
    timers.wrap(transfers, "to_device", "h2d_ms")
    gc.callbacks.append(timers.on_gc)
    return timers


# --------------------------------------------------------------------------
# the phases
# --------------------------------------------------------------------------


def profile_options(phase: str):
    import jax
    if phase == "traced":
        return None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    if phase == "traced_host_only":
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_HOST"}
    return opts


def run_phase(cell, system, seed: int, n: int, timers: Timers) -> list:
    """``n`` queries in a closed loop; one dict of host-clock times each."""
    import jax

    from bench import trace as trace_mod
    from bench.generator import span
    items = cell.mix.keys(len(system.items), n, seed)
    rows = []
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        for item in items:
            timers.take()
            t0 = time.perf_counter()
            with span(CALL):
                out = system.call(item, True)
            t1 = time.perf_counter()
            jax.block_until_ready(out)
            t2 = time.perf_counter()
            row = {"item": int(item), "call_ms": 1e3 * (t1 - t0),
                   "query_s": t2 - t0}
            row.update(timers.take())
            rows.append(row)
            del out
    return rows


# --------------------------------------------------------------------------
# reading a trace
# --------------------------------------------------------------------------


def _varint(b, i: int) -> tuple:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        if c < 0x80:
            return r, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one protobuf message: ints for
    varints, byte slices for length-delimited and fixed-width fields."""
    i, end = 0, len(b)
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def op_scopes(buf: bytes, device_prefix: str) -> dict:
    """``{device plane: {(program id, event name): (codag scope, ...)}}``
    from the serialized ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``:
    ``XSpace.planes`` 1; ``XPlane.name`` 2, ``.event_metadata`` 4,
    ``.stat_metadata`` 5, maps with key 1 and value 2;
    ``XEventMetadata.name`` 2, ``.stats`` 5; ``XStatMetadata.id`` 1,
    ``.name`` 2; ``XStat.metadata_id`` 1, ``.uint64_value`` 3,
    ``.int64_value`` 4, ``.str_value`` 5, ``.ref_value`` 7).  The lines,
    which hold the events, are skipped unread."""
    out = {}
    for f, plane in _fields(memoryview(buf)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
                if not name.startswith(device_prefix):
                    break
            elif pf == 4:
                metas.extend(v2 for f2, v2 in _fields(v) if f2 == 2)
            elif pf == 5:
                for sm in (v2 for f2, v2 in _fields(v) if f2 == 2):
                    d = dict(_fields(sm))
                    stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        if not name.startswith(device_prefix):
            continue
        ids = {n: k for k, n in stat_names.items()}
        scopes = {}
        for m in metas:
            ev_name, stats = "", {}
            for mf, v in _fields(m):
                if mf == 2:
                    ev_name = bytes(v).decode()
                elif mf == 5:
                    d = dict(_fields(v))
                    for vf in (5, 7, 3, 4):
                        if vf in d:
                            val = d[vf]
                            stats[d.get(1, 0)] = (
                                bytes(val).decode() if vf == 5 else
                                stat_names.get(val, "") if vf == 7 else val)
                            break
            found = tuple(dict.fromkeys(_SCOPE.findall(
                str(stats.get(ids.get("tf_op"), "")))))
            if found:
                scopes[(stats.get(ids.get("program_id")), ev_name)] = found
        out[name] = scopes
    return out


def _union(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """Merged ``(starts, ends)`` of the given intervals."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > e[:-1]
    return s[first], e[np.flatnonzero(np.r_[first[1:], True])]


def _scope_codes(ops: tuple, modules: tuple, scope_of: dict) -> tuple:
    """``(distinct scope tuples, index per operation)`` of one chip's
    ``XLA Ops``; a scopeless operation takes the scopes shared by the
    scoped operations nested in it."""
    raw, starts, ends = ops
    m_names, m_starts, m_ends = modules
    pids = [int(m.group(1)) if m else None
            for m in map(_PROGRAM_ID.search, m_names)]
    k = np.searchsorted(m_starts, starts, side="right") - 1
    inside = (k >= 0) & (starts < m_ends[np.maximum(k, 0)])
    distinct: dict = {(): 0}
    cache: dict = {}
    codes = np.zeros(len(raw), np.int32)
    for i, (n, m, ok) in enumerate(zip(raw, k.tolist(), inside.tolist())):
        key = (pids[m] if ok else None, n)
        c = cache.get(key)
        if c is None:
            c = cache[key] = distinct.setdefault(scope_of.get(key, ()),
                                                 len(distinct))
        codes[i] = c
    names = list(distinct)
    order = np.argsort(starts, kind="stable")
    s_sorted = starts[order]
    for i in np.flatnonzero(codes == 0):
        lo = np.searchsorted(s_sorted, starts[i], side="left")
        hi = np.searchsorted(s_sorted, ends[i], side="left")
        if hi - lo < 2:
            continue
        nested = order[lo:hi]
        nested = nested[(ends[nested] <= ends[i]) & (nested != i)]
        held = set(np.unique(codes[nested]).tolist()) - {0}
        if held:
            shared = set.intersection(*(set(names[c]) for c in held))
            sc = tuple(n for n in names[min(held)] if n in shared)
            codes[i] = distinct.setdefault(sc, len(distinct))
            names = list(distinct)
    return names, codes


def load(path: str, with_device: bool) -> dict:
    """The calling thread's host events inside each ``query.call`` and, with
    ``with_device``, chip 0's ``XLA Ops`` (with their scopes), ``XLA
    Modules`` and where the trace drops events; times in ns."""
    from jax.profiler import ProfileData

    from bench import trace as trace_mod
    with open(path, "rb") as f:
        buf = f.read()
    pd = ProfileData.from_serialized_xspace(buf)
    host, window, device = None, None, None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           e) for e in line.events]
                calls = [(s, e) for n, s, e, _ in events if n == CALL]
                window = window or next(((s, e) for n, s, e, _ in events
                                         if n == trace_mod.WINDOW), None)
                if calls and host is None:
                    host = {"calls": sorted(calls), "events": [
                        (n, s, e, dict(ev.stats) if n.startswith(PROGRAM)
                         else {}) for n, s, e, ev in events
                        if n not in (CALL, trace_mod.WINDOW)]}
        elif (with_device and device is None
              and plane.name.startswith(trace_mod.DEVICE_PREFIX)):
            lines = {line.name: line for line in plane.lines}
            if trace_mod.OPS_LINE not in lines:
                continue
            device = {"plane": plane.name, "dropped": [
                e.start_ns for ln in plane.lines for e in ln.events
                if e.name == trace_mod.DROPPED]}
            for key, line in (("ops", trace_mod.OPS_LINE),
                              ("modules", "XLA Modules")):
                names, starts, ends = [], [], []
                for e in (lines[line].events if line in lines else ()):
                    names.append(e.name)
                    starts.append(e.start_ns)
                    ends.append(e.start_ns + e.duration_ns)
                device[key] = (names, np.asarray(starts, np.float64),
                               np.asarray(ends, np.float64))
    if host is None or window is None:
        raise RuntimeError(f"no {CALL!r} spans in {path}")
    out = {"host": host, "window": window, "device": device}
    if device is not None:
        device["scopes"] = op_scopes(buf, trace_mod.DEVICE_PREFIX).get(
            device["plane"], {})
    return out


def host_split(trace: dict) -> list:
    """Per call: ms per program span, their stats, ``between_ms`` and the
    innermost events covering the time between spans."""
    calls = trace["host"]["calls"]
    starts = np.asarray([c[0] for c in calls], np.float64)
    per = [{"spans_ms": {}, "stats": [], "inner": [], "others": []}
           for _ in calls]
    for n, s, e, stats in trace["host"]["events"]:
        k = int(np.searchsorted(starts, s, side="right")) - 1
        if k < 0 or e > calls[k][1]:
            continue
        q = per[k]
        if n.startswith(PROGRAM):
            q["spans_ms"][n] = q["spans_ms"].get(n, 0.0) + (e - s) / 1e6
            q["stats"].append({"span": n, **stats})
            q["inner"].append((s, e))
        else:
            q["others"].append((n, s, e))
    out = []
    for (c0, c1), q in zip(calls, per):
        cs, ce = _union(np.asarray([a for a, _ in q["inner"]], np.float64),
                        np.asarray([b for _, b in q["inner"]], np.float64))
        gaps, prev = [], c0
        for a, b in list(zip(cs.tolist(), ce.tolist())) + [(c1, c1)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        by: dict = {}
        for g0, g1 in gaps:
            # the shortest event holding most of this stretch
            cover = [(e - s, n, min(e, g1) - max(s, g0))
                     for n, s, e in q["others"] if s < g1 and e > g0]
            cover = [c for c in cover if 2 * c[2] >= g1 - g0]
            name = min(cover)[1] if cover else "none"
            by[name] = by.get(name, 0.0) + (g1 - g0) / 1e6
        out.append({"call_ms": (c1 - c0) / 1e6, "spans_ms": q["spans_ms"],
                    "between_ms": sum((b - a) for a, b in gaps) / 1e6,
                    "between_by": dict(sorted(by.items(),
                                              key=lambda kv: -kv[1])[:4]),
                    "stats": q["stats"]})
    return out


def device_split(trace: dict) -> dict:
    """Busy, window, busy per ``codag.*`` scope and idle per innermost
    program span, inside the traced window."""
    dev = trace["device"]
    w0, w_end = trace["window"]
    w1 = min([w_end] + dev["dropped"])
    ops, mods = dev["ops"], dev["modules"]
    names, codes = _scope_codes(ops, mods, dev["scopes"])
    s = np.clip(ops[1], w0, w1)
    e = np.clip(ops[2], w0, w1)
    keep = e > s
    bs, be = _union(np.concatenate([s[keep], np.clip(mods[1], w0, w1)]),
                    np.concatenate([e[keep], np.clip(mods[2], w0, w1)]))
    busy = float(np.sum(be - bs))
    scope_busy = {}
    for scope in sorted({n for sc in names for n in sc}):
        under = np.asarray([scope in sc for sc in names], bool)[codes] & keep
        us, ue = _union(s[under], e[under])
        scope_busy[scope] = float(np.sum(ue - us)) / 1e9
    scoped = (codes != 0) & keep
    ss, se = _union(s[scoped], e[scoped])
    # device idle under the innermost program span open at each instant
    spans = sorted((max(a, w0), min(b, w1), n)
                   for n, a, b, _ in trace["host"]["events"]
                   if n.startswith(PROGRAM) and a < w1 and b > w0)
    cum = np.concatenate([[0.0], np.cumsum(be - bs)])

    def busy_until(x: float) -> float:
        k = int(np.searchsorted(bs, x, side="right"))
        return cum[k - 1] + min(x, be[k - 1]) - bs[k - 1] if k else 0.0

    idle: dict = {}
    cuts = sorted({x for a, b, _ in spans for x in (a, b)})
    for a, b in zip(cuts, cuts[1:]):
        open_ = [sp for sp in spans if sp[0] <= a and sp[1] >= b]
        if open_:
            inner = max(open_, key=lambda sp: (sp[0], -sp[1]))[2]
            gap = (b - a) - (busy_until(b) - busy_until(a))
            idle[inner] = idle.get(inner, 0.0) + gap / 1e9
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy / 1e9,
            "dropped_s": (w_end - w1) / 1e9,
            "scope_busy_s": scope_busy,
            "scoped_share": float(np.sum(se - ss)) / busy if busy else None,
            "idle_by_span": idle}


# --------------------------------------------------------------------------


def _medians(rows: list) -> dict:
    keys = sorted({k for r in rows for k in r if k.endswith(("_ms", "_s"))})
    return {k: statistics.median(r.get(k, 0.0) for r in rows) for k in keys}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--phases", default=",".join(PHASES))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    phases = args.phases.split(",")
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}; known: {PHASES}")

    t_start = time.perf_counter()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax

    from bench import harness
    cell = harness.resolve(args.workload)
    harness.check_devices(cell.chips)
    harness.enable_compile_cache()
    seed = args.seed % (1 << 64)
    system = cell.config_module.build(cell.cfg, cell.mix.traffic, seed)
    cell.mix.warm(system, seed)
    print(json.dumps({"setup_s": time.perf_counter() - t_start,
                      "items": len(system.items)}), flush=True)
    timers = install_timers()
    result = {"workload": args.workload, "seed": args.seed,
              "device_kind": jax.devices()[0].device_kind, "phases": {}}
    for i, phase in enumerate(phases):
        trace_dir = (tempfile.mkdtemp(prefix="stage_split_")
                     if phase.startswith("traced") else None)
        if trace_dir:
            jax.profiler.start_trace(
                trace_dir, profiler_options=profile_options(phase))
        try:
            rows = run_phase(cell, system, seed + i, args.queries, timers)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        got = {"queries": rows, "median": _medians(rows)}
        if trace_dir:
            t = time.perf_counter()
            path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True)
            trace = load(path, with_device=phase == "traced")
            shutil.rmtree(trace_dir, ignore_errors=True)
            split = host_split(trace)
            got["host"] = split
            got["median"].update({
                f"span.{n}": statistics.median(
                    q["spans_ms"].get(n, 0.0) for q in split)
                for n in sorted({n for q in split for n in q["spans_ms"]})})
            got["median"]["between_ms"] = statistics.median(
                q["between_ms"] for q in split)
            if trace["device"] is not None:
                got["device"] = device_split(trace)
            got["reduce_s"] = time.perf_counter() - t
        result["phases"][phase] = got
        line = {"phase": phase, **got["median"]}
        if "device" in got:
            line["device"] = got["device"]
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
