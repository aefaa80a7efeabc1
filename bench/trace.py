"""Profiler trace of the measured window, and its reduction to numbers.

The run wraps its window in a host span named ``WINDOW``; the reduction
reads every line of every TPU plane (``DEVICE_PREFIX``) and the benchmark's
own host spans (``generator.SPANS``), all on the profiler's one clock, and
gives:

* ``window_s``: the length of the traced window: the ``WINDOW`` span, up
  to where the device trace ends.  The profiler keeps a bounded number of
  device events (about 6.3 million on the v5e) and marks the rest of the
  span with one ``DROPPED`` event; from there the chip's work is not
  recorded, so the traced window closes where the first such event starts;
* ``busy_s``: per chip, the union of the intervals of the ``BUSY_LINES``
  (the programs that ran, ``XLA Modules``, and their operations,
  ``XLA Ops``), inside the traced window; averaged over the chips;
* ``idle_pct``: ``100 * (1 - busy_s / window_s)``;
* ``device_ops``: the operations that took most device time (summed by
  name, inside the window, averaged over the chips);
* ``idle_gaps``: the longest stretches in which chip 0 was not busy, each
  named by the host span that covered most of it (``none`` where no span
  was open);
* ``line_busy_s``: chip 0's busy seconds inside the traced window by each
  of its lines, so that the lines can be compared;
* ``dropped_s``: how much of the ``WINDOW`` span the trace did not record.
"""
from __future__ import annotations

import functools
import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
BUSY_LINES = ("XLA Modules", OPS_LINE)
DROPPED = "Trace Buffers Dropped"
TOP = 10


_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


@functools.lru_cache(maxsize=1 << 16)
def op_name(hlo: str) -> str:
    """``%copy.18 = copy s32[96,65540]`` from an ``XLA Ops`` event's HLO
    text: the instruction, its opcode and its result shape."""
    lhs, _, rhs = hlo.partition(" = ")
    m = _OPCODE.search(rhs)
    if not m:
        return lhs
    shape = rhs.split("{", 1)[0] if not rhs.startswith("(") else "(tuple)"
    return f"{lhs} = {m.group(1)} {shape}"


def _intervals(events) -> tuple:
    names, starts, ends = [], [], []
    for e in events:
        names.append(op_name(e.name))
        starts.append(e.start_ns)
        ends.append(e.start_ns + e.duration_ns)
    return names, np.asarray(starts, np.float64), np.asarray(ends, np.float64)


def load(path: str, spans: tuple) -> dict:
    """``from_profile`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), spans)


def from_profile(pd, spans: tuple) -> dict:
    """``{"devices": [{line name: (names, starts, ends)}], "spans": [(name,
    s, e)]}`` from a ``jax.profiler.ProfileData``; times in ns."""
    devices, host = [], []
    wanted = set(spans) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append({line.name: _intervals(line.events)
                            for line in plane.lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": host}


def find(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


def union(starts: np.ndarray, ends: np.ndarray) -> list:
    """Merged, sorted ``[(start, end)]`` of the given intervals."""
    out: list = []
    for s, e in sorted(zip(starts.tolist(), ends.tolist())):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(starts, ends, w0, w1) -> tuple:
    s, e = np.clip(starts, w0, w1), np.clip(ends, w0, w1)
    keep = e > s
    return s[keep], e[keep], keep


def reduce(trace: dict) -> dict:
    windows = [(s, e) for n, s, e in trace["spans"] if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found "
                           f"{len(windows)}")
    w0, span_end = windows[0]
    w1 = min([span_end] + [s for d in trace["devices"]
                           for names, starts, _ in d.values()
                           for n, s in zip(names, starts.tolist())
                           if n == DROPPED])
    devices = [d for d in trace["devices"] if any(n in d for n in BUSY_LINES)]
    if not devices:
        raise RuntimeError(f"no {DEVICE_PREFIX}* plane with a line of "
                           f"{BUSY_LINES} in the trace")
    busy, per_op, chip0 = [], {}, None
    for lines in devices:
        starts, ends = [], []
        for name in BUSY_LINES:
            if name in lines:
                s, e, _ = _clip(lines[name][1], lines[name][2], w0, w1)
                starts.append(s)
                ends.append(e)
        merged = union(np.concatenate(starts), np.concatenate(ends))
        busy.append(sum(b - a for a, b in merged))
        if OPS_LINE in lines:
            names, s, e = lines[OPS_LINE]
            s, e, keep = _clip(s, e, w0, w1)
            for n, d in zip(np.asarray(names, object)[keep], e - s):
                per_op[n] = per_op.get(n, 0.0) + float(d)
        if chip0 is None:
            chip0 = merged
    n_dev = len(devices)
    gaps, prev = [], w0
    for a, b in chip0 + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        cover: dict = {}
        for n, s, e in trace["spans"]:
            if n != WINDOW and s < b and e > a:
                cover[n] = cover.get(n, 0.0) + min(b, e) - max(a, s)
        named.append([max(cover, key=cover.get) if cover else "none",
                      (b - a) / 1e9])
    line_busy = {}
    for name, (_, s, e) in devices[0].items():
        s, e, _ = _clip(s, e, w0, w1)
        line_busy[name] = sum(b - a for a, b in union(s, e)) / 1e9
    window_s = (w1 - w0) / 1e9
    busy_s = float(np.mean(busy)) / 1e9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": [[n, d / n_dev / 1e9] for n, d in top],
            "idle_gaps": named, "line_busy_s": line_busy,
            "dropped_s": (span_end - w1) / 1e9}
