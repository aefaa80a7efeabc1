"""``scripts/stage_split.py``'s reading of a trace, on hand-made traces.

The v5e keeps an operation's ``jax.named_scope`` names in the ``tf_op``
stat of its event metadata, beside ``program_id``; the CPU backend writes
neither, so the serialized ``XSpace`` here is encoded by hand in the same
form.
"""
import importlib.util
import os

import numpy as np
import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts",
                     "stage_split.py")
_spec = importlib.util.spec_from_file_location("stage_split", _PATH)
split = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(split)

DEVICE = "/device:TPU:0"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message of ``(field number, int | str | bytes)``."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name: str, ops: dict) -> bytes:
    """An ``XPlane`` whose event metadata gives each op ``{name: (program
    id, tf_op or None)}``."""
    stat_meta = [(5, _msg((1, k), (2, _msg((1, k), (2, n)))))
                 for k, n in ((1, "tf_op"), (2, "program_id"))]
    ev_meta = []
    for i, (op, (pid, tf_op)) in enumerate(ops.items(), start=10):
        stats = [(5, _msg((1, 2), (4, pid)))]
        if tf_op is not None:
            stats.append((5, _msg((1, 1), (5, tf_op))))
        ev_meta.append((4, _msg((1, i), (2, _msg((1, i), (2, op),
                                                 *stats)))))
    return _msg((2, name), *ev_meta, *stat_meta)


OPS = {"%while.1": (7, None),
       "%fusion.2": (7, "jit(decode_scatter)/jit(_decode_impl)/"
                        "codag.decode.rle_v1/vmap(codag.phase1)/while/body/"
                        "add"),
       "%fusion.3": (7, "jit(decode_scatter)/jit(_decode_impl)/"
                        "codag.decode.rle_v1/codag.phase2/cumsum"),
       "%copy.1": (8, "jit(other)/copy")}


def test_op_scopes_reads_tf_op_from_event_metadata():
    buf = _msg((1, _plane("/host:CPU", OPS)), (1, _plane(DEVICE, OPS)))
    scopes = split.op_scopes(buf, "/device:TPU:")
    assert list(scopes) == [DEVICE]
    assert scopes[DEVICE] == {
        (7, "%fusion.2"): ("codag.decode.rle_v1", "codag.phase1"),
        (7, "%fusion.3"): ("codag.decode.rle_v1", "codag.phase2")}


def _device_trace(dropped=()) -> dict:
    buf = _msg((1, _plane(DEVICE, OPS)))
    ops = [("%while.1", 10, 50), ("%fusion.2", 12, 20), ("%fusion.2", 30, 40),
           ("%fusion.3", 60, 80), ("%copy.1", 85, 90)]
    mods = [("jit_decode_scatter(7)", 10, 80), ("jit_other(8)", 85, 90)]

    def lines(evs):
        return ([n for n, _, _ in evs],
                np.asarray([s for _, s, _ in evs], np.float64),
                np.asarray([e for _, _, e in evs], np.float64))
    return {"window": (0, 100),
            "host": {"calls": [(0, 100)], "events": [
                ("codag.plan.stage", 0, 10, {}),
                ("codag.api.combine", 80, 100, {})]},
            "device": {"plane": DEVICE, "ops": lines(ops),
                       "modules": lines(mods), "dropped": list(dropped),
                       "scopes": split.op_scopes(buf, "/device:")[DEVICE]}}


def test_device_split_by_scope_and_span():
    got = split.device_split(_device_trace())
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(75e-9)
    # the scopeless while takes the scopes of the fusions nested in it
    assert got["scope_busy_s"] == pytest.approx({
        "codag.decode.rle_v1": 60e-9, "codag.phase1": 40e-9,
        "codag.phase2": 20e-9})
    assert got["scoped_share"] == pytest.approx(60 / 75)
    assert got["idle_by_span"] == pytest.approx({
        "codag.plan.stage": 10e-9, "codag.api.combine": 15e-9})


def test_device_split_ends_at_dropped_events():
    got = split.device_split(_device_trace(dropped=[70]))
    assert got["window_s"] == pytest.approx(70e-9)
    assert got["dropped_s"] == pytest.approx(30e-9)
    assert got["busy_s"] == pytest.approx(60e-9)
    assert got["scope_busy_s"]["codag.phase2"] == pytest.approx(10e-9)


def test_host_split_names_the_time_between_spans():
    trace = {"host": {"calls": [(0, 100), (200, 260)], "events": [
        ("$api.py:110 decompress_many", 2, 98, {}),
        ("codag.plan.build", 5, 10, {"plan": 1, "groups": 2}),
        ("codag.plan.stage", 10, 30, {"plan": 1, "bytes": 64}),
        ("stage_split.gc", 30, 39, {}),
        ("codag.plan.dispatch", 40, 60, {"plan": 1}),
        ("codag.plan.dispatch", 60, 90, {"plan": 1}),
        ("codag.plan.stage", 210, 250, {"plan": 2, "bytes": 0})]}}
    first, second = split.host_split(trace)
    assert first["call_ms"] == pytest.approx(100e-6)
    assert first["spans_ms"] == pytest.approx({
        "codag.plan.build": 5e-6, "codag.plan.stage": 20e-6,
        "codag.plan.dispatch": 50e-6})
    assert first["between_ms"] == pytest.approx(25e-6)
    assert first["between_by"] == pytest.approx({
        "$api.py:110 decompress_many": 15e-6, "stage_split.gc": 10e-6})
    assert [s["span"] for s in first["stats"]] == [
        "codag.plan.build", "codag.plan.stage", "codag.plan.dispatch",
        "codag.plan.dispatch"]
    assert first["stats"][1] == {"span": "codag.plan.stage", "plan": 1,
                                 "bytes": 64}
    assert second["spans_ms"] == pytest.approx({"codag.plan.stage": 40e-6})
    assert second["between_by"] == pytest.approx({"none": 20e-6})
