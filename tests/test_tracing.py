"""Host spans of the decode plan and named scopes on the decode programs.

A ``decompress_many(..., device_out=True)`` records, on the calling thread,
``codag.plan.build`` -> ``codag.plan.stage`` -> one ``codag.plan.dispatch``
per group -> ``codag.api.combine``, all with the plan's ``plan`` id; the
lowered decode program names its stages ``codag.decode.<codec>``,
``codag.phase1``, ``codag.phase2`` and ``codag.epilogue``.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import api, registry, transfers
from repro.core import plan as plan_mod
from repro.core.engine import CodagEngine, EngineConfig

ENGINE = CodagEngine(EngineConfig())
CALL = "test.call"
# codecs whose decode needs no sequential parse: every lane decodes at once
NO_PHASE1 = {"bitpack", "huffman"}


def _columns():
    """Two int64 columns (plane-decomposed: four width-4 blobs) and one
    byte column (one width-1 blob): two groups under one codec."""
    rng = np.random.default_rng(5)
    runs = np.repeat(rng.integers(0, 1 << 40, 60), rng.integers(1, 90, 60))
    a = runs.astype(np.int64)
    b = (runs[::-1] * 3).astype(np.int64)
    flag = np.repeat(np.frombuffer(b"RAN", np.uint8), 700)
    return [a, b, flag]


def _traced(fn, tmp_path):
    """Run ``fn`` under the profiler, inside a ``test.call`` span; returns
    its result and the host lines as ``[[(name, start_ns, stats)]]``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(CALL):
            out = jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, dict(e.stats))
                              for e in line.events
                              if e.name == CALL or e.name.startswith("codag.")])
    return out, lines


def _codag_on_calling_thread(lines):
    """The ``codag.*`` spans of the line that holds ``test.call``, in
    order; asserts no other line holds any."""
    caller, = [ln for ln in lines if any(n == CALL for n, _, _ in ln)]
    others = [n for ln in lines if ln is not caller for n, _, _ in ln
              if n.startswith("codag.")]
    assert others == []
    return sorted((e for e in caller if e[0].startswith("codag.")),
                  key=lambda e: e[1])


def test_spans_of_one_device_call(tmp_path):
    cols = _columns()
    cas = api.compress_many(cols, "rle_v1", chunk_bytes=4096)
    with jax.enable_x64(True), transfers.count_host_transfers() as moved:
        outs, lines = _traced(
            lambda: api.decompress_many(cas, ENGINE, device_out=True),
            tmp_path)
        for got, want in zip(outs, cols):
            assert np.array_equal(np.asarray(got), want)
    spans = _codag_on_calling_thread(lines)
    assert [n for n, _, _ in spans] == [
        "codag.plan.build", "codag.plan.stage", "codag.plan.dispatch",
        "codag.plan.dispatch", "codag.api.combine"]
    ids = {stats["plan"] for _, _, stats in spans}
    assert len(ids) == 1
    build, stage, d0, d1, combine = (s for _, _, s in spans)
    assert build["groups"] == 2 and build["blobs"] == 5
    assert build["chunks"] == d0["chunks"] + d1["chunks"]
    assert stage["bytes"] == moved["h2d_bytes"] > 0
    assert {(d0["codec"], d0["width"]), (d1["codec"], d1["width"])} == {
        ("rle_v1", 4), ("rle_v1", 1)}
    assert combine["arrays"] == 3


def test_staged_plan_stages_nothing_again(tmp_path):
    blobs = [b for ca in api.compress_many(_columns(), "rle_v1",
                                           chunk_bytes=4096)
             for b in ca.blobs]
    plan = plan_mod.DecodePlan.build(blobs)
    with jax.enable_x64(True):
        plan.execute_device(ENGINE)
        with transfers.count_host_transfers() as moved:
            _, lines = _traced(lambda: plan.execute_device(ENGINE), tmp_path)
    assert moved["h2d_bytes"] == 0
    spans = _codag_on_calling_thread(lines)
    assert [n for n, _, _ in spans] == [
        "codag.plan.stage", "codag.plan.dispatch", "codag.plan.dispatch"]
    assert spans[0][2] == {"plan": plan.plan_id, "bytes": 0}


@pytest.mark.parametrize("sharded", [False, True])
def test_stage_bytes_are_what_staging_uploaded(tmp_path, sharded):
    from jax.sharding import Mesh
    blobs = [b for ca in api.compress_many(_columns(), "rle_v1",
                                           chunk_bytes=4096)
             for b in ca.blobs]
    plan = plan_mod.DecodePlan.build(blobs)
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def stage():
        if sharded:
            plan.stage_sharded(mesh, "x")
        else:
            plan.stage()

    with transfers.count_host_transfers() as moved:
        _, lines = _traced(stage, tmp_path)
    (name, _, stats), = _codag_on_calling_thread(lines)
    assert name == "codag.plan.stage"
    assert stats == {"plan": plan.plan_id, "bytes": moved["h2d_bytes"]}
    assert moved["h2d_bytes"] > 0


def test_plans_get_their_own_ids():
    blobs = api.compress(np.arange(100, dtype=np.uint32), "rle_v1").blobs
    a, b = (plan_mod.DecodePlan.build(blobs) for _ in range(2))
    assert a.plan_id != b.plan_id


def _lowered_decode_text(codec: str, epilogue=None) -> str:
    """``as_text(debug_info=True)`` of the jitted decode program the device
    executor runs for one blob of ``codec``'s demo data."""
    from repro.core import tuning
    c = registry.get(codec)
    arr = c.demo_data(4096, np.random.default_rng(0))
    blob = api.compress(arr, codec, chunk_bytes=4096).blobs[0]
    plan = plan_mod.DecodePlan.build([blob]).stage()
    g = plan.groups[0]
    name, width, chunk_elems, bits = g.key
    lowered = plan_mod._decode_scatter_fn().lower(
        plan._staged[None][0], list(plan._staged_scatter[None][0]),
        cfg=ENGINE.config, codec=name, width=width, chunk_elems=chunk_elems,
        bits=bits, epilogue=epilogue,
        meta=plan._blob_meta(g, epilogue is not None, None),
        tune=tuning.kernel_tune(name, width, ENGINE.config.tune))
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("codec", registry.names())
def test_decode_program_names_its_stages(codec):
    text = _lowered_decode_text(codec)
    for scope in (f"codag.decode.{codec}", "codag.phase2"):
        assert scope in text, scope
    assert ("codag.phase1" in text) == (codec not in NO_PHASE1)


def test_epilogue_names_its_scope():
    from repro.kernels.harness import Epilogue
    text = _lowered_decode_text("rle_v1", Epilogue(out_dtype="float32"))
    assert "codag.epilogue" in text
