"""CPU rehearsal of the benchmark: every cell at a tiny size, the control,
the faults each cell can have, cells added from new files alone, the trace
reduction on a recorded v5e trace, the peaks table and the data.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The cells run as functions (``harness.run_cell``) with their configuration
cut down here, past the harness's look for a chip.
"""
import json
import os
import shutil
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import control, generator, harness, peaks  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
EXCERPT = os.path.join(ROOT, "bench", "tests", "data", "v5e_excerpt.xspace.txt")
SEED = 2 ** 31 + 7          # more than 32 signed bits hold


def tiny(cell):
    """Cut a cell to a size the CPU runs in seconds; widths stay."""
    cell.cfg["rows_per_stripe"] = {k: 32768
                                   for k in cell.cfg["rows_per_stripe"]}
    cell.cfg["chunk_bytes"] = 16384
    return cell


def run(cell, **kw):
    import jax
    return harness.run_cell(cell, SEED, 2.0, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices(), log=lambda s: None, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct(workload):
    cell = tiny(harness.resolve(workload))
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    res = run(tiny(harness.resolve(workload)), wrap=control.Control)
    assert not res["correct"]
    assert res["checks"]["mismatched"]["value"] >= 1


def _alter_answers(monkeypatch):
    """A wrong value where the decode's answer is produced."""
    from repro.core import plan
    execute = plan.DecodePlan.execute_device

    def bad_execute(self, *a, **k):
        outs = execute(self, *a, **k)
        return [o.at[0].add(1) for o in outs]

    monkeypatch.setattr(plan.DecodePlan, "execute_device", bad_execute)


def _drop_half(monkeypatch):
    """Half of every batch left out: the call answers for half of its
    columns."""
    from repro.core import api
    many = api.decompress_many

    def half_many(cas, *a, **k):
        return many(list(cas)[: max(1, len(cas) // 2)], *a, **k)

    monkeypatch.setattr(api, "decompress_many", half_many)


@pytest.mark.parametrize("fault", [_alter_answers, _drop_half],
                         ids=["answer_altered", "half_left_out"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    cell = tiny(harness.resolve(workload))
    fault(monkeypatch)
    res = run(cell)
    assert not res["correct"], res["checks"]


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def _copy_tree(tmp_path, bench: dict) -> str:
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("part,path", [
    ("traffic", "bench/traffic/no_such.json"),
    ("loop", "bench/loops/no_such.py"),
    ("keys", "bench/keys/no_such.py"),
    ("metric", "bench/metrics/setup_s.py")])
def test_missing_file_fails_by_name(tmp_path, part, path):
    bench = json.loads(json.dumps(BENCH))
    root = _copy_tree(tmp_path, bench)
    mix = os.path.join(root, "bench", "traffic", "rle.json")
    m = json.load(open(mix))
    if part == "traffic":
        bench["workloads"][0]["traffic"] = "no_such"
        (tmp_path / "checkout" / "BENCHMARK.json").write_text(json.dumps(bench))
    elif part == "loop":
        m["loop"] = "no_such"
    elif part == "keys":
        m["keys"] = {"kind": "no_such"}
    else:
        os.remove(os.path.join(root, path))
    json.dump(m, open(mix, "w"))
    workload = next(w["name"] for w in bench["workloads"]
                    if w["traffic"] in ("rle", "no_such"))
    with pytest.raises(harness.CellError, match=path):
        harness.resolve(workload, root=root)


def _add_cell(bench, name, traffic):
    bench["workloads"].append({"name": name, "config": "orc_scan",
                               "traffic": traffic, "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(name)


def test_new_cell_from_entry_alone(tmp_path):
    """A cell added by a new mix file and a new entry; no file edited."""
    bench = json.loads(json.dumps(BENCH))
    _add_cell(bench, "orc_scan.rle_v2", "rle_v2_only")
    root = _copy_tree(tmp_path, bench)
    mix = json.load(open(os.path.join(root, "bench", "traffic", "rle.json")))
    mix["select"]["encodings"] = ["rle_v2"]
    with open(os.path.join(root, "bench", "traffic", "rle_v2_only.json"),
              "w") as f:
        json.dump(mix, f)
    cell = tiny(harness.resolve("orc_scan.rle_v2", root=root))
    res = run(cell)
    assert res["correct"] and "decoded_MiBps" in res["metrics"]


BURSTS = '''"""bursts: a closed loop that issues `burst` queries back to back, then
sleeps `pause_s`."""
import time

from bench.generator import Request


def run(mix, system, seed, seconds):
    import jax
    p = mix.traffic["bursts"]
    reqs, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        for k in mix.keys(len(system.items), p["burst"], seed + len(reqs)):
            r = Request(item=k, device_out=True, due=time.perf_counter())
            r.out = jax.block_until_ready(system.call(k, True))
            r.sent = r.done = time.perf_counter()
            reqs.append(r)
        time.sleep(p["pause_s"])
    return reqs, t0


def warm(mix, system, seed):
    import jax
    for i in range(len(system.items)):
        jax.block_until_ready(system.call(i, True))
    return 0
'''

SHUFFLED = '''"""shuffled: every item once per round, each round in the seed's order."""
import numpy as np


def draw(params, n_items, count, seed):
    rng = np.random.default_rng(seed)
    rounds = -(-count // n_items)
    return np.concatenate([rng.permutation(n_items)
                           for _ in range(rounds)])[:count]
'''


def test_new_loop_and_key_choice_from_new_files_alone(tmp_path):
    """A bursty mix with shuffled keys: a new loop, a new key choice, a new
    mix and a new entry; no file edited."""
    bench = json.loads(json.dumps(BENCH))
    _add_cell(bench, "orc_scan.rle_bursts", "rle_bursts")
    root = _copy_tree(tmp_path, bench)
    (tmp_path / "checkout" / "bench" / "loops" / "bursts.py").write_text(BURSTS)
    (tmp_path / "checkout" / "bench" / "keys" / "shuffled.py").write_text(
        SHUFFLED)
    mix = json.load(open(os.path.join(root, "bench", "traffic", "rle.json")))
    mix.update(loop="bursts", keys={"kind": "shuffled"},
               bursts={"burst": 3, "pause_s": 0.05})
    with open(os.path.join(root, "bench", "traffic", "rle_bursts.json"),
              "w") as f:
        json.dump(mix, f)
    cell = tiny(harness.resolve("orc_scan.rle_bursts", root=root))
    res = run(cell)
    assert res["correct"] and res["attempted"] >= 3
    assert "decoded_MiBps" in res["metrics"]


def test_cycle_keys_same_for_every_seed():
    cell = harness.resolve(CELLS[0])
    assert cell.mix.keys(4, 10, 1) == cell.mix.keys(4, 10, 2) == \
        [0, 1, 2, 3, 0, 1, 2, 3, 0, 1]


def test_seed_orders_the_data_and_keeps_it():
    """Another seed gives every column the same chunks in another order."""
    from bench.configs import orc_table
    for name in orc_table.COLUMNS:
        a = orc_table.column(1, 5, 0, name, 32768, 100, 16384)
        b = orc_table.column(1, 6, 0, name, 32768, 100, 16384)
        again = orc_table.column(1, 5, 0, name, 32768, 100, 16384)
        chunk = orc_table.chunk_elems(a.dtype, 16384)
        assert np.array_equal(a, again)
        assert sorted(map(bytes, a.reshape(-1, chunk))) == \
            sorted(map(bytes, b.reshape(-1, chunk)))


def test_lineitem_follows_the_tpch_rules():
    from bench.configs import orc_table
    t = orc_table.stripe(3, 0, 65536, 100)
    q = t["l_quantity"] // 100
    assert (t["l_quantity"] % 100 == 0).all() and q.min() == 1 and q.max() == 50
    assert t["l_discount"].min() == 0 and t["l_discount"].max() == 10
    assert t["l_tax"].min() == 0 and t["l_tax"].max() == 8
    retail = t["l_extendedprice"] // q
    assert (t["l_extendedprice"] % q == 0).all()
    assert retail.min() >= 90000 and retail.max() <= 90000 + 20000 + 99900
    assert set(np.unique(t["l_returnflag"])) == set(b"RAN")
    assert set(np.unique(t["l_linestatus"])) == set(b"OF")
    ship = t["l_shipdate"]
    assert ship.min() > orc_table.STARTDATE
    assert ship.max() <= orc_table.ENDDATE - 151 + 121
    assert (t["l_linestatus"][ship > orc_table.CURRENTDATE] == ord("O")).all()


# -------------------------------------------------------------------------
# trace reduction
# -------------------------------------------------------------------------


def test_reduction_of_v5e_trace_excerpt():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(open(EXCERPT).read())
    red = trace_mod.reduce(trace_mod.from_profile(pd, generator.SPANS))
    assert red["window_s"] == pytest.approx(0.020)
    assert red["busy_s"] == pytest.approx(0.0158)
    assert red["idle_pct"] == pytest.approx(21.0)
    assert red["line_busy_s"] == pytest.approx(
        {"XLA Modules": 0.0158, "XLA Ops": 0.015602,
         "Async XLA Ops": 1.9763e-05})
    assert red["device_ops"][:2] == [
        ["%broadcast_select_fusion = fusion (tuple)", pytest.approx(0.0097)],
        ["%copy.18 = copy s32[96,65540]", pytest.approx(0.0059)]]
    assert [g[0] for g in red["idle_gaps"]] == [
        "query.call", "query.wait", "query.call"]
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [0.0015, 0.0015, 0.0012])


def _by_hand(lines):
    ms = 1e6
    spans = [(trace_mod.WINDOW, 0, 10 * ms), ("query.wait", 0, 4 * ms),
             ("query.call", 4 * ms, 10 * ms)]
    return trace_mod.reduce({"devices": [{
        k: (names, np.array(s) * ms, np.array(e) * ms)
        for k, (names, s, e) in lines.items()}], "spans": spans})


def test_reduction_by_hand():
    red = _by_hand({"XLA Ops": (["a", "b", "a"], [1, 3, 6], [2, 4.5, 7])})
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.0035)
    assert red["idle_pct"] == pytest.approx(65.0)
    assert red["device_ops"] == [["a", pytest.approx(0.002)],
                                 ["b", pytest.approx(0.0015)]]
    assert red["idle_gaps"] == [["query.call", pytest.approx(0.003)],
                                ["query.call", pytest.approx(0.0015)],
                                ["query.wait", pytest.approx(0.001)],
                                ["query.wait", pytest.approx(0.001)]]


def test_traced_window_ends_where_the_device_trace_drops_events():
    """Past its buffers the profiler records one ``Trace Buffers Dropped``
    event to the end; the traced window closes where it starts."""
    red = _by_hand({"XLA Modules": (["m"], [0.5], [4]),
                    "XLA Ops": (["a", "b"], [0.5, 2], [2, 4]),
                    "XLA TraceMe": ([trace_mod.DROPPED], [4], [10])})
    assert red["window_s"] == pytest.approx(0.004)
    assert red["dropped_s"] == pytest.approx(0.006)
    assert red["busy_s"] == pytest.approx(0.0035)
    assert red["idle_pct"] == pytest.approx(12.5)
    assert red["line_busy_s"]["XLA TraceMe"] == 0
    assert red["idle_gaps"] == [["query.wait", pytest.approx(0.0005)]]


def test_busy_covers_a_program_whose_ops_were_not_recorded():
    """Where a program runs and no operation of it is recorded, its
    interval on the ``XLA Modules`` line still counts as busy."""
    red = _by_hand({"XLA Modules": (["m", "m"], [1, 5], [4.5, 9.5]),
                    "XLA Ops": (["a", "b"], [1, 3], [2, 4.5])})
    assert red["busy_s"] == pytest.approx(0.008)
    assert red["line_busy_s"] == pytest.approx(
        {"XLA Modules": 0.008, "XLA Ops": 0.0025})
    assert red["device_ops"] == [["b", pytest.approx(0.0015)],
                                 ["a", pytest.approx(0.001)]]
    assert red["idle_gaps"] == [["query.wait", pytest.approx(0.001)],
                                ["query.call", pytest.approx(0.0005)],
                                ["query.call", pytest.approx(0.0005)]]


# -------------------------------------------------------------------------
# peaks and byte counts
# -------------------------------------------------------------------------


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_decode_bytes_from_the_cells_data():
    from repro.core import api
    a = np.repeat(np.arange(64, dtype=np.uint32), 512)
    ca = api.compress(a, "rle_v1", chunk_bytes=16384)
    assert peaks.decode_bytes(ca.compressed_bytes, a.nbytes) == \
        ca.compressed_bytes + a.nbytes
    with pytest.raises(ValueError):
        peaks.decode_bytes(-1, 5)


def test_roofline_reader_is_a_share_of_busy_time():
    class Sys:
        def compressed_bytes(self, i):
            return 10 ** 9

        def decoded_bytes(self, i):
            return 638 * 10 ** 6

    # 2 ms of least HBM time; busy half of the traced window, so 4 ms of
    # the 8 ms to the last completion
    req = generator.Request(item=0, device_out=True, due=0.0, done=0.008)
    r = harness.Run(system=Sys(), requests=[req], t0=0.0, setup_s=0.0,
                    setup_compile={},
                    trace={"busy_s": 0.002, "window_s": 0.004,
                           "idle_pct": 50.0},
                    device_kind="TPU v5 lite")
    reader = harness.load_module(os.path.join(
        ROOT, "bench", "metrics", "decode_hbm_roofline.scan.py"), "roof")
    assert reader.read(r) == pytest.approx(50.0)
