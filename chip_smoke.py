"""Smoke run of the decode path on a TPU, through the entry points users call.

    python chip_smoke.py [--seed N]          # one chip: scan, service, restore
    python chip_smoke.py --four-chips        # four chips: mesh decode + psum

The script first asks JAX for its devices and exits non-zero, printing no
result, unless they are TPUs: there is no CPU fallback.  It then enables
the persistent compile cache (``tuning.enable_compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``).
Every phase compares its results with the original arrays on the host and
raises on the first mismatch.  Data is generated from ``--seed`` in the
process.  The last line of a successful run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases (one chip):

* scan — the seven Table IV columns of ``benchmarks.datasets.build``,
  compressed with each of the 7 registered codecs and decoded with one
  ``api.decompress_many(cas, device_out=True)`` per codec on the default
  engine (the XLA decode), bit-exact including the uint64 columns MC0 and
  TC2 under ``jax.enable_x64``.  Then the same blobs, as a staged
  ``DecodePlan``, re-execute under ``transfers.no_host_transfers()``
  without compiling again: that rerun gives the warm seconds.
* service — one ``DecompressionService`` (cache off, so every window
  decodes) takes ~500 ``submit`` calls from 8 producer threads over one
  small blob per codec, half ``device_out=True``; every future is
  bit-exact.
* restore — ``checkpoint.save(codec="rle_v2")`` of the Mortgage and
  Twitter columns, then a streaming ``checkpoint.restore(device_out=True,
  store=filesystem_store(...))``; every leaf is equal and lives on the TPU.

Phase ``--four-chips`` (and nothing else): the scan columns under dbp
decoded over ``make_decode_mesh(4)`` with ``out_shardings``, bit-exact
against the one-device decode and each result spread over 4 devices; then
``collectives.compressed_psum`` on a 4-member mesh against a plain f32
``psum``, within the int8 bound of ``tests/test_distributed.py``.

Sizes and cuts (scale only; column shapes, distributions and the default
chunk geometry are the library's):

* The run, compilation included, has to end within 20 minutes on a cold
  compile cache.  Compiling costs about 30-40 s per codec on the v5e (one
  program per fused group).  The XLA decode's time on the chip grows with
  the decoded bytes, at about 0.05 s/MiB (huffman), 0.12 (dbp), 0.33
  (rle_v1), 0.53 (rle_v2), 1.2 (lzss) and 5.3 s/MiB (tdeflate), measured
  on a TPU v5e at the default 128 KiB chunks.
* Column size per codec (``SCAN_MB``): 5 MiB per column, 35 MiB decoded
  per codec; bitpack 32 MiB per column (224 MiB, under a 256 MiB cap per
  fused dispatch, which bounds the decode's temporary memory).
* tdeflate is cut to 1 MiB per column (7 MiB): at 5.3 s/MiB and two
  decodes, 35 MiB would take about six minutes of the twenty.
* Host encoding (tdeflate about 2.7 s/MiB in NumPy) runs in a pool of
  CPU-only worker processes.  The columns are built once at the largest
  size and cut to each codec's size by prefix.
* bitpack gets its registry ``demo_data`` (seven low-range uint32 arrays):
  it stores every value at one bit width, and on MC3, CD2 and TC2 it saves
  nothing.
* service: ~500 submits over one ``SERVICE_KIB`` (256 KiB) blob per codec.
  A window decodes every distinct blob it holds, so the blob's size times
  the window count is decoded; a whole scan column per request would put
  tdeflate alone at minutes.
* restore: 16 MiB per leaf (48 MiB).
* four chips: 2 MiB columns under dbp (it encodes and decodes fast), and a
  4 x 2^20 f32 psum; four chips cost four times as much per second.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import compile_meter  # noqa: E402

MiB = 1 << 20
SCAN_MB = {"rle_v1": 5, "rle_v2": 5, "tdeflate": 1, "dbp": 5, "lzss": 5,
           "huffman": 5, "bitpack": 32}
DEMO_CODECS = ("bitpack",)      # no natural Table IV column: registry data
SERVICE_KIB = 256
SERVICE_SUBMITS = 504           # 8 producers x 63
SERVICE_PRODUCERS = 8
RESTORE_CODEC = "rle_v2"
RESTORE_COLUMNS = ("MC0", "MC3", "TC2")
RESTORE_MB = 16
FOUR_CHIP_CODEC = "dbp"
FOUR_CHIP_MB = 2
PSUM_ELEMS = 1 << 20


def log(phase: str, /, **kv) -> None:
    print(phase + " " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _same(got, want) -> bool:
    """Bit-exact equality (dtype, shape and bytes) against the original."""
    import numpy as np
    got = np.asarray(got)
    return (got.dtype == want.dtype and got.shape == want.shape
            and np.array_equal(got.reshape(-1).view(np.uint8),
                               want.reshape(-1).view(np.uint8)))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# --------------------------------------------------------------------------
# data: columns from the seed, host encoding in CPU-only worker processes
# --------------------------------------------------------------------------


def cut(full: dict, mb: float) -> dict:
    """The prefix of each column holding ``mb`` MiB."""
    return {name: col[: int(mb * MiB) // col.itemsize]
            for name, col in full.items()}


def make_columns(full: dict, sizes_mb: dict, seed: int) -> dict:
    """{codec: {column: ndarray}}: the Table IV columns ``full`` cut to
    each codec's size, or registry demo data for ``DEMO_CODECS``."""
    import numpy as np

    from repro.core import registry

    rng = np.random.default_rng(seed)
    out = {}
    for codec, mb in sizes_mb.items():
        if codec in DEMO_CODECS:
            demo = registry.get(codec).demo_data
            out[codec] = {f"demo{i}": demo(int(mb * MiB) // 4, rng)
                          for i in range(len(full))}
        else:
            out[codec] = cut(full, mb)
    return out


def _cpu_only() -> None:
    # encoder workers never touch the accelerator: one process owns a chip
    os.environ["JAX_PLATFORMS"] = "cpu"


def _compress(arr, codec: str, chunk_bytes: int):
    from repro.core import api
    return api.compress(arr, codec, chunk_bytes=chunk_bytes)


def compress_columns(columns: dict, workers: int) -> dict:
    """{codec: {column: CompressedArray}}, encoded in a pool of CPU-only
    processes.  The chunk geometry is resolved here, for this process's
    device (the tuned table, else ``format.DEFAULT_CHUNK_BYTES``): in the
    workers ``api.compress`` would resolve it for the CPU."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    from repro.core import format as fmt
    from repro.core import tuning

    tasks = [(codec, name, arr,
              tuning.chunk_bytes_for(codec, tuning.encode_width(codec,
                                                                arr.dtype))
              or fmt.DEFAULT_CHUNK_BYTES)
             for codec, cols in columns.items() for name, arr in cols.items()]
    t0 = time.perf_counter()
    with ProcessPoolExecutor(max_workers=max(1, min(workers, len(tasks))),
                             mp_context=multiprocessing.get_context("spawn"),
                             initializer=_cpu_only) as pool:
        done = list(pool.map(_compress, [t[2] for t in tasks],
                             [t[0] for t in tasks], [t[3] for t in tasks]))
    out: dict = {}
    for (codec, name, _, _), ca in zip(tasks, done):
        out.setdefault(codec, {})[name] = ca
    log("encode", tasks=len(tasks), workers=min(workers, len(tasks)),
        seconds=round(time.perf_counter() - t0, 3))
    return out


def encode_all(columns: dict, workers: int) -> tuple:
    """The scan columns and the service's arrays, encoded in one pool:
    ``(scan cas, service columns, service cas)``.  The service gets the
    first ``SERVICE_KIB`` of one column per codec (TPC where it has one)."""
    svc_columns = {}
    for codec, cols in columns.items():
        arr = cols["TPC"] if "TPC" in cols else next(iter(cols.values()))
        svc_columns[codec] = {
            "service": arr[: SERVICE_KIB * 1024 // arr.itemsize]}
    cas = compress_columns({c: {**cols, **svc_columns[c]}
                            for c, cols in columns.items()}, workers)
    svc_cas = {c: {n: cas[c].pop(n) for n in cols}
               for c, cols in svc_columns.items()}
    return cas, svc_columns, svc_cas


def _planes(arr, ca) -> list:
    """The original values of each blob of ``ca``: lo/hi uint32 planes for
    a plane-decomposed 8-byte array, else the array itself."""
    import numpy as np
    if len(ca.blobs) == 1:
        return [arr]
    u = arr.reshape(-1).view(np.uint64)
    return [(u & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (u >> np.uint64(32)).astype(np.uint32)]


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def run_phase(name: str, fn, *args) -> None:
    """Run one phase; log its wall time and the compiles it paid for, as
    the process's one compile listener (``bench.compile_meter``) counts
    them: backend compiles (persistent-cache loads included), their
    seconds and the persistent-cache hits."""
    meter = compile_meter.meter()
    before = meter.snapshot()
    t0 = time.perf_counter()
    fn(*args)
    log(name, phase_s=round(time.perf_counter() - t0, 3), **{
        k: round(v, 3)
        for k, v in compile_meter.delta(meter.snapshot(), before).items()})


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def scan(columns: dict, cas: dict) -> None:
    """Per codec: one device-out ``decompress_many`` (it compiles), bit-exact
    against the columns; then the same blobs as a staged ``DecodePlan``
    re-executed under ``no_host_transfers()`` (warm: the programs are the
    ones the first call compiled), bit-exact against their planes."""
    import jax

    from repro.core import api, transfers
    from repro.core import plan as plan_mod
    from repro.kernels import ops

    dev0 = jax.devices()[0]
    meter = compile_meter.meter()
    with jax.enable_x64(True), ops.count_dispatches() as calls:
        for codec, by_name in cas.items():
            arrs = [columns[codec][n] for n in by_name]
            cal = list(by_name.values())
            before = meter.snapshot()
            outs, cold = _timed(
                lambda: api.decompress_many(cal, device_out=True))
            compile_s = meter.snapshot()["compile_s"] - before["compile_s"]
            for name, arr, out in zip(by_name, arrs, outs):
                _check(_same(out, arr), f"scan {codec}/{name} not bit-exact")
                _check(out.devices() == {dev0},
                       f"scan {codec}/{name} on {out.devices()}")
            del outs

            # the staged rerun, with host transfers forbidden (jax's guard
            # rejects implicit ones on a chip)
            blobs = [b for ca in cal for b in ca.blobs]
            want = [p for n, ca in by_name.items()
                    for p in _planes(columns[codec][n], ca)]
            plan = plan_mod.DecodePlan.build(blobs).stage()
            before = meter.snapshot()
            with transfers.count_host_transfers() as moved, \
                    transfers.no_host_transfers():
                outs, warm = _timed(plan.execute_device)
            for i, (out, w) in enumerate(zip(outs, want)):
                _check(_same(out, w),
                       f"scan {codec} guarded blob {i} not bit-exact")
            _check(moved["d2h"] == 0 and moved["h2d"] == 0,
                   f"scan {codec} guarded rerun moved data: {moved}")
            _check(meter.snapshot()["backend_compiles"]
                   == before["backend_compiles"],
                   f"scan {codec} guarded rerun compiled")
            del outs
            unc = sum(a.nbytes for a in arrs)
            log("scan", codec=codec, columns=len(arrs),
                decoded_MiB=round(unc / MiB, 3),
                compressed_MiB=round(sum(ca.compressed_bytes for ca in cal)
                                     / MiB, 3),
                groups=plan.num_dispatches,
                chunks=sum(b.num_chunks for b in blobs),
                compile_s=round(compile_s, 3), cold_s=round(cold, 3),
                warm_s=round(warm, 6), decoded_bytes=unc,
                no_host_transfers="ok")

    kinds = {(c["backend"], c["interpret"]) for c in calls}
    _check(all(b == "xla" for b, _ in kinds),
           f"scan dispatched off the XLA decode: {kinds}")
    log("scan", dispatch_kinds=sorted(kinds))


def service(columns: dict, cas: dict, submits: int = SERVICE_SUBMITS,
            producers: int = SERVICE_PRODUCERS) -> None:
    """~``submits`` requests from ``producers`` threads over the first blob
    of each codec's first array; half device-out; every future bit-exact."""
    import jax
    import numpy as np

    from repro.core.server import DecompressionService

    dev0 = jax.devices()[0]
    picks = []                  # (codec, blob, original) — one per codec
    for codec, by_name in cas.items():
        name, ca = next(iter(by_name.items()))
        picks.append((codec, ca.blobs[0],
                      _planes(columns[codec][name], ca)[0]))

    def verify(res, want, device_out: bool, what: str) -> None:
        if device_out:
            _check(isinstance(res, jax.Array) and res.devices() == {dev0},
                   f"{what}: device result not on {dev0}")
        else:
            _check(isinstance(res, np.ndarray), f"{what}: not a host array")
        _check(_same(res, want), f"{what} not bit-exact")

    svc = DecompressionService(max_batch_blobs=64, max_delay_ms=2.0,
                               cache_bytes=0)
    try:
        passes = []
        for _ in range(2):      # cold (compiles) then warm, same requests
            t0 = time.perf_counter()
            futs = [(svc.submit(b, device_out=d), w, d, c)
                    for c, b, w in picks for d in (False, True)]
            for f, w, d, c in futs:
                verify(f.result(timeout=1200), w, d, f"service warmup {c}")
            passes.append(time.perf_counter() - t0)

        errors: list = []
        per = submits // producers

        def producer(k: int) -> None:
            try:
                futs = []
                for i in range(per):
                    c, b, w = picks[(k + i) % len(picks)]
                    d = (k + i) % 2 == 0
                    futs.append((svc.submit(b, device_out=d), w, d, c))
                for f, w, d, c in futs:
                    verify(f.result(timeout=1200), w, d, f"service {c}")
            except BaseException as e:       # surfaced after the join
                errors.append(e)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=producer, args=(k,))
                   for k in range(producers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        steady = time.perf_counter() - t0
        if errors:
            raise errors[0]
    finally:
        closed = svc.close(timeout=600)
    _check(closed, "service close() did not drain")
    st = svc.stats()
    _check(st.errors == 0, f"service errors: {st.errors}")
    steady_bytes = sum(picks[(k + i) % len(picks)][2].nbytes
                       for k in range(producers) for i in range(per))
    log("service", submits=per * producers, producers=producers,
        blobs=len(picks), decoded_bytes=steady_bytes,
        compile_s=round(passes[0] - passes[1], 3), warm_s=round(steady, 6))
    log("service", stats=json.dumps(dict(
        windows=st.windows, blobs=st.blobs, dispatches=st.dispatches,
        blobs_per_window=st.blobs_per_window,
        dispatches_per_window=st.dispatches_per_window,
        cache_hit_rate=st.cache_hit_rate, errors=st.errors,
        latency_p50_ms=st.latency_p50_ms,
        latency_p99_ms=st.latency_p99_ms)))


def restore(columns: dict) -> None:
    """checkpoint.save(codec=RESTORE_CODEC) of ``columns``, then a
    streaming device-out restore through a filesystem store (cold, warm)."""
    import tempfile

    import jax

    from repro.checkpoint import checkpoint
    from repro.core import store as store_mod

    dev0 = jax.devices()[0]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        t0 = time.perf_counter()
        checkpoint.save(d, 1, columns, codec=RESTORE_CODEC)
        save_s = time.perf_counter() - t0
        with jax.enable_x64(True), store_mod.filesystem_store(d) as st:
            like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in columns.items()}
            times = []
            for _ in range(2):          # cold (compiles), then warm
                got, dt = _timed(lambda: checkpoint.restore(
                    d, 1, like, device_out=True, store=st))
                times.append(dt)
                for k, want in columns.items():
                    _check(_same(got[k], want), f"restore {k} not bit-exact")
                    _check(got[k].devices() == {dev0},
                           f"restore {k} on {got[k].devices()}")
    log("restore", codec=RESTORE_CODEC, leaves=len(columns),
        decoded_bytes=sum(v.nbytes for v in columns.values()),
        save_s=round(save_s, 3), compile_s=round(times[0] - times[1], 3),
        warm_s=round(times[1], 6))


def four_chips(seed: int, mb: float = FOUR_CHIP_MB,
               psum_elems: int = PSUM_ELEMS, workers: int = 8) -> None:
    """Mesh decode over 4 devices vs the one-device decode, and the int8
    compressed psum over a 4-member mesh vs a plain f32 psum."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import api, tuning
    from repro.distributed import collectives as C
    from repro.launch import mesh as mesh_lib

    from benchmarks import datasets

    devs = jax.devices()
    _check(len(devs) >= 4, f"--four-chips needs 4 devices, have {len(devs)}")
    columns = datasets.build(mb, seed)
    cas = compress_columns({FOUR_CHIP_CODEC: columns},
                           workers)[FOUR_CHIP_CODEC]
    names, cal = list(cas), list(cas.values())
    mesh = mesh_lib.make_decode_mesh(4)
    shardings = [NamedSharding(mesh, P(mesh.axis_names[0]))] * len(cal)
    with jax.enable_x64(True):
        _timed(lambda: api.decompress_many(cal, device_out=True))
        single, one_warm = _timed(
            lambda: api.decompress_many(cal, device_out=True))
        _, cold = _timed(lambda: api.decompress_many(
            cal, mesh=mesh, out_shardings=shardings))
        sharded, warm = _timed(lambda: api.decompress_many(
            cal, mesh=mesh, out_shardings=shardings))
        for name, one, out in zip(names, single, sharded):
            _check(_same(one, columns[name]), f"one-device {name} wrong")
            _check(_same(out, np.asarray(one)), f"mesh {name} != one-device")
            _check(one.devices() == {devs[0]}, f"one-device {name} placed "
                   f"on {one.devices()}")
            placed = {s.device for s in out.addressable_shards}
            _check(len(out.sharding.device_set) == 4 and len(placed) == 4,
                   f"mesh {name} spans {sorted(map(str, placed))}")
    log("four_chips", phase="mesh_decode", codec=FOUR_CHIP_CODEC,
        columns=len(cal),
        decoded_bytes=sum(a.nbytes for a in columns.values()),
        compile_s=round(cold - warm, 3), warm_s=round(warm, 6),
        one_device_warm_s=round(one_warm, 6))

    pods = Mesh(np.asarray(devs[:4]), ("pod",))
    rng = np.random.default_rng(seed)
    x_host = rng.standard_normal((4, psum_elems)).astype(np.float32)
    x = jax.device_put(x_host, NamedSharding(pods, P("pod")))
    tune = tuning.kernel_tune(C.WIRE_CODEC, 1)

    def member_sum(reduce):
        return jax.jit(jax.shard_map(
            lambda xs: reduce(xs[0])[None], mesh=pods, in_specs=P("pod"),
            out_specs=P("pod"), check_vma=False))

    wire = member_sum(lambda v: C.compressed_psum(v, "pod", tune=tune))
    plain = member_sum(lambda v: jax.lax.psum(v, "pod"))
    _, cold = _timed(lambda: wire(x))
    got, warm = _timed(lambda: wire(x))
    ref = plain(x)
    placed = {s.device for s in got.addressable_shards}
    _check(len(placed) == 4, f"psum result on {sorted(map(str, placed))}")
    got_h, ref_h = np.asarray(got), np.asarray(ref)
    _check(all(np.array_equal(got_h[0], g) for g in got_h[1:]),
           "members disagree on the compressed psum")
    # the int8 bound of tests/test_distributed.py: each member's blocks
    # round to within half a grid step of max|x|/127
    scale = np.abs(x_host).max() / 127.0
    err_ref = float(np.abs(got_h[0] - ref_h[0]).max())
    err_true = float(np.abs(got_h[0] - x_host.astype(np.float64).sum(0))
                     .max())
    _check(err_ref <= 2 * scale + 1e-6 and err_true <= 2 * scale + 1e-6,
           f"compressed psum error {err_ref} / {err_true} > {2 * scale}")
    log("four_chips", phase="compressed_psum", members=4,
        elems=psum_elems, max_err_vs_psum=err_ref, bound=2 * scale,
        compile_s=round(cold - warm, 3), warm_s=round(warm, 6))


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (mesh decode and "
                         "compressed psum over 4 devices)")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform} "
              f"devices only", file=sys.stderr)
        return 1

    from repro.core import tuning
    log("setup", compile_cache=tuning.enable_compile_cache())
    kind = tuning.device_kind()
    table = tuning.load_table()
    has_row = any(kind in kinds for ws in table["codecs"].values()
                  for kinds in ws.values())
    log("setup", device_kind=devs[0].device_kind, tuned_table_key=kind,
        tuned_table_row=has_row, devices=len(devs))

    t0 = time.perf_counter()
    if args.four_chips:
        run_phase("four_chips", four_chips, args.seed)
    else:
        from benchmarks import datasets
        full = datasets.build(max([RESTORE_MB] + [
            mb for c, mb in SCAN_MB.items() if c not in DEMO_CODECS]),
            args.seed)
        columns = make_columns(full, SCAN_MB, args.seed)
        cas, svc_columns, svc_cas = encode_all(columns,
                                               os.cpu_count() or 1)
        run_phase("scan", scan, columns, cas)
        run_phase("service", service, svc_columns, svc_cas)
        run_phase("restore", restore,
                  cut({k: full[k] for k in RESTORE_COLUMNS}, RESTORE_MB))
    log("total", seconds=round(time.perf_counter() - t0, 3),
        **{k: round(v, 3)
           for k, v in compile_meter.meter().snapshot().items()})
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
