"""chip_smoke.py at a tiny size on the CPU backend.

The script itself refuses to run without a TPU; these tests call its
phases directly (scan, service, restore in-process; the four-chip phase in
a subprocess on 4 virtual CPU devices) so a broken phase shows up before a
chip run does.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY_MB = {codec: 0.05 for codec in chip_smoke.SCAN_MB}


@pytest.fixture(scope="module")
def data():
    from benchmarks import datasets
    full = datasets.build(0.05, seed=1)
    columns = chip_smoke.make_columns(full, TINY_MB, seed=1)
    return (full, columns) + chip_smoke.encode_all(columns, workers=2)


def test_tiny_columns_cover_every_codec(data):
    from repro.core import registry
    _, columns, cas, svc_columns, svc_cas = data
    assert set(columns) == set(cas) == set(registry.names())
    assert all(len(cols) == 7 for cols in columns.values())
    assert all(len(cas[c]) == 7 for c in cas)
    assert set(svc_cas) == set(cas)


def test_scan_phase(data, capsys):
    _, columns, cas, _, _ = data
    chip_smoke.scan(columns, cas)
    out = capsys.readouterr().out
    assert out.count("scan codec=") == len(cas)
    assert out.count("no_host_transfers=ok") == len(cas)


def test_service_phase(data, capsys):
    _, _, _, svc_columns, svc_cas = data
    chip_smoke.service(svc_columns, svc_cas, submits=48, producers=4)
    out = capsys.readouterr().out
    assert "service submits=48" in out
    stats = json.loads(out.split("service stats=")[1].splitlines()[0])
    assert stats["errors"] == 0 and stats["blobs"] >= 48


def test_restore_phase(data, capsys):
    full = data[0]
    chip_smoke.restore({k: full[k] for k in chip_smoke.RESTORE_COLUMNS})
    assert "restore codec=rle_v2 leaves=3" in capsys.readouterr().out


def test_compile_meter_counts_a_fresh_compile(capsys):
    import jax
    import jax.numpy as jnp
    from bench import compile_meter
    meter = compile_meter.meter()
    before = meter.snapshot()
    chip_smoke.run_phase("probe", lambda: jax.jit(lambda x: x * 7 + 3)(
        jnp.arange(5)).block_until_ready())
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("probe ")]
    logged = dict(kv.split("=") for kv in line.split()[1:])
    assert float(logged["phase_s"]) >= 0
    grew = compile_meter.delta(meter.snapshot(), before)
    assert grew["backend_compiles"] >= 1 and grew["compile_s"] > 0
    assert int(logged["backend_compiles"]) == grew["backend_compiles"]
    assert compile_meter.meter() is meter


def test_main_refuses_without_tpu(capsys):
    """On the CPU backend the script exits non-zero and prints no result."""
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "needs a TPU" in captured.err


def test_script_alone_fails(tmp_path):
    """Copied away from the repository, the script cannot run."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    r = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=300, cwd=str(tmp_path),
                       env={**os.environ, "PYTHONPATH": ""})
    assert r.returncode != 0 and '"ok": true' not in r.stdout


def test_four_chips_phase_on_virtual_devices():
    body = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        import chip_smoke
        chip_smoke.four_chips(seed=1, mb=0.05, psum_elems=4096, workers=2)
        print("PASS")
    """ % ROOT)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, timeout=900, env=env, cwd=ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-4000:]}"
    assert "phase=mesh_decode" in r.stdout
    assert "phase=compressed_psum" in r.stdout and "PASS" in r.stdout
