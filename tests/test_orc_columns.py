"""The benchmark cells' own traffic on the one decode path.

The ``orc_scan`` cells decode the seven TPC-H Q1 ``lineitem`` columns
(``bench/configs/orc_table``) under ORC RLE v1, RLE v2 and Deflate through
``api.decompress_many(..., device_out=True)`` with 64-bit types on, as
``bench/configs/orc_scan.py`` calls it: int64 decimals split into two uint32
planes, int32 dates and one-byte flags.  Here the same columns, cut to 8,192
rows in 4 KiB chunks, decode column by column and as a whole stripe, and
each decode is checked against the column and against the same decode
through the oracle backend.
"""
import functools
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.configs import orc_table
from repro.core import api
from repro.core import plan as plan_mod
from repro.core.engine import CodagEngine, EngineConfig
from repro.kernels import ops

CFG = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                  / "orc_scan.json").read_text())
ROWS, CHUNK_BYTES = 8192, 4096
ENCODINGS = tuple(CFG["encodings"])        # rle_v1, rle_v2, tdeflate
ORACLE = CodagEngine(EngineConfig(backend="oracle"))


@functools.lru_cache(maxsize=None)
def _column(name: str) -> np.ndarray:
    return orc_table.column(CFG["base_seed"], 0, 0, name, ROWS,
                            CFG["scale_factor"], CHUNK_BYTES)


@functools.lru_cache(maxsize=None)
def _compressed(encoding: str, name: str):
    return api.compress(_column(name), encoding, chunk_bytes=CHUNK_BYTES)


def _check(got, want, what):
    assert got.dtype == want.dtype, what
    assert got.shape == want.shape, what
    np.testing.assert_array_equal(np.asarray(got), want, err_msg=what)


@pytest.mark.parametrize("name", CFG["columns"])
@pytest.mark.parametrize("encoding", ENCODINGS)
def test_column_decodes_bit_exact(encoding, name):
    ca = _compressed(encoding, name)
    with jax.enable_x64(True):
        [got] = api.decompress_many([ca], device_out=True)
        [ref] = api.decompress_many([ca], ORACLE, device_out=True)
        _check(got, _column(name), f"{encoding}/{name}")
        _check(ref, _column(name), f"{encoding}/{name} oracle")


@pytest.mark.parametrize("encoding,groups", [("rle_v1", 2), ("rle_v2", 2),
                                             ("tdeflate", 1)])
def test_stripe_decodes_in_one_dispatch_per_group(encoding, groups):
    """A stripe is one plan: the 4-byte lanes (int64 planes and int32
    dates) share a group, the flags take another; Deflate views every
    column as bytes, so its stripe is one group."""
    cas = [_compressed(encoding, n) for n in CFG["columns"]]
    blobs = [b for ca in cas for b in ca.blobs]
    assert plan_mod.DecodePlan.build(blobs).num_dispatches == groups
    with jax.enable_x64(True):
        with plan_mod.count_lowered() as lowered, \
                ops.count_dispatches() as dispatched:
            got = api.decompress_many(cas, device_out=True)
        assert len(lowered) == len(dispatched) == groups
        ref = api.decompress_many(cas, ORACLE, device_out=True)
        for name, g, r in zip(CFG["columns"], got, ref):
            _check(g, _column(name), f"{encoding}/{name}")
            _check(r, _column(name), f"{encoding}/{name} oracle")
