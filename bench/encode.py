"""Host encoding of a deployment's data in a pool of CPU-only processes.

Encoding is the program's host-side NumPy code and part of every run's
set-up.  The workers never touch the accelerator (one process owns a chip):
each sets ``JAX_PLATFORMS=cpu`` before anything imports JAX.
"""
from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor


def _cpu_only() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"


def encode_column(base_seed: int, seed: int, stripe: int, name: str,
                  rows: int, scale_factor: float, codecs: tuple,
                  chunk_bytes: int) -> dict:
    """{codec: CompressedArray} of one lineitem column of one stripe, its
    chunks in the seed's order (``orc_table.column``)."""
    from repro.core import api

    from bench.configs import orc_table

    col = orc_table.column(base_seed, seed, stripe, name, rows, scale_factor,
                           chunk_bytes)
    return {c: api.compress(col, c, chunk_bytes=chunk_bytes) for c in codecs}


def run_pool(fn, tasks: list, workers: int | None = None) -> list:
    """``[fn(*t) for t in tasks]`` in spawned CPU-only worker processes."""
    workers = max(1, min(workers or (os.cpu_count() or 2) - 1, len(tasks)))
    with ProcessPoolExecutor(max_workers=workers,
                             mp_context=multiprocessing.get_context("spawn"),
                             initializer=_cpu_only) as pool:
        futs = [pool.submit(fn, *t) for t in tasks]
        return [f.result() for f in futs]
