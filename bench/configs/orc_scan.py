"""ORC column scans: the system side of ``orc_scan.json``.

Each stripe holds the columns TPC-H Q1 reads from ``lineitem``
(``orc_table``), each column compressed by the program's host encoder under
one encoding, in the configuration's chunks.  The compressed stripes stay in
host memory.  A query is the user's call,
``api.decompress_many(stripe, device_out=True)``, so parse, group, pad,
host-to-device copy, decode and 64-bit plane recombination all sit on the
measured path.

The traffic's ``select.encodings`` picks the encodings; the items are the
stripes in the order ``(stripe 0, each encoding), (stripe 1, ...)``.
The plain reference of a query is its columns, drawn again from the
configuration's base seed and ordered by the run's seed.
"""
from __future__ import annotations

import numpy as np

from bench import encode
from bench.configs import orc_table


class OrcScan:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import api
        self._api = api
        self.seed, self.base_seed = seed, int(cfg["base_seed"])
        self.chunk_bytes = int(cfg["chunk_bytes"])
        self.scale_factor = float(cfg["scale_factor"])
        self.columns = list(cfg["columns"])
        encodings = list(traffic["select"]["encodings"])
        rows = {e: int(cfg["rows_per_stripe"][e]) for e in encodings}
        # encodings of one stripe length share a task: the column is drawn once
        by_rows: dict = {}
        for e in encodings:
            by_rows.setdefault(rows[e], []).append(e)
        stripes = range(int(cfg["stripes"]))
        tasks = [(self.base_seed, seed, s, name, n, self.scale_factor,
                  tuple(encs), self.chunk_bytes)
                 for n, encs in by_rows.items()
                 for s in stripes for name in self.columns]
        cas = {}
        for t, out in zip(tasks, encode.run_pool(encode.encode_column, tasks)):
            for e in t[6]:
                cas.setdefault((e, t[2]), {})[t[3]] = out[e]
        self.items = [(e, s) for s in stripes for e in encodings]
        self._cas = [[cas[it][n] for n in self.columns] for it in self.items]
        self._rows = [rows[e] for e, _ in self.items]
        self._ref: dict = {}

    def call(self, i: int, device_out: bool):
        import jax
        with jax.enable_x64(True):          # 64-bit columns stay 64-bit
            return self._api.decompress_many(self._cas[i],
                                             device_out=device_out)

    def decoded_bytes(self, i: int) -> int:
        return sum(int(np.prod(ca.orig_shape)) * np.dtype(ca.orig_dtype).itemsize
                   for ca in self._cas[i])

    def compressed_bytes(self, i: int) -> int:
        return sum(ca.compressed_bytes for ca in self._cas[i])

    def reference(self, i: int) -> list:
        if i not in self._ref:
            _, s = self.items[i]
            self._ref[i] = [orc_table.column(
                self.base_seed, self.seed, s, n, self._rows[i],
                self.scale_factor, self.chunk_bytes) for n in self.columns]
        return self._ref[i]

    def describe(self) -> str:
        """Rows and compression ratio of each column, for the run's log."""
        return "; ".join(
            f"{e}/s{s} rows={n} " + " ".join(
                f"{c}={ca.ratio:.4f}" for c, ca in zip(self.columns, cas))
            for (e, s), n, cas in zip(self.items, self._rows, self._cas))


def build(cfg: dict, traffic: dict, seed: int) -> OrcScan:
    return OrcScan(cfg, traffic, seed)
