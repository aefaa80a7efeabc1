"""Quickstart: compress on host, decompress on device with the CODAG engine.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import api, format as fmt
from repro.core.engine import CodagEngine, EngineConfig

rng = np.random.default_rng(0)

# a run-heavy integer column (think: ORC analytics data, Table IV)
column = np.repeat(rng.integers(0, 100, 2000).astype(np.uint32),
                   rng.integers(1, 64, 2000))

for codec in (fmt.RLE_V1, fmt.RLE_V2, fmt.TDEFLATE):
    ca = api.compress(column, codec)
    out = api.decompress(ca)                       # device decode (XLA path)
    assert np.array_equal(out, column)
    print(f"{codec:9s}: {column.nbytes/1e6:6.2f} MB -> "
          f"{ca.compressed_bytes/1e6:6.3f} MB  (ratio {ca.ratio:.4f})")

# the Pallas kernel path: interpreted on the CPU backend, compiled by Mosaic
# on a TPU (where the generic wrapper's blocks do not lower yet, so this
# raises Mosaic's own error there instead of quietly interpreting):
eng = CodagEngine(EngineConfig(backend="pallas"))
out = api.decompress(api.compress(column, fmt.RLE_V2), eng)
assert np.array_equal(out, column)
print("Pallas kernel decode OK")
