"""CodagEngine — the decode configuration every entry path carries.

The paper's central claim is about *provisioning*: how many independent
decompression streams the hardware scheduler can interleave.  The engine
always provisions CODAG's way: one independent stream per chunk (every
chunk row of a fused table decodes side by side in one launch), with the
all-thread two-phase decode inside each stream (§IV-D).

``EngineConfig`` picks only the backend:

  backend="xla"     the codec bodies compiled by XLA: the decode path on
                    every platform, the TPU included.
  backend="pallas"  the Pallas kernels: interpreted on the CPU backend,
                    compiled by Mosaic on the TPU (``harness.resolve_interpret``).
  backend="oracle"  the sequential references the other two are checked
                    against.

The engine is a *configuration* wrapper: every decode it issues lowers
through the unified plan IR (``core.plan.dispatch`` is the one
``ops.decode`` site; the convenience round trips build one-blob
``DecodePlan``s), so the engine, the public API, and the serving loop all
execute the same pipeline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import format as fmt
from repro.core import plan as plan_mod
from repro.core import transfers
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    backend: str = "xla"        # "xla" | "pallas" | "oracle"
    interpret: Optional[bool] = None  # None: interpret on CPU only
    # explicit kernel-knob overrides ((name, value), ...) — merged over the
    # tuned-defaults table per dispatch (explicit wins; ``core.tuning``)
    tune: tuple = ()


class CodagEngine:
    def __init__(self, config: EngineConfig = EngineConfig()):
        self.config = config

    def decompress_chunks(self, dev: Dict[str, Any], *, codec: str,
                          width: int, chunk_elems: int,
                          bits: int = 0, epilogue=None) -> jnp.ndarray:
        """Decode to (num_chunks, chunk_elems); jit-compatible.

        Lowers straight to the plan IR's dispatch stage (the repo's one
        ``ops.decode`` call site) under this engine's config.
        ``epilogue``: optional ``kernels.harness.Epilogue`` fused into the
        dispatch (cast/widen/dequant before the matrix reaches a consumer).
        """
        return plan_mod.dispatch(dev, config=self.config, codec=codec,
                                 width=width, chunk_elems=chunk_elems,
                                 bits=bits, epilogue=epilogue)

    def decompress_table_device(self, table: fmt.CompressedBlob,
                                epilogue=None) -> jnp.ndarray:
        """Decode a flat chunk table (a single blob or a multi-blob merge
        from ``format.concat_blobs``) with one dispatch, no reassembly; the
        raw (num_chunks, chunk_elems) matrix STAYS on device.  Callers
        owning a blob→row mapping scatter it back with
        ``format.reassemble_device``."""
        dev, bits = ops.table_inputs(table)
        return self.decompress_chunks(dev, codec=table.codec,
                                      width=table.width,
                                      chunk_elems=table.chunk_elems,
                                      bits=bits, epilogue=epilogue)

    def decompress_table(self, table: fmt.CompressedBlob) -> np.ndarray:
        """Host variant of :func:`decompress_table_device`: one dispatch,
        then one sanctioned device→host materialization."""
        return transfers.to_host(self.decompress_table_device(table))

    def decompress(self, blob: fmt.CompressedBlob) -> np.ndarray:
        """Host convenience: full round trip back to the original ndarray
        (a one-blob DecodePlan, executed on the host path)."""
        return plan_mod.DecodePlan.build([blob]).execute(self)[0]

    def decompress_device(self, blob: fmt.CompressedBlob,
                          epilogue=None) -> jnp.ndarray:
        """Device convenience: full round trip to a device-resident array —
        a one-blob DecodePlan on the device path (decode + reassembly and
        any fused epilogue, no host visit)."""
        return plan_mod.DecodePlan.build([blob]).execute_device(
            self, epilogue=epilogue)[0]
