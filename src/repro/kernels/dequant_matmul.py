"""Fused int8-dequant matmul — Pallas TPU kernel + device-resident consumer.

The compute hot-spot of quantized serving (§Perf hillclimb 2 / EXPERIMENTS
H2-B): y = x @ (q * s) with int8 weights and per-output-channel scales.
Fusing the dequant into the matmul K-loop means the memory system reads
1 byte/weight (the entire point of weight compression) and the f32/bf16
expansion only ever exists tile-at-a-time in VMEM — never in HBM.

Classic tiled-matmul structure: grid (M/bm, N/bn, K/bk), f32 VMEM
accumulator, MXU-aligned 128-multiple tiles, dequant applied to the weight
tile on load.  Validated in interpret mode against ref.py's oracle, and
compiled by Mosaic for a described v5e chip in ``tests/test_tpu_compile``.

``decompress_dequant_matmul`` is the end-to-end ISSUE-4 consumer: weights
arrive *compressed*, are decoded + zero-point-corrected to int8 on device
(a fused decode ``Epilogue``), and feed the matmul without ever visiting
the host — the full decode→consume path runs under
``transfers.no_host_transfers()``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.harness import resolve_interpret


def ref_dequant_matmul(x: jnp.ndarray, q: jnp.ndarray,
                       s: jnp.ndarray) -> jnp.ndarray:
    """Oracle: x (M,K) @ dequant(q (K,N), s (1,N)) -> (M,N) in x.dtype."""
    w = q.astype(jnp.float32) * s.astype(jnp.float32)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = q_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def dequant_matmul(x: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray, *,
                   bm: int = 128, bn: int = 128, bk: int = 128,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (M,K) bf16/f32, q: (K,N) int8, s: (1,N) f32 -> (M,N) x.dtype."""
    M, K = x.shape
    _, N = q.shape
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm_ == 0 and N % bn_ == 0 and K % bk_ == 0, (M, N, K)
    grid = (M // bm_, N // bn_, K // bk_)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],  # f32 acc tile
        interpret=resolve_interpret(interpret),
    )(x, q, s)


# --------------------------------------------------------------------------
# Device-resident consumer: compressed weights in, activations out
# --------------------------------------------------------------------------


def compress_weights(q: np.ndarray, codec: str = "bitpack",
                     zero_point: int = 0,
                     chunk_bytes: int = 64 * 1024):
    """Pack int8 weights for the device-resident matmul path.

    Stores ``q + zero_point`` as uint8 (a zero-point shift keeps low-
    magnitude quantized weights in a narrow non-negative range, which is
    what bitpack exploits: |q| < 2^(b-1) packs at b bits/weight instead of
    8).  Returns the ``api.CompressedArray``; decode with the matching
    epilogue from :func:`weight_epilogue`.
    """
    from repro.core import api
    if q.dtype != np.int8:
        raise ValueError(f"expected int8 weights, got {q.dtype}")
    stored = (q.astype(np.int16) + int(zero_point)).astype(np.uint8)
    return api.compress(stored, codec, chunk_bytes)


def weight_epilogue(zero_point: int = 0):
    """The fused decode epilogue matching :func:`compress_weights`:
    widen the stored uint8 back through the zero-point shift to int8,
    inside the decode dispatch (epilogue operand key ``"epi_zero"``)."""
    from repro.kernels.harness import Epilogue
    return (Epilogue(out_dtype="int8", zero_key="epi_zero"),
            {"epi_zero": np.uint8(zero_point)})


def decompress_dequant_matmul(x: jnp.ndarray, ca, s: jnp.ndarray, *,
                              zero_point: int = 0, engine=None,
                              bm: int = 128, bn: int = 128, bk: int = 128,
                              interpret: Optional[bool] = None
                              ) -> jnp.ndarray:
    """End-to-end device-resident consumer (the ISSUE-4 acceptance path).

    ``ca`` holds (K, N) int8 weights from :func:`compress_weights`.  The
    weights are decoded, scattered to their (K, N) layout, and zero-point-
    corrected to int8 entirely on device (one fused dispatch per codec
    group, epilogue fused in), then consumed by the fused dequant matmul —
    no uint intermediate, no host round trip.

    The staged ``DecodePlan`` (fused tables + scatter + operands, uploaded
    once) is cached on ``ca``, so repeat calls over the same compressed
    weights — the serving steady state — perform no host transfers at all.
    """
    from repro.core import plan as plan_mod
    from repro.core.engine import CodagEngine, EngineConfig
    cached = getattr(ca, "_dqm_plan", None)
    if cached is None or cached[2] != zero_point:
        epi, operands = weight_epilogue(zero_point)
        plan = plan_mod.DecodePlan.build(list(ca.blobs)).stage()
        cached = (plan, (epi, operands), zero_point)
        ca._dqm_plan = cached
    plan, (epi, operands), _ = cached
    [q] = plan.execute_device(engine or CodagEngine(EngineConfig()),
                              epilogue=epi, epilogue_operands=operands)
    return dequant_matmul(x, q, s, bm=bm, bn=bn, bk=bk, interpret=interpret)
