"""bitpack unpack — Pallas TPU kernel (fully vectorized, memory-bound).

The one codec in the suite with *no* sequential dependence: element i lives
at bit i*bits, so every VPU lane unpacks independently with a funnel shift —
the pure form of the paper's observation that writing is trivially parallel
once positions are known.  Used for int8/int4 optimizer moments and
quantized KV-cache (optim/grad_compress.py), and as the wire format of the
compressed collectives: distributed/collectives.py builds this exact blob
layout *on device* so gradient syncs decode through the same kernel.

Grid is (num_chunks, elems/TILE): the word row rides along whole (it is
~bits/32 the size of the output tile), the output is tiled (1, TILE) with
TILE=2048 = 16 VREGs of 8x128 — MXU-free, pure VPU+DMA, and the roofline
bench shows it pinned on the HBM term as expected.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import registry
from repro.kernels import harness, ref

TILE = 2048


def unpack_tile(words: jnp.ndarray, start, n: int, bits: int) -> jnp.ndarray:
    """Unpack elements [start, start+n) from a uint32 word buffer."""
    idx = start + jnp.arange(n, dtype=jnp.int32)
    bitpos = idx * bits
    w = bitpos >> 5
    off = (bitpos & 31).astype(jnp.uint32)
    w0 = jnp.take(words, w, mode="clip")
    w1 = jnp.take(words, w + 1, mode="clip")
    lo = jnp.right_shift(w0, off)
    sh = (jnp.uint32(32) - off) & jnp.uint32(31)
    hi = jnp.where(off > 0, jnp.left_shift(w1, sh), jnp.uint32(0))
    mask = jnp.uint32(0xFFFFFFFF) if bits == 32 else jnp.uint32((1 << bits) - 1)
    return (lo | hi) & mask


def _kernel(words_ref, out_ref, *, bits: int, tile: int):
    j = pl.program_id(1)
    out_ref[0, :] = unpack_tile(words_ref[0, :], j * tile, tile, bits)


@functools.partial(jax.jit, static_argnames=("bits", "out_elems", "interpret",
                                             "tile"))
def unpack_pallas(words: jnp.ndarray, *, bits: int, out_elems: int,
                  interpret: Optional[bool] = None,
                  tile: int = TILE) -> jnp.ndarray:
    """words: (num_chunks, W) uint32 -> (num_chunks, out_elems) uint32.

    ``tile`` is the output-tile width (autotunable; default 16 VREGs) —
    smaller tiles raise grid parallelism, larger ones amortize the per-cell
    word-row DMA."""
    n, w = words.shape
    tiles = (out_elems + tile - 1) // tile
    padded = tiles * tile
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, tile=tile),
        grid=(n, tiles),
        in_specs=[pl.BlockSpec((1, w), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((1, tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, padded), jnp.uint32),
        interpret=harness.resolve_interpret(interpret),
    )(words)
    return out[:, :out_elems]


# --------------------------------------------------------------------------
# registry plumbing: DecodeSpec bodies + the Codec entry
# --------------------------------------------------------------------------


def _body(inputs, consts, out_len, *, chunk_elems, width, bits):
    (words,) = inputs
    with jax.named_scope(harness.PHASE2):    # no Phase 1: fixed-width fields
        out = unpack_tile(words, jnp.int32(0), chunk_elems, bits)
        return out.astype(harness.DEV_DTYPE[width])


def _body_oracle(inputs, consts, out_len, *, chunk_elems, width, bits):
    (words,) = inputs
    return ref.unpack_bits(words, chunk_elems, bits).astype(
        harness.DEV_DTYPE[width])


def _pallas(body, inputs, consts, out_lens, *, chunk_elems, width, bits,
            interpret, tune=()):
    """Hand-tuned override: the output-tiled kernel above (16-VREG tiles)
    instead of the harness's one-chunk-per-cell generic wrapper.  The tile
    width is this codec's declared ``Tunable`` — the autotuner's winning
    value (or an explicit override) arrives via the static ``tune``."""
    (words,) = inputs
    tile = int(dict(tune).get("tile", TILE))
    out = unpack_pallas(words, bits=bits, out_elems=chunk_elems,
                        interpret=interpret, tile=tile)
    return out.astype(harness.DEV_DTYPE[width])


def _demo_data(n, rng):
    """Low-dynamic-range uint32s (gradient-index / quantized-state shaped)."""
    return rng.integers(0, 1 << 9, n).astype("uint32")


CODEC = registry.register(registry.Codec(
    name=fmt.BITPACK,
    encode=enc.compress_bitpack,
    decode=harness.DecodeSpec(
        body=_body,
        body_oracle=_body_oracle,
        chunk_inputs=harness.words_inputs,
        pallas_override=_pallas,
        tunables=(harness.Tunable("tile", (512, 1024, 2048, 4096), TILE),),
    ),
    needs_words=True,
    shared_extras=("bitpack_bits",),
    static_bits=lambda blob: int(blob.extras["bitpack_bits"][0]),
    demo_data=_demo_data,
))
