"""RLE v1 codec plugin (byte-aligned runs + literals; ORC RLE v1 structure).

Everything below is exactly what the paper's §IV-A framework claim says a
codec author writes: a Phase-1 header parse and a Phase-2 value expression.
The while-loop group-table scaffolding, the scatter/cumsum/gather all-thread
expansion, the group-serial oracle, and the Pallas chunk-per-cell wrapper
all live in ``kernels/harness.py``; the host encoder is
``encoders.compress_rle_v1``; the sequential oracle stays in
``kernels/ref.py``.

Group structure (DESIGN.md §2):
  control c in [0,127]   -> run of length c+3 (3..130), one value follows
  control c in [128,255] -> 256-c literals (1..128), values follow
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import registry
from repro.core import streams as st
from repro.kernels import harness, ref


def max_groups(out_len: int) -> int:
    # worst case: [run(3), lit(1)] repeating = 2 groups / 4 elements
    return out_len // 2 + 4


def _parse(comp, pos, width: int):
    """Control byte -> (length, advance, kind, value, literal offset)."""
    c = st.read_byte_at(comp, pos)
    is_run = c < 128
    length = jnp.where(is_run, c + 3, 256 - c)
    return {
        "length": length,
        "advance": 1 + jnp.where(is_run, width, length * width),
        "is_run": is_run,
        "value": st.read_value_at(comp, pos + 1, width),
        "litoff": pos + 1,
    }


def _express(comp, f, k, width: int):
    """Element k of a group: the run value, or the k-th gathered literal."""
    lit = st.gather_values(comp, f["litoff"] + k * width, width)
    return jnp.where(f["is_run"], f["value"], lit)


SPEC = harness.TwoPhaseSpec(
    fields=(harness.Field("is_run", jnp.bool_),
            harness.Field("value", jnp.uint32),
            harness.Field("litoff", jnp.int32)),
    parse=_parse,
    express=_express,
    max_groups=max_groups,
    max_group_len=ref.RLE1_MAX_WIN,
)


def _count_groups(row, width: int) -> int:
    """Host-side header walk (Table V avg symbol length)."""
    pos, groups = 0, 0
    while pos < len(row):
        c = int(row[pos])
        pos += 1 + (width if c < 128 else (256 - c) * width)
        groups += 1
    return groups


def _demo_data(n: int, rng) -> np.ndarray:
    """Run-heavy uint32 stream (the codec's natural workload)."""
    vals = rng.integers(0, 100, max(4, n // 50)).astype(np.uint32)
    return np.resize(np.repeat(vals, rng.integers(1, 100, len(vals))), n)


CODEC = registry.register(registry.Codec(
    name=fmt.RLE_V1,
    encode=enc.compress_rle_v1,
    decode=harness.DecodeSpec.from_two_phase(SPEC, oracle=ref.decode_rle_v1_impl),
    plane_decompose_64=True,
    demo_data=_demo_data,
    count_groups=_count_groups,
))
