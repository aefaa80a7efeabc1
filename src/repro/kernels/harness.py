"""Two-phase decode harness — the shared machinery of every codec kernel.

This module owns everything the paper's §IV-A framework claim says a codec
author should NOT have to write:

  * Phase 1 scaffolding   — the irreducibly-sequential leader loop: one
    ``lax.while_loop`` step per compressed *group*, appending
    ``(start, <codec fields>)`` rows to VMEM group tables.
  * Phase 2 expansion     — the all-thread decode: scatter a marker at every
    group start, prefix-sum it into a lane->group map, gather each group's
    fields, and let every VPU lane evaluate the codec's value expression
    independently (Table II's vectorized ``write_run``; literals ride the
    shared multi-byte gather ``streams.gather_values``).
  * a group-serial oracle — one group per step, vector-blend write: the
    paper-faithful sequential reference, free for any two-phase codec.
  * ONE ``pallas_call``   — the generic chunk-per-grid-cell wrapper: every
    per-chunk operand gets a ``(1, row)`` BlockSpec (chunk i's HBM->VMEM DMA
    double-buffers against chunk i-1's decode — CODAG's warp-per-chunk
    provisioning), broadcast constants get index-map ``(0, 0)``.

A two-phase codec (rle_v1, rle_v2, dbp) supplies a ``TwoPhaseSpec`` — a
header parse and a value expression — and gets all three backends.  Codecs
whose Phase 2 is not lane-independent (the LZ codecs tdeflate and lzss,
whose back-references read earlier output) or that need no Phase 1 at all
(bitpack) plug custom chunk bodies into the same ``DecodeSpec`` interface
instead; the LZ codecs share ``resolve_backrefs``, the all-lane Phase 2 of
a command list.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEV_DTYPE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

# Stable ``jax.named_scope`` names on the decode programs: a profiler trace
# names each device operation by the scopes it was traced under, so the
# device time of a decode splits by stage whatever XLA calls the ops.
# ``codag.decode.<codec>`` wraps a whole decode (``kernels.ops``).
PHASE1 = "codag.phase1"        # the sequential parse loop
PHASE2 = "codag.phase2"        # the expansion / command execution
EPILOGUE = "codag.epilogue"    # Epilogue.apply


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas kernels run in interpret mode on the CPU backend and are
    compiled by Mosaic on every other platform; ``None`` picks by platform,
    an explicit bool wins (ahead-of-time compile checks pass ``False``)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def words_view(comp: jnp.ndarray) -> jnp.ndarray:
    """(n, C) uint8 -> (n, ceil(C/4)) uint32 little-endian word view.

    Rows whose byte width is not a multiple of 4 are zero-padded up to the
    next word boundary (trailing partial words read as if the row were
    zero-extended, which is how every bit codec's padding behaves).
    """
    n, c = comp.shape
    if c % 4:
        comp = jnp.pad(comp, ((0, 0), (0, 4 - c % 4)))
        c = comp.shape[1]
    b = comp.reshape(n, c // 4, 4).astype(jnp.uint32)
    return (b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24))


# --------------------------------------------------------------------------
# TwoPhaseSpec: what a group-structured codec author writes
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Field:
    """One per-group table column (beyond the harness-owned ``start``)."""

    name: str
    dtype: Any


@dataclasses.dataclass(frozen=True)
class TwoPhaseSpec:
    """Header parse + value expression; the harness supplies the rest.

    ``parse(comp, pos, width)`` reads ONE group header at byte ``pos`` and
    returns a dict with ``"length"`` (elements this group expands to),
    ``"advance"`` (total group bytes, header + payload), and one entry per
    declared field.  ``express(comp, fields, k, width)`` computes element
    ``k`` of a group from its gathered fields — ``k`` is a lane vector
    (Phase 2 and the group-serial oracle) — and returns uint32.
    """

    fields: Tuple[Field, ...]
    parse: Callable[..., Dict[str, jnp.ndarray]]
    express: Callable[..., jnp.ndarray]
    max_groups: Callable[[int], int]
    max_group_len: int          # static lane-window bound (>= longest group)


def two_phase_chunk(spec: TwoPhaseSpec, comp: jnp.ndarray, out_len_dyn,
                    out_len_max: int, width: int) -> jnp.ndarray:
    """Decode one chunk with the all-thread two-phase scheme (§IV-D)."""
    MG = spec.max_groups(out_len_max)
    dt = DEV_DTYPE[width]
    names = [f.name for f in spec.fields]

    # ---- Phase 1: sequential group parse -> group tables ------------------
    def cond(s):
        return jnp.logical_and(s[2] < out_len_dyn, s[1] < MG)

    def body(s):
        pos, g, cnt, starts = s[0], s[1], s[2], s[3]
        tabs = s[4:]
        p = spec.parse(comp, pos, width)
        starts = starts.at[g].set(cnt)
        tabs = tuple(t.at[g].set(p[n]) for t, n in zip(tabs, names))
        return (pos + p["advance"], g + 1, cnt + p["length"], starts) + tabs

    with jax.named_scope(PHASE1):
        init = (jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.full((MG,), out_len_max, jnp.int32),  # sentinel = out_len_max
                *[jnp.zeros((MG,), f.dtype) for f in spec.fields])
        final = lax.while_loop(cond, body, init)
    starts, tabs = final[3], final[4:]

    # ---- Phase 2: all-lane expansion --------------------------------------
    with jax.named_scope(PHASE2):
        # lane->group map: scatter a 1 at every group start, prefix-sum.
        marker = jnp.zeros((out_len_max + 1,), jnp.int32).at[starts].add(1)
        grp = jnp.cumsum(marker[:out_len_max]) - 1
        idx = jnp.arange(out_len_max, dtype=jnp.int32)
        k = idx - jnp.take(starts, grp, mode="clip")
        fields = {n: jnp.take(t, grp, mode="clip")
                  for n, t in zip(names, tabs)}
        out = spec.express(comp, fields, k, width)
        return jnp.where(idx < out_len_dyn, out, 0).astype(dt)


@jax.custom_batching.custom_vmap
def lane_take(a: jnp.ndarray, i: jnp.ndarray) -> jnp.ndarray:
    """``jnp.take(a, i, mode="clip")`` from one chunk's 1-D lanes ``a``.

    Under ``vmap`` over chunks it gathers from the rows laid end to end,
    one flat gather with a 1-D index: on the v5e a third faster than the
    batched gather ``vmap`` makes of ``take`` (17 doubling rounds over
    38 x 131,072 lanes: 0.61 s against 0.87 s)."""
    return jnp.take(a, i, mode="clip")


@lane_take.def_vmap
def _lane_take_rows(axis_size, in_batched, a, i):
    a_batched, i_batched = in_batched
    if not a_batched:                      # one table for every row
        return jnp.take(a, i, mode="clip"), i_batched
    if not i_batched:
        i = jnp.broadcast_to(i, (axis_size,) + i.shape)
    n = a.shape[1]
    if axis_size * n >= 2 ** 31:           # flat offsets past int32
        return jax.vmap(lambda r, j: jnp.take(r, j, mode="clip"))(a, i), True
    rows = jnp.arange(axis_size, dtype=jnp.int32).reshape(
        (axis_size,) + (1,) * (i.ndim - 1)) * n
    flat = (jnp.clip(i, 0, n - 1).astype(jnp.int32) + rows).reshape(-1)
    return a.reshape(-1).at[flat].get(mode="promise_in_bounds").reshape(
        i.shape), True


def resolve_backrefs(starts: jnp.ndarray, is_match: jnp.ndarray,
                     dist: jnp.ndarray, src_base: jnp.ndarray, src_step: int,
                     out_len_max: int, unroll: int = 1) -> jnp.ndarray:
    """All-lane Phase 2 of an LZ command list: each output lane's source.

    Command ``g`` covers lanes ``[starts[g], starts[g+1])``; unused slots
    hold the sentinel ``out_len_max``.  Lane ``k`` of a literal command
    reads source ``src_base[g] + k * src_step``; a match lane copies the
    lane ``dist[g]`` back.  A marker scatter and a prefix sum map every lane
    to its command; match lanes then point back, literal lanes point at
    themselves, and ``ceil(log2(out_len_max))`` pointer-doubling rounds
    (``p = p[p]``) resolve overlapping and chained back-references, since
    every chain strictly decreases and ends at a literal lane (extra rounds
    are idempotent).  ``unroll`` gathers fuse into one loop step.  Returns
    the source of every lane: the caller gathers all values at once.
    """
    marker = jnp.zeros((out_len_max + 1,), jnp.int32).at[starts].add(1)
    grp = jnp.cumsum(marker[:out_len_max]) - 1
    idx = jnp.arange(out_len_max, dtype=jnp.int32)
    # folded per command, so each lane gathers two values: how far back it
    # points (0 on literal lanes) and its source less idx * src_step
    back = lane_take(jnp.where(is_match, dist, 0), grp)
    src = lane_take(src_base - starts * src_step, grp) + idx * src_step

    ptr = jnp.maximum(idx - back, 0)
    rounds = max(1, (out_len_max - 1).bit_length())

    def dbl(r, p):
        for _ in range(unroll):   # static unroll inside one loop step
            p = lane_take(p, p)
        return p

    ptr = lax.fori_loop(0, -(-rounds // unroll), dbl, ptr)
    return lane_take(src, ptr)


def group_serial_chunk(spec: TwoPhaseSpec, comp: jnp.ndarray, out_len_dyn,
                       out_len_max: int, width: int) -> jnp.ndarray:
    """Paper-faithful sequential reference: serial across groups, vector-
    parallel within each (the warp's collaborative write, §II-B)."""
    dt = DEV_DTYPE[width]
    W = spec.max_group_len
    names = [f.name for f in spec.fields]
    lanes = jnp.arange(W, dtype=jnp.int32)

    def cond(s):
        return s[1] < out_len_dyn

    def body(s):
        pos, cnt, buf = s
        p = spec.parse(comp, pos, width)
        fields = {n: p[n] for n in names}     # scalars broadcast over lanes
        vals = spec.express(comp, fields, lanes, width).astype(dt)
        cur = lax.dynamic_slice(buf, (cnt,), (W,))
        new = jnp.where(lanes < p["length"], vals, cur)
        buf = lax.dynamic_update_slice(buf, new, (cnt,))
        return pos + p["advance"], cnt + p["length"], buf

    buf0 = jnp.zeros((out_len_max + W,), dt)
    _, _, buf = lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(0), buf0))
    return buf[:out_len_max]


# --------------------------------------------------------------------------
# Epilogue: a consumer transform fused into the decode dispatch
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Post-decode transform applied to the raw ``(num_chunks, chunk_elems)``
    matrix INSIDE the decode dispatch (same jit computation — XLA fuses the
    elementwise tail into the decode kernels, so the intermediate uint matrix
    is never materialized for consumers that don't want it).

    Hashable and static to the jit cache; array operands ride the device
    pytree under the caller-chosen ``scale_key`` / ``zero_key`` entries
    (scalars or anything broadcastable to the chunk matrix).  Application
    order:

      1. ``view_dtype``  — bitcast reinterpretation, same itemsize
                           (e.g. the uint8 decode dtype viewed as int8)
      2. ``out_dtype``   — value cast; with scale/zero set this is also the
                           compute dtype of the dequant affine (default
                           float32), i.e. the bit-width widening step
      3. zero/scale      — ``(x - zero) * scale`` (dequantization)
      4. ``fn``          — escape hatch: ``fn(out, dev) -> out`` (compared
                           by identity for jit caching)

    Dtypes are stored as strings so specs hash/compare cleanly.
    """

    view_dtype: Optional[str] = None
    out_dtype: Optional[str] = None
    scale_key: Optional[str] = None
    zero_key: Optional[str] = None
    fn: Optional[Callable[..., jnp.ndarray]] = None

    def apply(self, out: jnp.ndarray, dev: Dict[str, Any]) -> jnp.ndarray:
        with jax.named_scope(EPILOGUE):
            if self.view_dtype is not None:
                out = jax.lax.bitcast_convert_type(
                    out, jnp.dtype(self.view_dtype))
            if self.scale_key is not None or self.zero_key is not None:
                out = out.astype(jnp.dtype(self.out_dtype or "float32"))
                if self.zero_key is not None:
                    out = out - dev[self.zero_key].astype(out.dtype)
                if self.scale_key is not None:
                    out = out * dev[self.scale_key].astype(out.dtype)
            elif self.out_dtype is not None:
                out = out.astype(jnp.dtype(self.out_dtype))
            if self.fn is not None:
                out = self.fn(out, dev)
            return out


# --------------------------------------------------------------------------
# DecodeSpec: the backend-complete decode contract a codec registers
# --------------------------------------------------------------------------

BodyFn = Callable[..., jnp.ndarray]   # (inputs, consts, out_len, *, chunk_elems, width, bits)


@dataclasses.dataclass(frozen=True)
class Tunable:
    """One kernel knob a codec exposes to the offline autotuner.

    ``name`` must not collide with the framework's own knobs
    (``core.tuning.KNOWN_KNOBS``); ``candidates`` is the value grid the
    autotuner searches, ``default`` what the kernel uses when the tuned
    table has no entry and the caller passed nothing.  Values reach the
    codec's ``pallas_override`` (or the generic wrapper) through the static
    ``tune`` tuple, so they are compile-time constants to the kernel.
    """

    name: str
    candidates: Tuple[Any, ...]
    default: Any


def _default_inputs(dev: Dict[str, Any]) -> Tuple[jnp.ndarray, ...]:
    return (dev["comp"],)


def words_inputs(dev: Dict[str, Any]) -> Tuple[jnp.ndarray, ...]:
    """Chunk-input hook for bit codecs: the uint32 word view of each row."""
    words = dev.get("comp_words")
    if words is None:
        words = words_view(dev["comp"])
    return (words,)


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """Per-backend chunk bodies plus the device-operand layout.

    Every body maps ``(inputs, consts, out_len)`` for ONE chunk to a
    ``(chunk_elems,)`` row in ``DEV_DTYPE[width]``.  ``chunk_inputs`` pulls
    the per-chunk operand arrays (leading dim = num_chunks) out of the
    device pytree; ``consts`` supplies broadcast tables replicated to every
    grid cell (Pallas kernels may not capture array constants).
    """

    body: BodyFn
    body_oracle: Optional[BodyFn] = None      # sequential ref; falls back to body
    chunk_inputs: Callable[[Dict[str, Any]], Tuple[jnp.ndarray, ...]] = _default_inputs
    consts: Callable[[], Tuple[jnp.ndarray, ...]] = tuple
    # optional hand-tuned pallas kernel (e.g. bitpack's output-tiled one);
    # everything else rides the generic chunk-per-grid-cell wrapper.
    pallas_override: Optional[Callable[..., jnp.ndarray]] = None
    # codec-default Epilogue fused into every dispatch unless the caller
    # passes its own (``ops.decode(..., epilogue=)`` overrides).
    epilogue: Optional[Epilogue] = None
    # kernel knobs this codec exposes to the offline autotuner
    # (``core.tuning``); values arrive via the static ``tune`` tuple.
    tunables: Tuple[Tunable, ...] = ()

    @classmethod
    def from_two_phase(cls, spec: TwoPhaseSpec,
                       oracle: Optional[Callable[..., jnp.ndarray]] = None,
                       ) -> "DecodeSpec":
        """All three backends from a parse + express pair.

        ``oracle`` optionally swaps in a handwritten sequential reference
        (signature ``(comp, out_len_dyn, out_len_max, width)``); by default
        the generic group-serial driver is used.
        """
        def body(inputs, consts, out_len, *, chunk_elems, width, bits):
            return two_phase_chunk(spec, inputs[0], out_len, chunk_elems, width)

        def body_oracle(inputs, consts, out_len, *, chunk_elems, width, bits):
            fn = oracle or functools.partial(group_serial_chunk, spec)
            return fn(inputs[0], out_len, chunk_elems, width)

        return cls(body=body, body_oracle=body_oracle)


def run(spec: DecodeSpec, dev: Dict[str, Any], *, width: int,
        chunk_elems: int, backend: str, interpret: bool,
        bits: int, epilogue: Optional[Epilogue] = None,
        tune: Tuple[Tuple[str, Any], ...] = ()) -> jnp.ndarray:
    """Decode every chunk of a device table through one DecodeSpec backend.

    ``epilogue`` (caller's, falling back to the spec's default) is applied
    to the chunk matrix inside the same computation — fused by XLA into the
    dispatch, so no raw uint intermediate reaches the consumer.

    ``tune``: sorted ``((knob, value), ...)`` of kernel knobs — the generic
    wrapper's ``num_stages`` plus any codec ``Tunable``s — resolved by the
    caller (``core.tuning.kernel_tune``).  Static: new values are new
    compilations.  Kernel knobs shape only the Pallas launch; the XLA and
    oracle backends ignore them (the decoded values are knob-
    independent by the conformance gate)."""
    inputs = spec.chunk_inputs(dev)
    consts = tuple(spec.consts())
    out_lens = dev["out_lens"]
    epilogue = epilogue if epilogue is not None else spec.epilogue
    if backend == "pallas":
        kernel = spec.pallas_override or _generic_pallas
        out = kernel(spec.body, inputs, consts, out_lens,
                     chunk_elems=chunk_elems, width=width, bits=bits,
                     interpret=interpret, tune=tune)
        return epilogue.apply(out, dev) if epilogue is not None else out
    body = {"xla": spec.body,
            "oracle": spec.body_oracle or spec.body}[backend]
    n_in = len(inputs)

    def one(*rows):
        return body(rows[:n_in], consts, rows[n_in],
                    chunk_elems=chunk_elems, width=width, bits=bits)

    out = jax.vmap(one)(*inputs, out_lens)
    return epilogue.apply(out, dev) if epilogue is not None else out


def _generic_pallas(body: BodyFn, inputs, consts, out_lens, *,
                    chunk_elems: int, width: int, bits: int,
                    interpret: bool,
                    tune: Tuple[Tuple[str, Any], ...] = ()) -> jnp.ndarray:
    """The single generic ``pallas_call`` wrapper, pipelined.

    Grid cell g decodes a *block* of ``num_stages`` consecutive chunks:
    per-chunk operands tile ``(num_stages, row)``, so one HBM->VMEM DMA
    brings the whole block in while the previous block is still decoding —
    Pallas's grid-step double buffering, with the DMA granularity (and so
    how much decode latency each transfer hides behind) exposed as the
    ``num_stages`` tunable.  ``num_stages=1`` is the original chunk-per-cell
    launch; broadcast constants replicate with a constant index map either
    way.  Under ``interpret=True`` the knob falls back to the single-stage
    path (the CPU validation grid stays exactly the hand-checked one)
    unless the ``interpret_pipeline`` tune flag forces it — how the
    conformance suite exercises the multi-stage body off-TPU.
    """
    knobs = dict(tune)
    num_stages = int(knobs.get("num_stages", 1))
    if interpret and not knobs.get("interpret_pipeline", 0):
        num_stages = 1
    n = inputs[0].shape[0]
    num_stages = max(1, min(num_stages, max(1, n)))
    pad = -n % num_stages
    if pad:
        # zero rows decode to nothing (out_lens 0 -> every body exits
        # immediately), the same convention as the plan's row padding
        inputs = tuple(jnp.pad(a, ((0, pad), (0, 0))) for a in inputs)
        out_lens = jnp.pad(out_lens, (0, pad))
    n_pad = n + pad
    n_in = len(inputs)
    consts2d = [jnp.asarray(c).reshape(1, -1) for c in consts]

    def kernel(*refs):
        in_refs, lens_ref = refs[:n_in], refs[n_in]
        const_refs = refs[n_in + 1: n_in + 1 + len(consts2d)]
        out_ref = refs[-1]
        cs = tuple(r[0, :] for r in const_refs)
        for s in range(num_stages):      # unrolled: static trip count
            rows = tuple(r[s, :] for r in in_refs)
            out_ref[s, :] = body(rows, cs, lens_ref[s, 0],
                                 chunk_elems=chunk_elems, width=width,
                                 bits=bits)

    in_specs = [pl.BlockSpec((num_stages, a.shape[1]), lambda i: (i, 0))
                for a in inputs]
    in_specs.append(pl.BlockSpec((num_stages, 1), lambda i: (i, 0)))
    in_specs += [pl.BlockSpec((1, c.shape[1]), lambda i: (0, 0))
                 for c in consts2d]
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // num_stages,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((num_stages, chunk_elems), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, chunk_elems),
                                       DEV_DTYPE[width]),
        interpret=interpret,
    )(*inputs, out_lens.reshape(-1, 1), *consts2d)
    return out[:n] if pad else out
