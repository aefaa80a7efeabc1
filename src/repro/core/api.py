"""Public compress/decompress API.

    from repro.core import api
    blob = api.compress(arr, "rle_v2")          # host-side encode
    out  = api.decompress(blob)                 # device decode, == arr

    cas  = api.compress_many(arrs, "rle_v2")    # list in, list out
    outs = api.decompress_many(cas)             # ONE dispatch per codec group

8-byte dtypes are plane-decomposed (lo/hi uint32 planes compressed as two
blobs) so RLE runs survive — see DESIGN.md §2 format notes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Union

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import encoders as enc
from repro.core import format as fmt
from repro.core import plan as plan_mod
from repro.core import registry
from repro.core.engine import CodagEngine, EngineConfig


@dataclasses.dataclass
class CompressedArray:
    """One logical array; 1 blob normally, 2 plane blobs for 8-byte dtypes."""
    blobs: list
    orig_dtype: str
    orig_shape: tuple

    @property
    def ratio(self) -> float:
        comp = sum(b.compressed_bytes for b in self.blobs)
        unc = sum(b.uncompressed_bytes for b in self.blobs)
        return comp / max(1, unc)

    @property
    def compressed_bytes(self) -> int:
        return sum(b.compressed_bytes for b in self.blobs)


def compress(arr: np.ndarray, codec: str,
             chunk_bytes: Optional[int] = None,
             bits: Optional[int] = None) -> CompressedArray:
    """Compress one array.  ``chunk_bytes=None`` resolves the tuned chunk
    size for this codec/width/device from ``core.tuning``'s committed
    defaults table, falling back to ``format.DEFAULT_CHUNK_BYTES``; an
    explicit value always wins (``encoders.compress`` resolution)."""
    if arr.dtype.itemsize == 8 and registry.get(codec).plane_decompose_64:
        # plane decomposition: lo/hi u32 planes keep runs intact
        as_u64 = arr.reshape(-1).view(np.uint64)
        lo = (as_u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (as_u64 >> np.uint64(32)).astype(np.uint32)
        return CompressedArray(
            blobs=[enc.compress(lo, codec, chunk_bytes),
                   enc.compress(hi, codec, chunk_bytes)],
            orig_dtype=str(arr.dtype), orig_shape=tuple(arr.shape))
    return CompressedArray(blobs=[enc.compress(arr, codec, chunk_bytes, bits=bits)],
                           orig_dtype=str(arr.dtype), orig_shape=tuple(arr.shape))


def _combine(ca: CompressedArray, outs: List[np.ndarray]) -> np.ndarray:
    return fmt.combine_planes(outs, ca.orig_dtype, ca.orig_shape)


def _combine_device(ca: CompressedArray, outs: List, transformed: bool):
    if transformed:
        # epilogue output: plane recombination over transformed values is
        # undefined — refuse rather than silently drop the hi plane
        if len(outs) != 1:
            raise ValueError(
                f"epilogue cannot be applied to a plane-decomposed "
                f"{ca.orig_dtype} array ({len(outs)} plane blobs): the "
                "transform runs per uint32 plane, so the 64-bit value "
                "cannot be recombined afterwards")
        return outs[0]
    return fmt.combine_planes_device(outs, ca.orig_dtype, ca.orig_shape)


def decompress(ca: CompressedArray,
               engine: Optional[CodagEngine] = None,
               device_out: bool = False):
    engine = engine or CodagEngine(EngineConfig())
    if device_out:
        return _combine_device(ca, [engine.decompress_device(b)
                                    for b in ca.blobs], transformed=False)
    return _combine(ca, [engine.decompress(b) for b in ca.blobs])


def compress_many(arrays: Sequence[np.ndarray],
                  codec: Union[str, Sequence[str]],
                  chunk_bytes: Optional[int] = None,
                  bits: Optional[int] = None) -> List[CompressedArray]:
    """Compress a list of arrays; ``codec`` may be one name or one per array.

    Encoding stays a host/offline concern (as in the paper); the point of the
    list form is that the resulting blobs land in the batched decode path.
    """
    codecs = [codec] * len(arrays) if isinstance(codec, str) else list(codec)
    if len(codecs) != len(arrays):
        raise ValueError(f"{len(codecs)} codecs for {len(arrays)} arrays")
    return [compress(a, c, chunk_bytes, bits=bits)
            for a, c in zip(arrays, codecs)]


def decompress_many(cas: Sequence[CompressedArray],
                    engine: Optional[CodagEngine] = None,
                    service=None, *, device_out: bool = False,
                    epilogue=None, epilogue_operands=None,
                    mesh=None, mesh_axis: Optional[str] = None,
                    out_shardings=None) -> List:
    """Batched decompress: every chunk of every array in one launch per
    (codec, width, chunk_elems, bits) group — the CODAG provisioning move.
    All paths lower to one ``core.plan.DecodePlan``.

    With no ``engine``, a host-out call routes through the process-wide
    ``server.default_service()`` (or an explicit ``service=``): all blobs
    enter ONE micro-batch window atomically — same one-dispatch-per-group
    accounting as the direct plan, plus the service's decoded-blob cache
    and coalescing with any other concurrently-submitted requests.  Passing
    an ``engine`` keeps the direct synchronous plan path (exact per-call
    dispatch control, custom engine configs).

    ``device_out=True`` returns device-resident jax arrays — decode,
    per-blob scatter, 64-bit plane recombination, and the optional fused
    ``epilogue`` (a ``kernels.harness.Epilogue``: cast / widen / dequant
    inside the decode dispatch) all happen on device with zero device→host
    syncs.  An explicit ``service=`` serves device views through its window
    machinery; otherwise the direct plan path runs (epilogues are
    plan-path only — a service window mixes tenants that may want
    different transforms).

    ``mesh`` (implies device out) decodes every group's chunk rows across
    the mesh's ``mesh_axis`` devices (``DecodePlan.execute_sharded``) —
    the multi-device provisioning move; ``out_shardings`` (one sharding or
    one per array, ``None`` entries allowed) commits each output under the
    requested ``NamedSharding`` — the plan's *place* stage — so results
    are born where the consumer wants them.

    Bit-exact vs. per-array ``decompress``; outputs follow input order.
    """
    if engine is not None and service is not None:
        raise ValueError("pass engine= OR service=, not both: the service "
                         "decodes on its own engine")
    device_out = device_out or mesh is not None
    if epilogue is not None and not device_out:
        raise ValueError("epilogue requires device_out=True: a fused "
                         "epilogue's output has no host reassembly path")
    if out_shardings is not None and not device_out:
        raise ValueError("out_shardings requires device_out=True (or "
                         "mesh=): host arrays have no device placement")
    if not cas:
        return []
    if service is not None or (engine is None and not device_out):
        if epilogue is not None:
            raise ValueError("epilogue is not supported on the service "
                             "path; pass engine= (or no engine) with "
                             "device_out=True")
        if mesh is not None or out_shardings is not None:
            raise ValueError("mesh/out_shardings are not supported on the "
                             "service path; pass engine= (or no engine) "
                             "for the direct plan executors")
        if service is None:
            from repro.core import server as server_mod
            service = server_mod.default_service()
        return service.decode_arrays(cas, device_out=device_out)
    flat: List[fmt.CompressedBlob] = []
    spans: List[tuple] = []   # (start, count) into flat, per array
    for ca in cas:
        spans.append((len(flat), len(ca.blobs)))
        flat.extend(ca.blobs)
    per_array = (plan_mod.as_shard_list(out_shardings, len(cas),
                                        what="arrays")
                 or [None] * len(cas))
    if device_out:
        plan = plan_mod.DecodePlan.build(flat)
        # single-blob arrays place inside the plan (born under their
        # sharding); plane-decomposed arrays place after recombination.
        blob_sh: List = [None] * len(flat)
        for (s, n), sh in zip(spans, per_array):
            if sh is not None and n == 1:
                blob_sh[s] = sh
        if mesh is not None:
            outs = plan.execute_sharded(
                mesh, axis=mesh_axis, engine=engine, epilogue=epilogue,
                epilogue_operands=epilogue_operands, out_shardings=blob_sh)
        else:
            outs = plan.execute_device(
                engine, epilogue=epilogue,
                epilogue_operands=epilogue_operands, out_shardings=blob_sh)
        results = []
        with TraceAnnotation("codag.api.combine", plan=plan.plan_id,
                             arrays=len(cas)):
            for ca, (s, n), sh in zip(cas, spans, per_array):
                out = _combine_device(ca, outs[s:s + n], epilogue is not None)
                if (sh is not None and n > 1
                        and plan_mod.placeable(out.shape, sh)):
                    import jax
                    out = jax.device_put(out, sh)
                results.append(out)
        return results
    outs = plan_mod.decompress_blobs(flat, engine)
    return [_combine(ca, outs[s:s + n]) for ca, (s, n) in zip(cas, spans)]
