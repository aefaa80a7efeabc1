"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
sweeping shapes and dtypes, exactly as the kernel contract requires."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoders as enc, format as fmt, registry
from repro.kernels import bitpack, ops, ref

RNG = np.random.default_rng(3)


def _gen(kind: str, n: int, dtype):
    info = np.iinfo(dtype)
    if kind == "runs":
        v = RNG.integers(0, min(50, info.max), max(1, n // 20)).astype(dtype)
        out = np.repeat(v, RNG.integers(1, 40, len(v)))
    elif kind == "random":
        out = RNG.integers(0, info.max, n, endpoint=True).astype(dtype)
    elif kind == "delta":
        out = (np.arange(n) * 5 + 11).astype(dtype)
    else:  # mixed
        out = np.concatenate([
            np.repeat(dtype(3), n // 3),
            RNG.integers(0, info.max, n // 3, endpoint=True).astype(dtype),
            (np.arange(n - 2 * (n // 3)) * 2).astype(dtype)])
    return out[:n] if len(out) >= n else np.pad(out, (0, n - len(out)))


def _decode_both(blob: fmt.CompressedBlob, codec):
    dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
    bits = registry.get(codec).static_bits(blob)
    pallas_out = ops.decode(dev, codec=codec, width=blob.width,
                            chunk_elems=blob.chunk_elems, backend="pallas",
                            interpret=True, bits=bits)
    oracle_out = ops.decode(dev, codec=codec, width=blob.width,
                            chunk_elems=blob.chunk_elems, backend="oracle",
                            bits=bits)
    return np.asarray(pallas_out), np.asarray(oracle_out), blob


@pytest.mark.parametrize("codec", [fmt.RLE_V1, fmt.RLE_V2, fmt.DBP])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.uint32])
@pytest.mark.parametrize("kind", ["runs", "random", "delta", "mixed"])
@pytest.mark.parametrize("n,chunk_bytes", [
    (257, 256),
    pytest.param(1024, 512, marks=pytest.mark.slow),
    pytest.param(4096, 2048, marks=pytest.mark.slow)])
def test_two_phase_kernel_vs_oracle(codec, dtype, kind, n, chunk_bytes):
    """Two-phase harness codecs: Pallas (interpret) vs sequential oracle."""
    arr = _gen(kind, n, dtype)
    blob = enc.compress(arr, codec, chunk_bytes=chunk_bytes)
    got_pallas, got_oracle, blob = _decode_both(blob, codec)
    # valid region comparison per chunk (tail of last chunk is padding)
    for i in range(blob.num_chunks):
        ol = int(blob.out_lens[i])
        np.testing.assert_array_equal(got_pallas[i, :ol], got_oracle[i, :ol],
                                      err_msg=f"chunk {i}")
    flat = got_pallas.reshape(-1)[:blob.total_elems]
    np.testing.assert_array_equal(flat.astype(dtype), arr.view(dtype))


@pytest.mark.parametrize("kind", ["runs", "random", "mixed"])
@pytest.mark.parametrize("n,chunk_bytes", [
    (700, 512), pytest.param(3000, 1024, marks=pytest.mark.slow)])
def test_tdeflate_kernel_vs_oracle(kind, n, chunk_bytes):
    arr = _gen(kind, n, np.uint8)
    blob = enc.compress(arr, fmt.TDEFLATE, chunk_bytes=chunk_bytes)
    got_pallas, got_oracle, blob = _decode_both(blob, fmt.TDEFLATE)
    for i in range(blob.num_chunks):
        ol = int(blob.out_lens[i])
        np.testing.assert_array_equal(got_pallas[i, :ol], got_oracle[i, :ol])
    flat = got_pallas.reshape(-1)[:blob.total_elems]
    np.testing.assert_array_equal(flat, arr)


@pytest.mark.parametrize("bits", [1, 3, 7, 8, 13, 16, 24, 32])
@pytest.mark.parametrize("n", [100, 2048,
                               pytest.param(5000, marks=pytest.mark.slow)])
def test_bitpack_kernel_vs_oracle(bits, n):
    maxv = (1 << bits) - 1 if bits < 32 else 2 ** 32 - 1
    arr = RNG.integers(0, maxv, n, endpoint=True).astype(np.uint32)
    words = enc.pack_bits(arr.astype(np.uint64), bits)
    wj = jnp.asarray(np.concatenate([words, np.zeros(2, np.uint32)]))
    got_k = bitpack.unpack_pallas(wj[None], bits=bits, out_elems=n,
                                  interpret=True)[0]
    got_o = ref.unpack_bits(wj, n, bits)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(got_o))
    np.testing.assert_array_equal(np.asarray(got_o), arr)


def test_scalar_variant_matches_vectorized():
    """The two-phase kernels agree with the group-serial oracle on uint16
    data in 777-byte chunks."""
    for codec in (fmt.RLE_V1, fmt.RLE_V2, fmt.DBP):
        arr = _gen("mixed", 2000, np.uint16)
        blob = enc.compress(arr, codec, chunk_bytes=777)
        dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
        a = ops.decode(dev, codec=codec, width=blob.width,
                       chunk_elems=blob.chunk_elems, backend="xla")
        b = ops.decode(dev, codec=codec, width=blob.width,
                       chunk_elems=blob.chunk_elems, backend="oracle")
        for i in range(blob.num_chunks):
            ol = int(blob.out_lens[i])
            np.testing.assert_array_equal(np.asarray(a)[i, :ol],
                                          np.asarray(b)[i, :ol])


def test_tdeflate_scalar_matches():
    """tdeflate's two-phase decode agrees with the serial oracle."""
    arr = _gen("mixed", 1500, np.uint8)
    blob = enc.compress(arr, fmt.TDEFLATE, chunk_bytes=600)
    dev = {k: jnp.asarray(v) for k, v in blob.to_device().items()}
    a = ops.decode(dev, codec=fmt.TDEFLATE, width=1,
                   chunk_elems=blob.chunk_elems, backend="xla")
    b = ops.decode(dev, codec=fmt.TDEFLATE, width=1,
                   chunk_elems=blob.chunk_elems, backend="oracle")
    for i in range(blob.num_chunks):
        ol = int(blob.out_lens[i])
        np.testing.assert_array_equal(np.asarray(a)[i, :ol],
                                      np.asarray(b)[i, :ol])


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 384, 256),
                                   (128, 512, 384)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_dequant_matmul_kernel(M, K, N, dtype):
    """Fused int8-dequant matmul (hillclimb 2 hot spot) vs oracle."""
    from repro.kernels import dequant_matmul as dq
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(dtype))
    q = jnp.asarray(rng.integers(-127, 127, (K, N)).astype(np.int8))
    s = jnp.asarray(np.abs(rng.normal(size=(1, N))).astype(np.float32) * 0.01)
    got = dq.dequant_matmul(x, q, s, interpret=True)
    want = dq.ref_dequant_matmul(x, q, s)
    # split-K accumulation order differs from the single-sum oracle
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=1e-4)
