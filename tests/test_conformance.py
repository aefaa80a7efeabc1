"""Golden-vector conformance suite: every registry codec, every backend.

``tests/vectors/<codec>.json`` holds small committed fixtures: raw input
bytes, deterministic encode parameters, and the content digest of the
encoded blob (``server.blob_digest``).  The suite locks two guarantees:

  * encoder conformance — re-encoding a vector reproduces the committed
    digest bit-for-bit (format drift cannot slip in silently; regenerate
    with ``scripts/make_vectors.py`` ONLY on an intentional format change);
  * decoder conformance — every backend (xla / oracle in the fast tier,
    pallas nightly) decodes every vector back to the original
    bytes exactly.

A codec present in ``registry.names()`` with no committed vectors fails
loudly here (and in ``scripts/check_registry.py``).
"""
import base64
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import encoders as enc, registry
from repro.core.server import blob_digest
from repro.kernels import ops

VEC_DIR = Path(__file__).parent / "vectors"
ALL_CODECS = registry.names()

# interpret-mode pallas is seconds per case -> nightly tier, same split as
# test_codecs.py.
BACKENDS = [
    "xla", "oracle",
    pytest.param("pallas", marks=pytest.mark.slow),
]


def load_vectors(codec: str):
    path = VEC_DIR / f"{codec}.json"
    if not path.exists():
        pytest.fail(
            f"codec {codec!r} is registered but has NO golden vectors at "
            f"{path} — run scripts/make_vectors.py and commit the fixtures")
    payload = json.loads(path.read_text())
    assert payload["codec"] == codec
    return payload["vectors"]


def vector_array(vec) -> np.ndarray:
    raw = base64.b64decode(vec["data_b64"])
    return np.frombuffer(raw, np.dtype(vec["dtype"])) \
             .reshape(vec["shape"]).copy()


@pytest.mark.parametrize("codec", ALL_CODECS)
def test_every_codec_has_vectors(codec):
    vectors = load_vectors(codec)
    assert len(vectors) >= 5, \
        f"{codec}: expected a full vector matrix, found {len(vectors)}"
    names = {v["name"] for v in vectors}
    # the generic edge-case set every codec must commit
    for required in ("runs_u32", "random_u8", "single_u32", "empty_u32"):
        assert required in names, f"{codec}: missing vector {required!r}"


@pytest.mark.parametrize("codec", ALL_CODECS)
def test_encoder_matches_golden_digest(codec):
    """Encoding a committed input reproduces the committed blob digest."""
    for vec in load_vectors(codec):
        arr = vector_array(vec)
        blob = enc.compress(arr, codec, vec["chunk_bytes"], bits=vec["bits"])
        assert blob.num_chunks == vec["num_chunks"], vec["name"]
        assert blob_digest(blob) == vec["blob_digest"], (
            f"{codec}/{vec['name']}: encoder output drifted from the "
            f"committed golden vector (intentional format change? "
            f"regenerate with scripts/make_vectors.py)")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("codec", ALL_CODECS)
def test_decode_conformance_all_backends(codec, backend):
    """Every vector round-trips bit-exactly on every decode backend."""
    for vec in load_vectors(codec):
        arr = vector_array(vec)
        blob = enc.compress(arr, codec, vec["chunk_bytes"], bits=vec["bits"])
        got = ops.decode_blob(blob, backend=backend)
        assert got.dtype == arr.dtype, f"{codec}/{backend}/{vec['name']}"
        assert got.shape == arr.shape, f"{codec}/{backend}/{vec['name']}"
        assert np.array_equal(got, arr), f"{codec}/{backend}/{vec['name']}"


def test_no_orphan_vector_files():
    """Every committed vector file corresponds to a registered codec."""
    names = set(ALL_CODECS)
    for path in VEC_DIR.glob("*.json"):
        assert path.stem in names, (
            f"vector file {path.name} has no registered codec — stale "
            f"fixture or missing plugin registration")
