"""Golden-stream regression fixtures: committed seeded streams + state
digests for BOTH ingest paths.

``stream/ingest.select_path`` dispatches each chunk to either the
``lax.scan`` reference body or the VMEM-resident Pallas kernel.  Numeric
drift in either path (a refactor reordering the einsums, a kernel tweak, a
dtype slip) would silently change every downstream artifact while all the
tolerance-based tests keep passing.  These tests pin the EXACT bits: each
committed fixture is a small seeded stream plus the blake2b digest of the
final FIGMNState under each path, and the tier-1 suite fails on the first
bit that moves.

Digests are platform-pinned to CPU (conftest sets JAX_PLATFORMS=cpu), the
backend every CI and container run uses, and to the jax version recorded
in digests.json (XLA may reorder float reductions between releases).
After an INTENTIONAL numeric change or a jax upgrade, regenerate and
commit:

    PYTHONPATH=src python tests/test_golden_streams.py --regen

(see tests/README.md for when that is and is not acceptable).
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import figmn
from repro.core.types import FIGMNConfig
from repro.stream import RuntimeConfig, StreamRuntime, select_path

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
DIGESTS = os.path.join(GOLDEN_DIR, "digests.json")

#: fixture streams: (name, n, d, modes, chunk) — small enough to run in
#: milliseconds, structured enough to exercise creation + updates + the
#: runt tail chunk (n not divisible by chunk).
FIXTURES = (("blobs_small", 96, 3, 3, 32),
            ("blobs_tail", 110, 5, 2, 32))
PATHS = ("scan", "vmem")


def _make_stream(name: str, n: int, d: int, modes: int) -> np.ndarray:
    # (python's str hash is process-salted — derive the seed stably)
    rng = np.random.default_rng(
        int.from_bytes(hashlib.blake2b(name.encode(),
                                       digest_size=4).digest(), "little"))
    centers = rng.normal(0, 6.0, (modes, d))
    x = centers[rng.integers(0, modes, n)] + rng.normal(0, 1.0, (n, d))
    return x.astype(np.float32)


def _cfg(x: np.ndarray) -> FIGMNConfig:
    return FIGMNConfig(kmax=8, dim=x.shape[1], beta=0.1, delta=1.0,
                       vmin=1e9, spmin=0.0, update_mode="exact",
                       sigma_ini=figmn.sigma_from_data(jnp.asarray(x), 1.0))


def _digest(state) -> str:
    h = hashlib.blake2b(digest_size=16)
    for name in ("mu", "lam", "logdet", "sp", "v"):
        h.update(np.ascontiguousarray(
            np.asarray(getattr(state, name))).tobytes())
    h.update(np.asarray(state.active).astype(np.uint8).tobytes())
    h.update(np.asarray(state.n_created, np.int32).tobytes())
    return h.hexdigest()


def _run(x: np.ndarray, path: str, chunk: int):
    rt = StreamRuntime(_cfg(x), RuntimeConfig(chunk=chunk, path=path))
    rt.ingest(x)
    return rt.state


def regen() -> dict:
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    doc = {"jax_version": jax.__version__, "fixtures": {}}
    for name, n, d, modes, chunk in FIXTURES:
        x = _make_stream(name, n, d, modes)
        np.savez(os.path.join(GOLDEN_DIR, f"{name}.npz"), x=x)
        entry = {"n": n, "d": d, "modes": modes, "chunk": chunk,
                 "digests": {}}
        for path in PATHS:
            state = _run(x, path, chunk)
            entry["digests"][path] = _digest(state)
            entry[f"n_active_{path}"] = int(state.n_active)
        doc["fixtures"][name] = entry
    with open(DIGESTS, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


def _load():
    if not os.path.exists(DIGESTS):
        pytest.fail(f"golden digests missing ({DIGESTS}); regenerate with "
                    f"PYTHONPATH=src python tests/test_golden_streams.py "
                    f"--regen and commit the result")
    with open(DIGESTS) as f:
        return json.load(f)


@pytest.mark.parametrize("name,n,d,modes,chunk", FIXTURES)
@pytest.mark.parametrize("path", PATHS)
def test_ingest_paths_reproduce_golden_digests(name, n, d, modes, chunk,
                                               path):
    """Both dispatch targets of select_path must reproduce the committed
    bits exactly — tolerance tests cannot catch slow numeric drift."""
    doc = _load()
    entry = doc["fixtures"][name]
    with np.load(os.path.join(GOLDEN_DIR, f"{name}.npz")) as z:
        x = z["x"]
    assert x.shape == (n, d), "fixture stream changed shape"
    state = _run(x, path, entry["chunk"])
    assert _digest(state) == entry["digests"][path], (
        f"{path} ingest path drifted from the golden digest on {name} "
        f"(recorded under jax {doc.get('jax_version')}, running "
        f"{jax.__version__}): if intentional, regenerate via --regen and "
        f"explain the change")
    assert int(state.n_active) == entry[f"n_active_{path}"]


def test_committed_stream_matches_generator():
    """The .npz fixtures themselves are pinned: regenerating the stream
    from the seed must reproduce the committed bytes (guards against a
    fixture being hand-edited or a generator change going unnoticed)."""
    doc = _load()
    for name, n, d, modes, chunk in FIXTURES:
        with np.load(os.path.join(GOLDEN_DIR, f"{name}.npz")) as z:
            np.testing.assert_array_equal(z["x"],
                                          _make_stream(name, n, d, modes))
        assert doc["fixtures"][name]["chunk"] == chunk


def test_select_path_dispatch_contract():
    """The dispatch guard itself: forced paths are honoured verbatim; auto
    never picks the kernel off-TPU (interpret mode is a correctness path,
    not a fast path); unknown requests fail loudly."""
    x = _make_stream("blobs_small", 96, 3, 3)
    cfg = _cfg(x)
    assert select_path(cfg, requested="scan") == "scan"
    assert select_path(cfg, requested="vmem") == "vmem"
    assert select_path(cfg, requested="auto") == "scan"   # CPU container
    with pytest.raises(ValueError):
        select_path(cfg, requested="mmap")


def test_scan_and_vmem_agree_within_tolerance():
    """Digest tests pin bits per-path; this pins the PATHS to each other:
    on a creation-free segment the kernel must track the reference closely
    (it is the same math, different memory schedule)."""
    name, n, d, modes, chunk = FIXTURES[0]
    x = _make_stream(name, n, d, modes)
    s_scan = _run(x, "scan", chunk)
    s_vmem = _run(x, "vmem", chunk)
    act = np.asarray(s_scan.active)
    # the kernel cannot create components mid-chunk, so pools can differ
    # in size; compare the slots both paths own
    both = act & np.asarray(s_vmem.active)
    assert both.any()
    np.testing.assert_allclose(np.asarray(s_scan.mu)[both],
                               np.asarray(s_vmem.mu)[both], atol=5e-2)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="regenerate tests/golden/ fixtures + digests")
    args = ap.parse_args()
    if args.regen:
        doc = regen()
        print(json.dumps(doc, indent=1))
    else:
        ap.error("nothing to do (did you mean --regen?)")
