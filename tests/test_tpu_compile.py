"""Compile the main path's device programs for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached: it refuses what the chip would refuse (Mosaic
layouts, VMEM over the limit, HBM over capacity) without any chip time.
Each test lowers one program at the size a deployment runs and compiles it
for one v5e chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers each import
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import devices
from repro.core import figmn, inference, shortlist
from repro.core.types import FIGMNConfig, FIGMNState
from repro.kernels import figmn_stream
from repro.stream import ingest

V5E = "TPU v5 lite"
#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
HBM_BYTES = 16 * 10 ** 9
#: the paper's MNIST shape (configs/figmn_paper.py, Table 1)
PAPER_D = 784


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == V5E
    return SingleDeviceSharding(topo.devices[0])


def _cfg(k: int, d: int, sigma, c: int = 0) -> FIGMNConfig:
    return FIGMNConfig(kmax=k, dim=d, beta=0.001, delta=1.0, vmin=1e9,
                       spmin=0.0, update_mode="exact", shortlist_c=c,
                       sigma_ini=sigma)


def _shapes(sharding, k: int, d: int):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    state = FIGMNState(mu=s((k, d)), lam=s((k, d, d)), logdet=s((k,)),
                       sp=s((k,)), v=s((k,)), active=s((k,), jnp.bool_),
                       n_created=s((), jnp.int32))
    return s, state


def _largest_admitted_k(d: int, n: int) -> int:
    """The largest pool the vmem guard admits on a v5e at (D, chunk)."""
    budget = devices.lookup(V5E).vmem_bytes
    sigma = jnp.ones((d,), jnp.float32)
    k = 8
    while ingest.select_path(_cfg(k + 8, d, sigma), device="tpu",
                             vmem_budget=budget, chunk=n) == "vmem":
        k += 8
    assert ingest.select_path(_cfg(k, d, sigma), device="tpu",
                              vmem_budget=budget, chunk=n) == "vmem"
    return k


@pytest.mark.parametrize("case", ["ood_monitor", "guard_boundary_d16",
                                  "guard_boundary_d256",
                                  f"guard_boundary_d{PAPER_D}"])
def test_stream_kernel_compiles(one_chip, case):
    """The resident kernel at the OOD monitor's config (launch/serve.py:
    K = 8, D = 16, four-point chunks) and at the largest pool the guard
    admits, at the default chunk, for D = 16, 256 and the paper's D."""
    if case == "ood_monitor":
        k, d, n = 8, 16, 4
    else:
        d, n = int(case.rsplit("_d", 1)[1]), 256
        k = _largest_admitted_k(d, n)
    s, state = _shapes(one_chip, k, d)
    compiled = figmn_stream.figmn_stream_pallas.lower(
        s((n, d)), state.mu, state.lam, state.logdet, state.sp,
        s((k,), jnp.int32), s((1,)), dim=d, n_points=n,
        vmem_limit=devices.lookup(V5E).vmem_bytes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("body", ["scan", "sparse"])
def test_chunk_bodies_fit_one_chip_at_paper_d(one_chip, body):
    """The dense scan and the shortlist chunk bodies at D = 784 with a
    1024-component pool (Λ alone is 2.52 GB) compile and fit one chip's
    HBM."""
    k, n = 1024, 32
    s, state = _shapes(one_chip, k, PAPER_D)
    cfg = _cfg(k, PAPER_D, s((PAPER_D,)), c=16 if body == "sparse" else 0)
    fn = figmn.fit if body == "scan" else shortlist.fit_sparse
    compiled = fn.lower(cfg, state, s((n, PAPER_D)), do_prune=False).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_dense_predict_stage_compiles_at_paper_d(one_chip):
    """The dense eq. 27 read path at D = 784: the factor stage and one
    blocked predict call for 64 requests against a 1024-component pool."""
    k, b = 1024, 64
    s, state = _shapes(one_chip, k, PAPER_D)
    cfg = _cfg(k, PAPER_D, s((PAPER_D,)))
    targets = (PAPER_D - 1,)
    factors = inference._factors_jit.lower(cfg, state, targets).compile()
    f_shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(inference._factors_jit, cfg, state, targets))
    predict = inference._predict_dense_jit.lower(
        f_shapes, state.sp, state.active, s((b, PAPER_D - 1))).compile()
    for compiled in (factors, predict):
        mem = compiled.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < HBM_BYTES
