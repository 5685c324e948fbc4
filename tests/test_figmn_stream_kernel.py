"""VMEM-resident streaming FIGMN kernel (kernels/figmn_stream.py) vs the
jnp reference — the §Perf TPU-adaptation kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import devices
from repro.core import figmn
from repro.core.types import FIGMNConfig, chi2_quantile
from repro.kernels import figmn_stream


def _formed_mixture(seed=0, d=8, k=4):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 6, (3, d))
    x0 = np.concatenate([rng.normal(c, 1.0, (30, d)) for c in centers])
    cfg = FIGMNConfig(kmax=k, dim=d, beta=0.05, delta=1.0, vmin=1e9,
                      spmin=0.0, update_mode="exact",
                      sigma_ini=figmn.sigma_from_data(
                          jnp.asarray(x0, jnp.float32), 1.0))
    state = figmn.fit(cfg, figmn.init_state(cfg),
                      jnp.asarray(x0, jnp.float32))
    return cfg, state, centers, rng


@pytest.mark.parametrize("d,n", [(8, 40), (16, 64)])
def test_stream_kernel_matches_reference(d, n):
    cfg, state, centers, rng = _formed_mixture(d=d)
    xs = np.concatenate([rng.normal(c, 0.8, (n // 3 + 1, d))
                         for c in centers])[:n]
    xs = jnp.asarray(xs, jnp.float32)

    s_ref = state
    for i in range(n):
        s_ref = figmn.learn_one(cfg, s_ref, xs[i], do_prune=False)
    created = int(s_ref.n_created - state.n_created)

    thresh = jnp.asarray([float(chi2_quantile(d, 1.0 - cfg.beta))],
                         jnp.float32)
    mu, lam, logdet, sp, nacc = figmn_stream.figmn_stream_pallas(
        xs, state.mu, state.lam, state.logdet, state.sp,
        state.active.astype(jnp.int32), thresh, dim=d, n_points=n,
        interpret=True)
    # update-only points must match exactly; creation events are no-ops in
    # the kernel (the wrapper segments streams there)
    assert int(nacc[0]) == n - created
    if created == 0:
        m = np.asarray(state.active)
        np.testing.assert_allclose(np.asarray(mu)[m],
                                   np.asarray(s_ref.mu)[m], atol=2e-4)
        np.testing.assert_allclose(np.asarray(lam)[m],
                                   np.asarray(s_ref.lam)[m],
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(sp)[m],
                                   np.asarray(s_ref.sp)[m], atol=1e-3)


def test_vmem_budget_claim():
    """The working-set claim behind the kernel: a component shard at the
    dry-run scale fits a v5e's VMEM, counting everything the kernel holds
    there at the default chunk."""
    k_local, d = 512 // 16, 256        # dry-run figmn cell, per device
    bytes_needed = figmn_stream.vmem_bytes(k_local, d, 256)
    assert bytes_needed >= k_local * d * d * 4          # Λ at least
    assert bytes_needed <= devices.lookup("TPU v5 lite").vmem_bytes
