"""Top-C shortlist engine (core/shortlist.py): exactness at C=K, statistical
fidelity at small C, and scatter conservation.

The exactness tier (see tests/README.md): the shortlist is EXACT by
construction when C ≥ active K — the bound pass then selects every live
slot, the sorted top-K gather is the identity permutation, and the sparse
body runs the dense fused formulas on the same values in the same order.
These tests pin that as bit-identity against the dense scan path
(including on the committed golden streams), not as a tolerance."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import figmn, shortlist
from repro.core.types import FIGMNConfig
from repro.kernels import ops
from repro.stream import RuntimeConfig, StreamRuntime, select_path

import test_golden_streams as golden


def _blob_stream(seed=0, n=260, d=5, modes=3, spread=7.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, spread, (modes, d))
    x = centers[rng.integers(0, modes, n)] + rng.normal(0, 1.0, (n, d))
    return x.astype(np.float32)


def _cfg(x, **kw):
    defaults = dict(kmax=12, dim=x.shape[1], beta=0.1, delta=1.0, vmin=1e9,
                    spmin=0.0, update_mode="exact",
                    sigma_ini=figmn.sigma_from_data(jnp.asarray(x), 1.0))
    defaults.update(kw)
    return FIGMNConfig(**defaults)


def _assert_states_bitident(a, b):
    for f in ("mu", "lam", "logdet", "sp", "v"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(a.active), np.asarray(b.active))
    assert int(a.n_created) == int(b.n_created)


# ---------------------------------------------------------------------------
# exactness tier: C = K ⇒ bit-identity with the dense scan path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("update_mode", ["exact", "paper"])
def test_fit_sparse_ck_bitidentical_to_dense(update_mode):
    x = _blob_stream()
    cfg = _cfg(x, update_mode=update_mode, shortlist_c=12)
    ref = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    got = shortlist.fit_sparse(cfg, figmn.init_state(cfg), jnp.asarray(x))
    _assert_states_bitident(ref, got)


def test_fit_sparse_ck_bitidentical_with_inline_prune():
    x = _blob_stream(seed=2)
    cfg = _cfg(x, vmin=10.0, spmin=2.0, shortlist_c=12)
    ref = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    got = shortlist.fit_sparse(cfg, figmn.init_state(cfg), jnp.asarray(x))
    _assert_states_bitident(ref, got)


@pytest.mark.parametrize("name,n,d,modes,chunk", golden.FIXTURES)
def test_sparse_path_reproduces_golden_scan_digests(name, n, d, modes,
                                                    chunk):
    """On the committed golden streams, the sparse runtime path at C=K
    must land on the SCAN path's pinned digest — the shortlist rides the
    same exactness contract the golden tier guards."""
    doc = golden._load()
    entry = doc["fixtures"][name]
    import os
    with np.load(os.path.join(golden.GOLDEN_DIR, f"{name}.npz")) as z:
        x = z["x"]
    cfg = dataclasses.replace(golden._cfg(x), shortlist_c=8)
    rt = StreamRuntime(cfg, RuntimeConfig(chunk=entry["chunk"]))
    assert rt.path == "sparse"
    rt.ingest(x)
    assert golden._digest(rt.state) == entry["digests"]["scan"]


def test_chunked_sparse_ingestion_equals_one_shot():
    """The PR-1 chunking invariant holds for the sparse body too."""
    x = _blob_stream(seed=4)
    cfg = _cfg(x, shortlist_c=12)
    rt = StreamRuntime(cfg, RuntimeConfig(chunk=37, path="sparse"))
    rt.ingest(x)
    ref = shortlist.fit_sparse(cfg, figmn.init_state(cfg), jnp.asarray(x))
    _assert_states_bitident(ref, rt.state)


# ---------------------------------------------------------------------------
# statistical tier: small C tracks dense within tolerance
# ---------------------------------------------------------------------------

def test_small_c_heldout_ll_tracks_dense():
    x = _blob_stream(seed=1, n=400, d=6, modes=3)
    held = _blob_stream(seed=9, n=150, d=6, modes=3)
    cfg = _cfg(x)
    ref = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    ll_ref = float(jnp.mean(figmn.score_batch(cfg, ref, jnp.asarray(held))))
    for c in (3, 6):
        cfg_c = dataclasses.replace(cfg, shortlist_c=c)
        got = shortlist.fit_sparse(cfg_c, figmn.init_state(cfg_c),
                                   jnp.asarray(x))
        ll = float(jnp.mean(figmn.score_batch(cfg_c, got,
                                              jnp.asarray(held))))
        assert abs(ll - ll_ref) < 0.5, (c, ll, ll_ref)


def test_sparse_scorer_tracks_dense():
    x = _blob_stream(seed=3, n=300, d=6)
    held = _blob_stream(seed=8, n=700, d=6)     # > block_b: tiled path
    cfg = _cfg(x, shortlist_c=4)
    state = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    dense = np.asarray(figmn.score_batch(cfg, state, jnp.asarray(held)))
    sparse = np.asarray(shortlist.score_batch_sparse(
        cfg, state, jnp.asarray(held)))
    # truncation only ever drops tail mass ⇒ sparse ≤ dense up to the
    # order of the float32 reductions: 2 ulps of dense, and the mean
    # gap is the numerically-zero posterior tail
    ulp = np.spacing(np.abs(dense))
    assert (sparse <= dense + 2 * ulp).all(), np.max((sparse - dense) / ulp)
    assert abs(float(np.mean(sparse - dense))) < 1e-2
    # C = K reproduces the dense batched scorer to float tolerance
    full = np.asarray(shortlist.score_batch_sparse(
        cfg, state, jnp.asarray(held), c=cfg.kmax))
    np.testing.assert_allclose(full, dense, atol=1e-5)


# ---------------------------------------------------------------------------
# conservation tier: the scatter write-back touches ONLY the shortlist rows
# ---------------------------------------------------------------------------

def test_learn_one_sparse_touches_only_shortlist_rows():
    x = _blob_stream(seed=5)
    cfg = _cfg(x, shortlist_c=2)
    state = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    diag = shortlist.lam_diag(state)
    pt = jnp.asarray(x[-1])
    idx = np.asarray(shortlist.topc(
        shortlist.shortlist_scores(cfg, state, diag, pt), 2))
    new, _ = shortlist.learn_one_sparse(cfg, state, diag, pt,
                                        do_prune=False)
    untouched = np.setdiff1d(np.arange(cfg.kmax), idx)
    for f in ("mu", "lam", "logdet", "sp"):
        np.testing.assert_array_equal(
            np.asarray(getattr(new, f))[untouched],
            np.asarray(getattr(state, f))[untouched], err_msg=f)
    # ...and the shortlisted row that absorbed the point DID move
    assert not np.array_equal(np.asarray(new.sp)[idx],
                              np.asarray(state.sp)[idx])


def test_pallas_gathered_matvec_and_scatter_apply():
    """The kernel variants (scalar-prefetch gather, aliased scatter) match
    the jnp reference; untouched rows come back bit-identical."""
    rng = np.random.default_rng(0)
    k, d, c = 10, 6, 3
    lam = jnp.asarray(rng.normal(size=(k, d, d)), jnp.float32)
    diff = jnp.asarray(rng.normal(size=(c, d)), jnp.float32)
    idx = jnp.asarray([7, 2, 9], jnp.int32)
    y = ops.gathered_matvec(lam, diff, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.einsum("kde,ke->kd",
                                                     lam[idx], diff)),
                               rtol=1e-6)
    logdet = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    d2 = jnp.einsum("kd,kd->k", diff, y)
    w = jnp.asarray([0.3, 0.1, 0.05], jnp.float32)
    for mode in ("exact", "paper"):
        lam_new, logdet_new = ops.scatter_fused_apply(
            lam, logdet, idx, y, d2, w, d, mode, interpret=True)
        beta, dlogdet = figmn.fused_step_coeffs(d2, w, d, mode)
        yy = jnp.einsum("kd,ke->kde", y, y)
        if mode == "exact":
            rows = (lam[idx] - beta[:, None, None] * yy) \
                / (1.0 - w)[:, None, None]
        else:
            rows = lam[idx] / (1.0 - w)[:, None, None] \
                + beta[:, None, None] * yy
        np.testing.assert_allclose(np.asarray(lam_new)[np.asarray(idx)],
                                   np.asarray(rows), rtol=1e-5, atol=1e-5)
        untouched = np.setdiff1d(np.arange(k), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(lam_new)[untouched],
                                      np.asarray(lam)[untouched])
        np.testing.assert_allclose(
            np.asarray(logdet_new)[np.asarray(idx)],
            np.asarray(logdet[idx] + dlogdet), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(logdet_new)[untouched],
                                      np.asarray(logdet)[untouched])


def test_pallas_backend_sparse_fit_matches_jnp():
    x = _blob_stream(seed=6, n=120, d=4)
    base = _cfg(x, kmax=8, shortlist_c=3)
    sj = shortlist.fit_sparse(base, figmn.init_state(base), jnp.asarray(x))
    cfgp = dataclasses.replace(base, backend="pallas")
    sp = shortlist.fit_sparse(cfgp, figmn.init_state(cfgp), jnp.asarray(x))
    assert (np.asarray(sj.active) == np.asarray(sp.active)).all()
    np.testing.assert_allclose(np.asarray(sj.mu), np.asarray(sp.mu),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sj.lam), np.asarray(sp.lam),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# dispatch / config plumbing
# ---------------------------------------------------------------------------

def test_select_path_sparse_dispatch():
    x = _blob_stream()
    on = _cfg(x, shortlist_c=4)
    off = _cfg(x)
    assert select_path(on) == "sparse"                 # auto, C configured
    assert select_path(on, requested="sparse") == "sparse"
    assert select_path(on, requested="scan") == "scan"  # forced dense wins
    assert select_path(off) == "scan"
    with pytest.raises(ValueError):
        select_path(off, requested="sparse")           # needs shortlist_c
    # the sparse step IS the fused form: the unfused faithfulness knob has
    # no sparse counterpart and must fail loudly, not silently diverge
    unfused = dataclasses.replace(on, fused=False)
    with pytest.raises(ValueError):
        shortlist.fit_sparse(unfused, figmn.init_state(unfused),
                             jnp.asarray(x))


def test_chunk_stats_sparse_tracks_dense():
    """The shortlisted drift-stats pass: fails/ll agree with the dense
    ingest.chunk_stats at C=K, and stay close at small C."""
    from repro.core.types import chi2_quantile
    from repro.stream import ingest

    x = _blob_stream(seed=2, n=240, d=5)
    cfg = _cfg(x, shortlist_c=12)
    state = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x[:200]))
    xc = jnp.asarray(x[200:])
    thresh = jnp.asarray(float(chi2_quantile(cfg.dim, 1.0 - cfg.beta)),
                         jnp.float32)
    f_dense, ll_dense = ingest.chunk_stats(cfg, state, xc, thresh)
    f_ck, ll_ck = shortlist.chunk_stats_sparse(cfg, state, xc, thresh)
    np.testing.assert_array_equal(np.asarray(f_dense), np.asarray(f_ck))
    np.testing.assert_allclose(float(ll_ck), float(ll_dense), atol=1e-5)
    cfg2 = dataclasses.replace(cfg, shortlist_c=3)
    f_c3, ll_c3 = shortlist.chunk_stats_sparse(cfg2, state, xc, thresh)
    # truncation can only turn accepts into fails, never the reverse, and
    # can only LOWER the truncated log-density (this pool is deliberately
    # overlapping/underfit, so the dropped tail is non-trivial — the tight
    # ll bound lives in test_small_c_heldout_ll_tracks_dense on converged
    # mixtures)
    assert (np.asarray(f_c3) | ~np.asarray(f_dense)).all()
    assert float(ll_c3) <= float(ll_dense) + 1e-5
    assert float(ll_dense) - float(ll_c3) < 5.0


def test_dedup_score_batch_is_the_batched_pass():
    """Satellite contract: score_batch and chunk_stats share ONE batched
    implementation (figmn.log_joint_batch)."""
    x = _blob_stream(seed=7, n=150, d=4)
    cfg = _cfg(x, kmax=8)
    state = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    xs = jnp.asarray(x[:50])
    _, logjoint = figmn.log_joint_batch(cfg, state, xs)
    import jax
    expect = jax.scipy.special.logsumexp(logjoint, axis=1)
    np.testing.assert_array_equal(
        np.asarray(figmn.score_batch(cfg, state, xs)), np.asarray(expect))
    # and the vmap-of-scalar formulation it replaced agrees numerically
    per_point = jnp.stack([figmn.log_likelihood(cfg, state, xs[i])
                           for i in range(8)])
    np.testing.assert_allclose(np.asarray(expect[:8]),
                               np.asarray(per_point), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# property tier (hypothesis, shared fleet_streams strategies)
# ---------------------------------------------------------------------------

import conftest

if not conftest.HAVE_HYPOTHESIS:
    @pytest.mark.property
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_shortlist_invariants():
        """Placeholder so the skipped property suite stays visible."""
else:
    from hypothesis import HealthCheck, given, settings

    _SETTINGS = dict(max_examples=12, deadline=None,
                     suppress_health_check=[HealthCheck.too_slow,
                                            HealthCheck.data_too_large])

    def _pcfg(x, c, kmax=10):
        return FIGMNConfig(
            kmax=kmax, dim=x.shape[1], beta=0.1, delta=1.0, vmin=1e9,
            spmin=0.0, update_mode="exact", shortlist_c=c,
            sigma_ini=figmn.sigma_from_data(jnp.asarray(x), 1.0))

    @pytest.mark.property
    @given(stream=conftest.fleet_streams(max_points=200))
    @settings(**_SETTINGS)
    def test_property_ck_bitident(stream):
        """C = kmax ⇒ sparse ≡ dense scan, bit for bit, for arbitrary
        hypothesis-drawn clustered streams."""
        x, _ = stream
        cfg = _pcfg(x, c=10)
        ref = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
        got = shortlist.fit_sparse(cfg, figmn.init_state(cfg),
                                   jnp.asarray(x))
        _assert_states_bitident(ref, got)

    @pytest.mark.property
    @given(stream=conftest.fleet_streams(max_points=200))
    @settings(**_SETTINGS)
    def test_property_small_c_scorer_lower_bounds_dense(stream):
        """Truncated logsumexp can only DROP mass: the sparse score is a
        lower bound on the dense score for every point, any C."""
        x, seed = stream
        cfg = _pcfg(x, c=2)
        state = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
        dense = np.asarray(figmn.score_batch(cfg, state,
                                             jnp.asarray(x[:64])))
        sparse = np.asarray(shortlist.score_batch_sparse(
            cfg, state, jnp.asarray(x[:64])))
        assert (sparse <= dense + 1e-4).all(), seed
