"""Serving-cost layer (PR 8): the per-epoch eq. 27 factor cache, the
micro-batched admission path, the B=0 empty-batch contract, the bucketed
sample/prefill compilation fixes, and the conditional-variance query.

Contracts pinned here:
  * cached predict is BIT-IDENTICAL to the uncached kernel — on synthetic
    mixtures and on every committed golden stream (structural: the cache
    hands the same ``_factors_jit`` output to the same blocked kernel);
  * a snapshot publish invalidates: stale factors never serve a newer
    epoch (the cache key carries the version captured under the swap
    lock);
  * the factor LRU evicts under many target signatures and never exceeds
    capacity; concurrent readers over a publishing frontend see no torn
    reads;
  * micro-batched async answers equal their sync twins and the coalescing
    metrics move; a full admission queue rejects at submission;
  * a micro-batched answer is a read-only host ``numpy.ndarray`` view of
    its flush's one host copy, bit-identical to the synchronous read, for
    score, predict and both members of predict(return_var=True);
  * B=0 through score / predict / predict_async returns well-formed
    (0, ·) outputs on ALL THREE frontends (StreamRuntime, ScoringFrontend
    via FleetCoordinator, Mixture) — one contract;
  * ``sample`` compiles once per power-of-two bucket (trace-counter
    pinned) and draws identically for a fixed seed within a bucket;
  * conditional variance matches a float64 NumPy reference computed from
    the state's covariances, dense and shortlisted.
"""
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Mixture, MixtureSpec, Query, execute
from repro.api import query as query_mod
from repro.core import figmn, inference
from repro.core.types import FIGMNConfig
from repro.fleet import AdmissionConfig, FleetConfig, FleetCoordinator
from repro.obs import registry as obs_registry
from repro.stream import StreamRuntime

import test_golden_streams as golden


def _blob_stream(seed=0, n=300, d=5, modes=3, spread=7.0):
    rng = np.random.default_rng(seed)
    centers = np.random.default_rng(0).normal(0, spread, (modes, d))
    x = centers[rng.integers(0, modes, n)] + rng.normal(0, 1.0, (n, d))
    return x.astype(np.float32)


def _cfg(x, **kw):
    defaults = dict(kmax=12, dim=x.shape[1], beta=0.1, delta=1.0, vmin=1e9,
                    spmin=0.0, update_mode="exact",
                    sigma_ini=figmn.sigma_from_data(jnp.asarray(x), 1.0))
    defaults.update(kw)
    return FIGMNConfig(**defaults)


def _fitted(seed=0, **kw):
    x = _blob_stream(seed=seed)
    cfg = _cfg(x, **kw)
    return cfg, figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x)), x


# ---------------------------------------------------------------------------
# factor cache: bit-identity, invalidation, LRU, thread-safety
# ---------------------------------------------------------------------------

def test_cached_predict_bit_identical_to_uncached():
    cfg, state, x = _fitted()
    cache = inference.FactorCache(capacity=4)
    q = jnp.asarray(x[:64, :4])
    plain = np.asarray(inference.predict_batch_routed(cfg, state, q, [4]))
    miss = np.asarray(inference.predict_batch_routed(
        cfg, state, q, [4], factor_cache=cache, epoch=1))
    hit = np.asarray(inference.predict_batch_routed(
        cfg, state, q, [4], factor_cache=cache, epoch=1))
    np.testing.assert_array_equal(plain, miss)
    np.testing.assert_array_equal(plain, hit)
    assert cache.misses == 1 and cache.hits == 1
    # the sparse route shares the bundle: identical with and without cache
    sp = np.asarray(inference.predict_batch_routed(cfg, state, q, [4], c=3))
    sp_c = np.asarray(inference.predict_batch_routed(
        cfg, state, q, [4], c=3, factor_cache=cache, epoch=1))
    np.testing.assert_array_equal(sp, sp_c)


@pytest.mark.parametrize("name,n,d,modes,chunk", golden.FIXTURES)
def test_cached_predict_bit_identical_on_golden_streams(name, n, d, modes,
                                                        chunk):
    """Acceptance: cached predict is bit-identical to the uncached kernel
    on the committed golden streams."""
    with np.load(os.path.join(golden.GOLDEN_DIR, f"{name}.npz")) as z:
        x = z["x"]
    cfg = golden._cfg(x)
    state = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    cache = inference.FactorCache(capacity=4)
    q = jnp.asarray(x[:, :d - 1])
    plain = np.asarray(inference.predict_batch(cfg, state, q, [d - 1]))
    for _ in range(2):        # miss then hit: both bit-identical
        got = np.asarray(inference.predict_batch_routed(
            cfg, state, q, [d - 1], factor_cache=cache, epoch=7))
        np.testing.assert_array_equal(plain, got)
    assert cache.hits == 1 and cache.misses == 1


def test_factor_cache_disabled_capacity_zero():
    cfg, state, x = _fitted()
    cache = inference.FactorCache(capacity=0)
    q = jnp.asarray(x[:16, :4])
    plain = np.asarray(inference.predict_batch(cfg, state, q, [4]))
    got = np.asarray(inference.predict_batch_routed(
        cfg, state, q, [4], factor_cache=cache, epoch=1))
    np.testing.assert_array_equal(plain, got)
    assert len(cache) == 0


def test_factor_cache_lru_eviction_under_many_signatures():
    cfg, state, _ = _fitted()
    cache = inference.FactorCache(capacity=3)
    for t in range(5):                       # 5 target signatures, cap 3
        cache.get(cfg, state, (t,), epoch=1)
    assert len(cache) == 3
    assert cache.keys() == [(1, (2,)), (1, (3,)), (1, (4,))]
    cache.get(cfg, state, (2,), epoch=1)     # hit refreshes recency
    cache.get(cfg, state, (0,), epoch=1)     # evicts the now-oldest (3,)
    assert (1, (3,)) not in cache.keys()
    assert (1, (2,)) in cache.keys()


def test_publish_invalidates_stale_factors_never_serve_new_epoch():
    """The frontend pairs (state, version) under ONE lock; after a
    publish, reads must answer from the NEW snapshot — byte-compared
    against a fresh frontend that only ever saw the new state."""
    x = _blob_stream(seed=0)
    cfg = _cfg(x)
    reg = obs_registry.Registry()
    fc = FleetCoordinator(cfg, FleetConfig(n_replicas=2), registry=reg)
    fc.ingest(x[:150])
    q = x[:32, :4]
    first = np.asarray(fc.predict(q, [4]))
    v1 = fc.scoring.version
    assert fc.scoring.factor_cache.misses >= 1
    fc.ingest(x[150:])                       # consolidates + publishes
    assert fc.scoring.version > v1
    after = np.asarray(fc.predict(q, [4]))
    ref = np.asarray(inference.predict_batch(
        cfg, fc.global_state, jnp.asarray(q, cfg.dtype), [4]))
    np.testing.assert_array_equal(after, ref)
    assert not np.array_equal(first, after)  # the pool genuinely moved
    # both epochs live in the LRU under distinct keys
    versions = {k[0] for k in fc.scoring.factor_cache.keys()}
    assert len(versions) >= 2
    fc.close()


def test_threaded_readers_no_torn_reads_across_publishes():
    """Hammer predict from N threads while the main thread republishes
    alternating snapshots: every answer must equal the uncached kernel's
    answer under ONE of the two published states — never a mixture."""
    cfg, state_a, x = _fitted(seed=0)
    state_b = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x[::-1]))
    from repro.fleet.scoring import ScoringFrontend
    reg = obs_registry.Registry()
    fe = ScoringFrontend(cfg, workers=4, registry=reg)
    fe.publish(state_a)
    q = jnp.asarray(x[:16, :4])
    want = {np.asarray(inference.predict_batch(cfg, s, q, [4])).tobytes()
            for s in (state_a, state_b)}
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            got = np.asarray(fe.predict(q, [4])).tobytes()
            if got not in want:
                errors.append("torn read")
                return

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    for i in range(40):
        fe.publish(state_b if i % 2 == 0 else state_a)
    stop.set()
    for t in threads:
        t.join()
    assert not errors
    fe.close()


# ---------------------------------------------------------------------------
# micro-batched admission
# ---------------------------------------------------------------------------

def test_microbatch_coalesces_and_matches_sync():
    x = _blob_stream(seed=1)
    cfg = _cfg(x)
    reg = obs_registry.Registry()
    fc = FleetCoordinator(
        cfg, FleetConfig(n_replicas=2,
                         admission=AdmissionConfig(max_batch=16,
                                                   max_delay_s=0.05)),
        registry=reg)
    fc.ingest(x)
    q = x[:24, :4]
    sync = np.asarray(fc.predict(q, [4]))
    futs = [fc.predict_async(q[i:i + 1], [4]) for i in range(len(q))]
    got = np.concatenate([np.asarray(f.result(timeout=30)) for f in futs])
    np.testing.assert_array_equal(sync, got)
    # the coalescing metrics moved: at least one multi-request dispatch
    h = reg.histogram("figmn_serve_coalesced_requests")   # get-or-create
    assert h.count >= 1
    assert fc.scoring.batcher.depth == 0
    # score coalesces under its own compatibility class
    s_sync = np.asarray(fc.score(x[:8]))
    s_futs = [fc.score_async(x[i:i + 1]) for i in range(8)]
    s_got = np.concatenate([np.asarray(f.result(timeout=30))
                            for f in s_futs])
    np.testing.assert_array_equal(s_sync, s_got)
    # every request landed its own latency sample
    assert fc.scoring.latency.count >= len(q) + 8 + 2
    fc.close()


def test_microbatch_respects_compatibility_classes():
    """Different targets (and return_var) must NOT coalesce into one
    dispatch — each class answers its own shape."""
    x = _blob_stream(seed=2)
    cfg = _cfg(x)
    reg = obs_registry.Registry()
    fc = FleetCoordinator(
        cfg, FleetConfig(n_replicas=2,
                         admission=AdmissionConfig(max_batch=8,
                                                   max_delay_s=0.02)),
        registry=reg)
    fc.ingest(x)
    fa = fc.predict_async(x[:4, :4], [4])
    fb = fc.predict_async(x[:4, 1:], [0])
    fv = fc.predict_async(x[:4, :4], [4], return_var=True)
    a, b = fa.result(timeout=30), fb.result(timeout=30)
    mv, vv = fv.result(timeout=30)
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(fc.predict(x[:4, :4], [4])))
    np.testing.assert_array_equal(np.asarray(b),
                                  np.asarray(fc.predict(x[:4, 1:], [0])))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(mv))
    assert np.asarray(vv).shape == (4, 1) and (np.asarray(vv) >= 0).all()
    fc.close()


def test_admission_queue_cap_rejects():
    from repro.fleet.scoring import AdmissionConfig as AC
    from repro.fleet.scoring import ScoringFrontend
    cfg, state, x = _fitted()
    reg = obs_registry.Registry()
    # huge max_delay so nothing flushes while we overfill
    fe = ScoringFrontend(cfg, registry=reg,
                         admission=AC(max_batch=10_000, max_delay_s=30.0,
                                      queue_cap=4))
    fe.publish(state)
    q = x[:1, :4]
    futs = [fe.predict_async(q, [4]) for _ in range(4)]
    with pytest.raises(RuntimeError, match="admission queue full"):
        fe.predict_async(q, [4])
    assert reg.counter("figmn_serve_admission_rejected_total").value == 1
    fe.close()                               # close() drains the queue
    for f in futs:
        assert np.asarray(f.result(timeout=5)).shape == (1, 1)


# (kind, targets, return_var) of each synchronous/async twin
_READS = {"score": ("score", None, False),
          "predict": ("predict", [4], False),
          "predict_var": ("predict", [4], True)}


def _coalesced_answers(read, n=12, batch=4):
    """``n`` one-row async answers of ``read`` through a micro-batcher
    whose every flush holds ``batch`` consecutive requests (the delay is
    far longer than the test), and the synchronous reads of the same
    row groups — each answer's twin."""
    from repro.fleet.scoring import AdmissionConfig as AC
    from repro.fleet.scoring import ScoringFrontend
    kind, targets, return_var = _READS[read]
    cfg, state, x = _fitted(seed=4)
    q = x[:n] if kind == "score" else x[:n, :4]
    reg = obs_registry.Registry()
    fe = ScoringFrontend(cfg, registry=reg,
                         admission=AC(max_batch=batch, max_delay_s=60.0))
    fe.publish(state)
    groups = [q[i:i + batch] for i in range(0, n, batch)]
    if kind == "score":
        sync = [fe.score(g) for g in groups]
        futs = [fe.score_async(q[i:i + 1]) for i in range(n)]
    else:
        sync = [fe.predict(g, targets, return_var=return_var)
                for g in groups]
        futs = [fe.predict_async(q[i:i + 1], targets,
                                 return_var=return_var) for i in range(n)]
    got = [f.result(timeout=60) for f in futs]
    fe.close()
    reqs = reg.histogram("figmn_serve_coalesced_requests")
    assert reqs.count == n // batch and reqs.sum == n
    twins = ([np.concatenate(m) for m in zip(*sync)] if return_var
             else [np.concatenate(sync)])
    return twins, got, return_var


@pytest.mark.parametrize("read", sorted(_READS))
def test_microbatch_answers_are_host_arrays_bit_identical_to_sync(read):
    twins, got, return_var = _coalesced_answers(read)
    members = list(zip(*got)) if return_var else [got]
    assert len(members) == len(twins)
    for answers, twin in zip(members, twins):
        for a in answers:
            assert type(a) is np.ndarray and a.shape[0] == 1
        np.testing.assert_array_equal(np.concatenate(answers), twin)


@pytest.mark.parametrize("read", sorted(_READS))
def test_microbatch_answers_are_read_only(read):
    _, got, return_var = _coalesced_answers(read)
    answers = [m for pair in got for m in pair] if return_var else got
    before = [a.copy() for a in answers]
    with pytest.raises(ValueError, match="read-only"):
        answers[0][...] = 0.0
    for a, b in zip(answers, before):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# B=0: one empty-batch contract across all three frontends
# ---------------------------------------------------------------------------

def test_empty_batch_contract_all_frontends():
    x = _blob_stream(seed=3)
    cfg = _cfg(x)
    e5 = np.zeros((0, 5), np.float32)
    e4 = np.zeros((0, 4), np.float32)

    # frontend 1: StreamRuntime (live state)
    rt = StreamRuntime(cfg)
    rt.ingest(x)
    assert rt.score(e5).shape == (0,)
    assert rt.predict(e4, [4]).shape == (0, 1)
    m, v = rt.predict(e4, [4], return_var=True)
    assert m.shape == (0, 1) and v.shape == (0, 1)

    # frontend 2: ScoringFrontend via FleetCoordinator (snapshot), sync,
    # async-pooled AND async-micro-batched
    reg = obs_registry.Registry()
    fc = FleetCoordinator(
        cfg, FleetConfig(n_replicas=2, admission=AdmissionConfig()),
        registry=reg)
    fc.ingest(x)
    assert fc.score(e5).shape == (0,)
    assert fc.predict(e4, [4]).shape == (0, 1)
    assert fc.score_async(e5).result(timeout=10).shape == (0,)
    assert fc.predict_async(e4, [4]).result(timeout=10).shape == (0, 1)
    m, v = fc.predict_async(e4, [4], return_var=True).result(timeout=10)
    assert m.shape == (0, 1) and v.shape == (0, 1)
    fc.close()

    # frontend 3: the Mixture facade (and the raw query layer)
    mix = Mixture(MixtureSpec(model=cfg)).partial_fit(x)
    assert mix.score_samples(e5).shape == (0,)
    assert mix.predict(e4, [4]).shape == (0, 1)
    assert execute(cfg, mix.state, Query("conditional", targets=(4,)),
                   e4).shape == (0, 1)
    mix.close()

    # the empty-MIXTURE contract still outranks the empty-batch one
    empty_state = figmn.init_state(cfg)
    with pytest.raises(ValueError, match="empty mixture"):
        inference.predict_batch(cfg, empty_state, e4, [4])


# ---------------------------------------------------------------------------
# sample bucketing (compile-per-count bugfix)
# ---------------------------------------------------------------------------

def test_sample_bucketing_one_trace_for_nearby_counts():
    cfg, state, _ = _fitted(seed=4)
    query_mod._sample_jit.clear_cache()
    query_mod._SAMPLE_TRACES.clear()
    a = query_mod.sample(cfg, state, 9, seed=5)    # bucket 16
    b = query_mod.sample(cfg, state, 13, seed=5)   # bucket 16: SAME trace
    assert a.shape == (9, cfg.dim) and b.shape == (13, cfg.dim)
    assert query_mod._SAMPLE_TRACES == [16]
    # fixed seed, shared bucket: b is a's prefix extension
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:9])
    c = query_mod.sample(cfg, state, 17, seed=5)   # bucket 32: new trace
    assert c.shape == (17, cfg.dim)
    assert query_mod._SAMPLE_TRACES == [16, 32]
    # n=0: well-formed empty, no dispatch, no trace
    assert query_mod.sample(cfg, state, 0).shape == (0, cfg.dim)
    assert query_mod._SAMPLE_TRACES == [16, 32]


# ---------------------------------------------------------------------------
# conditional variance (the richer Query)
# ---------------------------------------------------------------------------

def _np_conditional_reference(cfg, state, xs_in, tgt):
    """Float64 NumPy eq. 27 mean AND variance from the covariance form."""
    lam = np.asarray(state.lam, np.float64)
    mu = np.asarray(state.mu, np.float64)
    sp = np.asarray(state.sp, np.float64)
    active = np.asarray(state.active, bool)
    d = cfg.dim
    idx_in = [i for i in range(d) if i != tgt]
    means, var_ks, logps = [], [], []
    for k in range(lam.shape[0]):
        cov = np.linalg.inv(lam[k])
        c_ii = cov[np.ix_(idx_in, idx_in)]
        c_ti = cov[np.ix_([tgt], idx_in)]
        diff = np.asarray(xs_in, np.float64) - mu[k, idx_in]
        sol = np.linalg.solve(c_ii, diff.T).T
        means.append(mu[k, tgt] + sol @ c_ti[0])
        # conditional variance of the target block (Schur in cov form)
        var_ks.append(cov[tgt, tgt] - c_ti[0] @
                      np.linalg.solve(c_ii, c_ti[0]))
        d2 = np.sum(diff * sol, axis=1)
        _, ld = np.linalg.slogdet(c_ii)
        logps.append(-0.5 * (len(idx_in) * np.log(2 * np.pi) + ld + d2))
    means = np.stack(means, 1)               # (B, K)
    logps = np.stack(logps, 1)
    logw = logps + np.log(np.maximum(sp, 1e-30))[None]
    logw = np.where(active[None], logw, -np.inf)
    post = np.exp(logw - logw.max(1, keepdims=True))
    post /= post.sum(1, keepdims=True)
    mean = np.sum(post * means, axis=1)
    ex2 = np.sum(post * (np.asarray(var_ks)[None] + means ** 2), axis=1)
    return mean, np.maximum(ex2 - mean ** 2, 0.0)


def test_conditional_variance_matches_numpy_reference():
    cfg, state, x = _fitted(seed=5)
    q = x[:48, :4]
    m_ref, v_ref = _np_conditional_reference(cfg, state, q, 4)
    m, v = inference.predict_batch(cfg, state, jnp.asarray(q), [4],
                                   return_var=True)
    np.testing.assert_allclose(np.asarray(m)[:, 0], m_ref, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(v)[:, 0], v_ref, rtol=2e-3,
                               atol=2e-4)
    assert (np.asarray(v) >= 0).all()
    # shortlisted twin, C covering the pool: bit-identical to dense
    ms, vs = inference.predict_batch_sparse(cfg, state, jnp.asarray(q),
                                            [4], c=cfg.kmax,
                                            return_var=True)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(ms))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vs))
    # truncating shortlist: variance stays close (tail mass ~ 0)
    ak = int(state.n_active)
    ms2, vs2 = inference.predict_batch_sparse(cfg, state, jnp.asarray(q),
                                              [4], c=max(ak - 1, 1),
                                              return_var=True)
    np.testing.assert_allclose(np.asarray(vs2), np.asarray(v), rtol=0.2,
                               atol=1e-2)


def test_return_var_through_query_and_mixture():
    x = _blob_stream(seed=6)
    cfg = _cfg(x)
    mix = Mixture(MixtureSpec(model=cfg)).partial_fit(x)
    m, v = mix.predict(x[:8, :4], [4], return_var=True)
    assert m.shape == (8, 1) and v.shape == (8, 1)
    qm, qv = mix.query(Query("conditional", targets=(4,), return_var=True),
                       x[:8, :4])
    np.testing.assert_array_equal(np.asarray(m), np.asarray(qm))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(qv))
    with pytest.raises(ValueError, match="conditional-query option"):
        Query("density", return_var=True)
    mix.close()
