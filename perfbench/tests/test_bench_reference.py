"""The plain reference agrees with the program at a tiny size, and the
traffic is a function of the seed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import deploy, figmn_ref, pool, streams

CONFIG = {"features": 5, "classes": 3, "dim": 8, "kmax": 6, "beta": 0.001,
          "delta": 1.0, "vmin": 1e9, "spmin": 0.0, "update_mode": "exact",
          "dtype": "float32", "chunk": 4,
          "stream": {"generator": "gaussian_classes", "sep": 3.0}}
SNAPSHOT = {"rank": 3, "feature_scale": 1.0, "label_scale": 0.3,
            "label_mix": 0.3, "sp_max": 4.0}


def _pools_close(prog, ref):
    for k in ("mu", "lam", "logdet", "sp", "v"):
        np.testing.assert_allclose(np.asarray(prog[k]), np.asarray(ref[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)
    np.testing.assert_array_equal(np.asarray(prog["active"]),
                                  np.asarray(ref["active"]))


def _program_fit(dep, start, xs):
    from repro.core import figmn
    state = figmn.fit(dep.figmn_config(), deploy.to_state(start), xs,
                      do_prune=False)
    return deploy.from_state(state)


def test_learning_from_a_full_pool_matches_figmn_fit():
    dep = deploy.deployment(CONFIG, 11)
    xs = np.concatenate([streams.ingest_chunk(dep.model, 11, 4, i)
                         for i in range(10)])
    prog = _program_fit(dep, dep.start_pool(), jnp.asarray(xs))
    ref = figmn_ref.learn(dep.start_pool(), xs, dep.sigma, dep.threshold)
    _pools_close(prog, ref)
    assert float(jnp.max(jnp.abs(ref["mu"] - dep.start_means()))) > 0.1


def test_creation_and_recycling_match_figmn_fit():
    # a narrow gate and few slots: points are novel, take free slots,
    # then recycle the weakest
    cfg = dict(CONFIG, beta=0.5, delta=0.05)
    dep = deploy.deployment(cfg, 12)
    def empty():
        return jax.tree_util.tree_map(jnp.asarray, {
            "mu": np.zeros((6, 8), np.float32),
            "lam": np.broadcast_to(np.diag(1 / dep.sigma ** 2),
                                   (6, 8, 8)).astype(np.float32),
            "logdet": np.full(6, np.sum(2 * np.log(dep.sigma)), np.float32),
            "sp": np.zeros(6, np.float32), "v": np.zeros(6, np.float32),
            "active": np.zeros(6, bool)})

    xs = streams.draw(dep.model, 12, 40, streams.INGEST, 0)
    prog = _program_fit(dep, empty(), jnp.asarray(xs))
    ref = figmn_ref.learn(empty(), xs, dep.sigma, dep.threshold)
    _pools_close(prog, ref)
    state = deploy.to_state(prog)
    assert bool(np.all(np.asarray(state.active)))


def test_reads_match_score_batch_and_eq27():
    from repro.core import figmn, inference
    dep = deploy.deployment(CONFIG, 13)
    xs = np.concatenate([streams.ingest_chunk(dep.model, 13, 4, i)
                         for i in range(6)])
    learned = figmn_ref.learn(dep.start_pool(), xs, dep.sigma,
                              dep.threshold)
    state = deploy.to_state(learned)
    cfg = dep.figmn_config()
    rows = streams.draw(dep.model, 13, 9, streams.READ)
    np.testing.assert_allclose(
        np.asarray(figmn_ref.log_density(learned, rows)),
        np.asarray(figmn.score_batch(cfg, state, jnp.asarray(rows))),
        rtol=1e-5, atol=1e-4)
    targets = tuple(range(dep.features, dep.dim))
    np.testing.assert_allclose(
        np.asarray(figmn_ref.conditional_mean(
            learned, rows[:, :dep.features], n_out=dep.classes)),
        np.asarray(inference.predict_batch(
            cfg, state, jnp.asarray(rows[:, :dep.features]), targets)),
        rtol=1e-4, atol=1e-5)


def test_reads_of_the_served_snapshot_match_score_batch_and_eq27():
    from repro.core import figmn, inference
    dep = deploy.deployment(CONFIG, 15)
    served = dep.served_pool(SNAPSHOT)
    state = deploy.to_state(served)
    cfg = dep.figmn_config()
    rows = streams.draw(dep.model, 15, 9, streams.READ)
    np.testing.assert_allclose(
        np.asarray(figmn_ref.log_density(served, rows)),
        np.asarray(figmn.score_batch(cfg, state, jnp.asarray(rows))),
        rtol=1e-5, atol=1e-4)
    targets = tuple(range(dep.features, dep.dim))
    ref = np.asarray(figmn_ref.conditional_mean(
        served, rows[:, :dep.features], n_out=dep.classes))
    np.testing.assert_allclose(
        ref, np.asarray(inference.predict_batch(
            cfg, state, jnp.asarray(rows[:, :dep.features]), targets)),
        rtol=1e-4, atol=1e-5)
    # eq. 27's W⁻¹Z term moves the answers: they are not the posterior's
    # mix of the label means alone
    flat = dict(served, lam=jnp.asarray(np.asarray(served["lam"])
                                        * np.eye(dep.dim)))
    flat["logdet"] = -jnp.linalg.slogdet(flat["lam"])[1]
    moved = np.abs(ref - np.asarray(figmn_ref.conditional_mean(
        flat, rows[:, :dep.features], n_out=dep.classes)))
    assert moved.max() > 0.05


def test_the_served_snapshot_is_shaped_as_a_learned_one():
    dep = deploy.deployment(CONFIG, 16)
    p = dep.served_pool(SNAPSHOT)
    lam = np.asarray(p["lam"], np.float64)
    f = dep.features
    np.testing.assert_allclose(lam, np.swapaxes(lam, 1, 2), atol=1e-6)
    assert np.all(np.linalg.eigvalsh(lam) > 0)
    np.testing.assert_allclose(np.asarray(p["logdet"]),
                               -np.linalg.slogdet(lam)[1], rtol=1e-5)
    assert np.abs(lam[:, :f, f:]).min(axis=(1, 2)).max() > 0
    off = 1 - np.eye(dep.classes)
    assert np.all(np.abs(lam[:, f:, f:] * off).max(axis=(1, 2)) > 1e-3)
    labels = np.asarray(p["mu"])[:, f:]
    np.testing.assert_allclose(labels.sum(axis=1), 1.0, rtol=1e-5)
    assert labels.max(axis=1).max() < 1.0
    sp = np.asarray(p["sp"])
    assert sp.min() >= 1.0 and sp.max() <= 4.0 and np.ptp(sp) > 0.5
    again = dep.served_pool(SNAPSHOT)
    for k in p:
        np.testing.assert_array_equal(np.asarray(p[k]),
                                      np.asarray(again[k]))
    other = deploy.deployment(CONFIG, 2 ** 31 + 16).served_pool(SNAPSHOT)
    assert not np.array_equal(np.asarray(other["lam"]), lam)


def test_an_unknown_stream_generator_is_an_error():
    cfg = dict(CONFIG, stream={"generator": "mnist_pixels", "sep": 3.0})
    with pytest.raises(ValueError, match="generator"):
        deploy.deployment(cfg, 1)


def test_the_starting_pool_is_the_state_kmax_creations_leave():
    dep = deploy.deployment(CONFIG, 14)
    p = dep.start_pool()
    np.testing.assert_array_equal(np.asarray(p["mu"]),
                                  pool.means(dep.model, 14, 6))
    np.testing.assert_allclose(np.asarray(p["lam"][3]),
                               np.diag(1 / dep.sigma ** 2), rtol=1e-6)
    assert np.all(np.asarray(p["sp"]) == 1)
    assert np.all(np.asarray(p["active"]))


def test_streams_are_a_function_of_the_seed():
    big = 2 ** 31 + 12345
    m1 = streams.class_model(big, 7, 3)
    m2 = streams.class_model(big, 7, 3)
    np.testing.assert_array_equal(m1.means, m2.means)
    a = streams.ingest_chunk(m1, big, 4, 5)
    np.testing.assert_array_equal(a, streams.ingest_chunk(m2, big, 4, 5))
    assert not np.array_equal(a, streams.ingest_chunk(m1, big + 1, 4, 5))
    assert not np.array_equal(a, streams.ingest_chunk(m1, big, 4, 6))


def test_schedules_do_the_same_work_in_another_order():
    kinds = {"score": 0.5, "label": 0.5}
    d1, k1 = streams.poisson_schedule(2 ** 33 + 1, 500.0, 2.0, kinds)
    d2, k2 = streams.poisson_schedule(2 ** 33 + 1, 500.0, 2.0, kinds)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(k1, k2)
    d3, k3 = streams.poisson_schedule(7, 500.0, 2.0, kinds)
    assert d1.size == d3.size == 1000
    assert not np.array_equal(d1, d3)
    np.testing.assert_allclose(np.sort(np.diff(np.r_[0.0, d1])),
                               np.sort(np.diff(np.r_[0.0, d3])), atol=1e-12)
    assert np.bincount(k1).tolist() == np.bincount(k3).tolist() == [500, 500]
    assert d1[-1] == pytest.approx(2.0, rel=0.02)
