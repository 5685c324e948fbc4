"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: sound runs come out correct, and runs with the timed path broken
underneath come out not correct, under the cells' committed limits."""
import dataclasses
import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import catalog, run

TINY = {"features": 6, "classes": 3, "dim": 9, "kmax": 8, "chunk": 4}
#: a narrower gate: about two points in five are novel and recycle a slot
NOVEL = {"beta": 0.3, "delta": 0.5}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("bench")
    for kind in ("drivers", "metrics"):
        shutil.copytree(os.path.join(catalog.ROOT, kind), r / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic", "limits"):
        (r / kind).mkdir()
    cfg = catalog.load_json("configs", "mnist-k1024")
    cfg.update(TINY, name="tiny")
    (r / "configs" / "tiny.json").write_text(json.dumps(cfg))
    cfg.update(NOVEL, name="novel")
    (r / "configs" / "novel.json").write_text(json.dumps(cfg))
    (r / "traffic" / "ingest.json").write_text(
        json.dumps(catalog.load_json("traffic", "ingest")))
    read = catalog.load_json("traffic", "read")
    read.update(knee_per_s=2500, load=0.8, admission={"max_batch": 4},
                checked_per_kind=10 ** 6, drain_s=30)
    (r / "traffic" / "read.json").write_text(json.dumps(read))
    for cell, real in (("tiny.ingest", "mnist-k1024.ingest"),
                       ("novel.ingest", "mnist-k1024.ingest"),
                       ("tiny.read", "mnist-k1024.read")):
        (r / "limits" / f"{cell}.json").write_text(
            json.dumps(catalog.load_json("limits", real)))
    return str(r)


BENCH = {
    "workloads": [
        {"name": "tiny.ingest", "config": "tiny", "traffic": "ingest",
         "chips": 1},
        {"name": "novel.ingest", "config": "novel", "traffic": "ingest",
         "chips": 1},
        {"name": "tiny.read", "config": "tiny", "traffic": "read",
         "chips": 1}],
    "end_to_end": [
        {"name": "ingest_pts_per_s", "unit": "points/s",
         "workloads": ["tiny.ingest", "novel.ingest"]},
        {"name": "read_p95_ms", "unit": "ms", "workloads": ["tiny.read"]},
        {"name": "read_p50_ms", "unit": "ms", "workloads": ["tiny.read"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}


def _run(root, cell, seconds=0.5):
    return run.run_cell(BENCH, cell, 2 ** 31 + 77, seconds, False,
                        root=root, check_chips=False)


def _over(result):
    """The names of the checks over their limits."""
    return {k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


def _unchanged(orig):
    return lambda cfg, state, xc, do_prune: state


def _half(orig):
    return lambda cfg, state, xc, do_prune: orig(
        cfg, state, xc[: xc.shape[0] // 2], do_prune)


def _altered(orig):
    def body(cfg, state, xc, do_prune):
        out = orig(cfg, state, xc, do_prune)
        return dataclasses.replace(out, mu=out.mu.at[0].add(1.0))
    return body


def _one_slot(orig):
    """One slot's Λ scaled up in every chunk, as a second division by
    (1 − ω) would: slot 0's."""
    def body(cfg, state, xc, do_prune):
        out = orig(cfg, state, xc, do_prune)
        return dataclasses.replace(out, lam=out.lam.at[0].multiply(2.0))
    return body


def _not_reset(orig):
    """A recycled slot keeps its old Λ: not reset to σ_ini⁻² I."""
    def body(cfg, state, xc, do_prune):
        lam0 = jnp.copy(state.lam)
        out = orig(cfg, state, xc, do_prune)
        made = out.v < jnp.max(out.v)      # every other slot aged alike
        return dataclasses.replace(
            out, lam=jnp.where(made[:, None, None], lam0, out.lam))
    return body


def test_a_sound_ingest_run_is_correct(root):
    r = _run(root, "tiny.ingest")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"ingest_pts_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state-unchanged", "half-the-batch",
                              "answer-altered"])
def test_a_broken_ingest_body_is_not_correct(root, monkeypatch, fault):
    from repro.stream import ingest
    monkeypatch.setattr(ingest, "fit_chunk_scan",
                        fault(ingest.fit_chunk_scan))
    assert not _run(root, "tiny.ingest")["correct"]


def test_a_sound_run_that_recycles_slots_is_correct(root):
    r = _run(root, "novel.ingest")
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault,cell,check", [
    (_one_slot, "tiny.ingest", "lam_far_share"),
    (_not_reset, "novel.ingest", "lam_far_share")],
    ids=["one-slot-precision", "recycled-slot-not-reset"])
def test_a_fault_in_a_few_slots_is_not_correct(root, monkeypatch, fault,
                                              cell, check):
    from repro.stream import ingest
    monkeypatch.setattr(ingest, "fit_chunk_scan",
                        fault(ingest.fit_chunk_scan))
    r = _run(root, cell)
    assert not r["correct"] and check in _over(r), r["checks"]


def test_a_sound_read_run_is_correct(root):
    r = _run(root, "tiny.read")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 1000
    assert set(r["metrics"]) == {"read_p95_ms", "read_p50_ms", "setup_s"}


def _score_shifted(orig):
    return lambda cfg, state, xs: orig(cfg, state, xs) + 5.0


def _score_half(orig):
    def f(cfg, state, xs):
        out = orig(cfg, state, xs)
        keep = out[: (out.shape[0] + 1) // 2]
        return jnp.concatenate([keep, keep])[: out.shape[0]]
    return f


@pytest.mark.parametrize("fault", [_score_shifted, _score_half],
                         ids=["answer-altered", "half-the-batch"])
def test_a_broken_score_kernel_is_not_correct(root, monkeypatch, fault):
    from repro.stream import ingest
    monkeypatch.setattr(ingest, "score_batch_jit",
                        fault(ingest.score_batch_jit))
    assert not _run(root, "tiny.read")["correct"]


def test_a_broken_label_kernel_is_not_correct(root, monkeypatch):
    from repro.core import inference
    orig = inference._predict_dense_jit

    def rolled(*a, **k):
        return jnp.roll(orig(*a, **k), 1, axis=-1)
    monkeypatch.setattr(inference, "_predict_dense_jit", rolled)
    assert not _run(root, "tiny.read")["correct"]


def _cross_negated(f, state, targets):
    return f._replace(winv_z=-f.winv_z)


def _solve_skipped(f, state, targets):
    """W⁻¹Z taken as Z: the o×o solve of eq. 27 left out."""
    out = np.asarray(targets)
    inp = np.setdiff1d(np.arange(state.lam.shape[1]), out)
    return f._replace(winv_z=state.lam[:, out[:, None], inp[None, :]])


@pytest.mark.parametrize("fault", [_cross_negated, _solve_skipped],
                         ids=["cross-term-sign", "solve-skipped"])
def test_a_broken_label_factor_is_not_correct(root, monkeypatch, fault):
    from repro.core import inference
    orig = inference._factors_jit

    def broken(cfg, state, targets):
        return fault(orig(cfg, state, targets), state, targets)
    monkeypatch.setattr(inference, "_factors_jit", broken)
    r = _run(root, "tiny.read")
    assert not r["correct"] and "label_gap" in _over(r), r["checks"]
