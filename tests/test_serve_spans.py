"""Spans and the queue-wait counter inside the read path's micro-batcher,
and the host-sync children of an ingest chunk.

* every ``serve.flush`` on the flush thread holds, one level deeper and in
  order, one ``serve.gather``, one ``serve.score``/``serve.predict`` and
  one ``serve.resolve``; the thread's top-level spans are only the
  flush and its two waits;
* one ``serve.submit`` per async request, and the flush names the request
  number of its oldest member;
* ``figmn_serve_queue_wait_seconds`` counts exactly the queued requests:
  synchronous reads add nothing;
* ``figmn_serve_resolve_copies_total`` counts one device-to-host copy per
  flush that dispatched a read; a flush whose requests all expired at the
  deadline filter adds nothing;
* with tracing off the answers are bit-identical and no span is recorded;
* every ``stream.ingest_chunk`` holds ``stream.chunk_guard``,
  ``stream.chunk_pull`` and ``stream.chunk_fence``, in that order.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import figmn
from repro.core.types import FIGMNConfig
from repro.fleet.scoring import (AdmissionConfig, DeadlineExceeded,
                                 ScoringFrontend)
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.stream import RuntimeConfig, StreamRuntime

N_ASYNC = 12            # per kind


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    obs_trace.disable()


def _blobs(seed=0, n=240, d=5, modes=3):
    rng = np.random.default_rng(seed)
    centers = np.random.default_rng(0).normal(0, 7.0, (modes, d))
    x = centers[rng.integers(0, modes, n)] + rng.normal(0, 1.0, (n, d))
    return x.astype(np.float32)


def _cfg(x):
    return FIGMNConfig(kmax=12, dim=x.shape[1], beta=0.1, delta=1.0,
                       vmin=1e9, spmin=0.0, update_mode="exact",
                       sigma_ini=figmn.sigma_from_data(jnp.asarray(x), 1.0))


@pytest.fixture(scope="module")
def fitted():
    x = _blobs()
    cfg = _cfg(x)
    return cfg, figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x)), x


def _serve(fitted, reg):
    """Async scores and label reads through a micro-batching frontend, then
    two synchronous reads; returns every answer, in request order."""
    cfg, state, x = fitted
    fe = ScoringFrontend(cfg, registry=reg,
                         admission=AdmissionConfig(max_batch=5,
                                                   max_delay_s=0.01))
    fe.publish(state)
    futs = [fe.score_async(x[i:i + 1]) for i in range(N_ASYNC)]
    futs += [fe.predict_async(x[i:i + 1, :4], [4]) for i in range(N_ASYNC)]
    out = [np.asarray(f.result(timeout=60)) for f in futs]
    out.append(np.asarray(fe.score(x[:3])))
    out.append(np.asarray(fe.predict(x[:3, :4], [4])))
    fe.close()                      # joins the flush thread: spans are in
    return out


def _children(spans, parent):
    """The spans one level under ``parent`` on its thread, by start."""
    end = parent.ts_s + parent.dur_s
    return sorted((s for s in spans if s.tid == parent.tid
                   and s.depth == parent.depth + 1
                   and parent.ts_s <= s.ts_s and s.ts_s + s.dur_s <= end),
                  key=lambda s: s.ts_s)


def test_every_flush_holds_gather_read_resolve(fitted):
    tracer = obs_trace.enable(capacity=1 << 14)
    _serve(fitted, obs_registry.Registry())
    spans = tracer.spans()
    flushes = [s for s in spans if s.name == "serve.flush"]
    assert len(flushes) >= 2 * N_ASYNC // 5
    answered = 0
    for f in flushes:
        attrs = dict(f.attrs)
        assert f.thread == "fleet-microbatch" and f.depth == 0
        kids = [c.name for c in _children(spans, f)]
        assert kids == ["serve.gather", f"serve.{attrs['kind']}",
                        "serve.resolve"]
        assert 1 <= attrs["requests"] == attrs["rows"] <= 5
        answered += attrs["requests"]
    assert answered == 2 * N_ASYNC
    # flush numbers run 1, 2, ... on the flush thread
    assert sorted(dict(f.attrs)["flush"] for f in flushes) == \
        list(range(1, len(flushes) + 1))
    # the flush thread's top level: waiting, coalescing, flushing
    top = {s.name for s in spans
           if s.thread == "fleet-microbatch" and s.depth == 0}
    assert top <= {"serve.wait_request", "serve.coalesce", "serve.flush"}
    assert "serve.flush" in top


def test_one_submit_span_per_async_request(fitted):
    tracer = obs_trace.enable(capacity=1 << 14)
    _serve(fitted, obs_registry.Registry())
    spans = tracer.spans()
    submits = [s for s in spans if s.name == "serve.submit"]
    assert len(submits) == 2 * N_ASYNC
    reqs = [dict(s.attrs)["req"] for s in submits]
    assert sorted(reqs) == list(range(1, 2 * N_ASYNC + 1))
    assert all(s.thread != "fleet-microbatch" for s in submits)
    # a flush names its oldest member: the first request of each flush of
    # a kind follows the last of the flush before it
    for kind in ("score", "predict"):
        firsts = sorted(dict(f.attrs)["req"] for f in spans
                        if f.name == "serve.flush"
                        and dict(f.attrs)["kind"] == kind)
        kind_reqs = sorted(dict(s.attrs)["req"] for s in submits
                           if dict(s.attrs)["kind"] == kind)
        assert firsts[0] == kind_reqs[0]
        assert set(firsts) <= set(kind_reqs)


def test_queue_wait_counts_exactly_the_queued_requests(fitted):
    reg = obs_registry.Registry()
    _serve(fitted, reg)
    wait = reg.histogram("figmn_serve_queue_wait_seconds")
    assert wait.count == 2 * N_ASYNC
    assert wait.sum > 0
    # synchronous reads bypass the queue: the latency histogram holds
    # them too, the queue wait does not
    lat = reg.histogram("figmn_serve_latency_seconds")
    assert lat.count == 2 * N_ASYNC + 2
    assert wait.sum <= lat.sum


def test_resolve_copies_one_per_dispatched_flush(fitted):
    cfg, state, x = fitted
    reg = obs_registry.Registry()
    tracer = obs_trace.enable(capacity=1 << 14)
    fe = ScoringFrontend(cfg, registry=reg,
                         admission=AdmissionConfig(max_batch=5,
                                                   max_delay_s=0.01))
    fe.publish(state)
    # deadlines that elapse in the queue: dropped before any dispatch
    late = [fe.score_async(x[i:i + 1], deadline_s=1e-6) for i in range(3)]
    for f in late:
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=60)
    futs = [fe.score_async(x[i:i + 1]) for i in range(N_ASYNC)]
    futs += [fe.predict_async(x[i:i + 1, :4], [4], return_var=True)
             for i in range(N_ASYNC)]
    for f in futs:
        f.result(timeout=60)
    fe.close()
    spans = tracer.spans()
    flushes = [s for s in spans if s.name == "serve.flush"]
    dispatched = [f for f in flushes if any(
        c.name in ("serve.score", "serve.predict")
        for c in _children(spans, f))]
    assert len(dispatched) < len(flushes)      # the deadline flush
    assert len(dispatched) >= 2 * N_ASYNC // 5
    assert reg.counter("figmn_serve_resolve_copies_total").value == \
        len(dispatched)


def test_tracing_off_answers_bit_identical_and_no_spans(fitted):
    traced = obs_trace.enable(capacity=1 << 14)
    on = _serve(fitted, obs_registry.Registry())
    assert traced.spans()
    quiet = obs_trace.enable(capacity=1 << 14)
    obs_trace.disable()
    reg = obs_registry.Registry()
    off = _serve(fitted, reg)
    assert quiet.spans() == []
    assert len(on) == len(off)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
    # the counter does not depend on tracing
    assert reg.histogram("figmn_serve_queue_wait_seconds").count == \
        2 * N_ASYNC


def test_ingest_chunk_holds_guard_pull_fence():
    x = _blobs(seed=3, n=150)
    cfg = _cfg(x)
    tracer = obs_trace.enable(capacity=1 << 12)
    rt = StreamRuntime(cfg, RuntimeConfig(chunk=32, path="scan"))
    rt.ingest(x)
    spans = tracer.spans()
    chunks = [s for s in spans if s.name == "stream.ingest_chunk"]
    assert len(chunks) == 5
    for c in chunks:
        assert [k.name for k in _children(spans, c)] == [
            "stream.chunk_guard", "stream.chunk_pull", "stream.chunk_fence"]
