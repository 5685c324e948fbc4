"""The trace reduction on a synthetic trace and on one recorded here."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import tracing
from perfbench.tracing import Event, Trace


def synthetic():
    ops = [Event("fusion.1", 100, 200), Event("fusion.2", 150, 300),
           Event("fusion.3", 500, 600), Event("copy", 700, 710)]
    progs = [Event("jit_fit(11)", 100, 300), Event("jit_fit(11)", 500, 600),
             Event("jit_other.2", 700, 710)]
    host = [Event("perfbench.window", 0, 1000),
            Event("api.partial_fit", 40, 660),
            Event("stream.ingest_chunk", 50, 650),
            Event("PjRtExecute", 300, 500)]
    return Trace({"/device:TPU:0": {"ops": ops, "programs": progs}}, host)


def test_window_busy_and_programs():
    tr = synthetic()
    win = tracing.window(tr)
    assert win == (0, 1000)
    assert tracing.busy_ns(tr, win) == 310
    progs = tracing.programs(tr, win)
    assert progs["jit_fit"] == (pytest.approx(300e-9), 2)
    assert progs["jit_other"] == (pytest.approx(10e-9), 1)
    assert tracing.matching_seconds(progs, ("jit_fit",)) == \
        pytest.approx(300e-9)


def test_busy_is_clipped_to_the_window():
    tr = synthetic()
    assert tracing.busy_ns(tr, (150, 550)) == 150 + 50


def test_idle_gaps_go_to_the_innermost_span():
    tr = synthetic()
    gaps = dict((n, (s, k)) for n, s, k in
                tracing.idle_gaps(tr, tracing.window(tr)))
    # [0, 100): mid 50, inside partial_fit and ingest_chunk (inner);
    # [300, 500): mid 400, ingest_chunk (PjRtExecute is not a span);
    # [600, 700): mid 650, ingest_chunk has closed, partial_fit has not;
    # [710, 1000): no span open at the middle
    assert gaps["stream.ingest_chunk"] == (pytest.approx(300e-9), 2)
    assert gaps["api.partial_fit"] == (pytest.approx(100e-9), 1)
    assert gaps["(no span)"] == (pytest.approx(290e-9), 1)


def test_idle_between_consecutive_programs():
    tr = synthetic()
    win = tracing.window(tr)
    runs = tracing.program_events(tr, win, ("jit_fit",))
    assert runs == [(100, 300), (500, 600)]
    assert tracing.idle_between(tr, win, runs) == 200


def test_union_and_complement():
    iv = tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)])
    assert iv == [(0, 3), (5, 9)]
    assert tracing.complement(iv, (0, 10)) == [(3, 5), (9, 10)]


def test_a_recorded_trace_loads(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("perfbench.window"):
        with jax.profiler.TraceAnnotation("stream.ingest_chunk"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.load(str(tmp_path))
    lo, hi = tracing.window(tr)
    assert hi > lo
    assert any(e.name == "stream.ingest_chunk" and lo <= e.start_ns <= hi
               for e in tr.host)


def test_a_trace_without_the_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.window(Trace({}, [Event("x.y", 0, 1)]))
