"""The serving front end's readers (``read_queue_wait_ms``,
``flush_host_ms``, ``programs_per_flush``) on a synthetic trace and a
hand-filled registry, and on a tiny traced read run on the CPU."""
import json
import os
import shutil

import pytest

from perfbench import catalog, run, spans
from perfbench.tracing import Event, Trace
from repro.obs import registry as obs_registry


def _reader(name):
    return catalog.load_module("metrics", name)


def synthetic():
    """Two flushes in the window and one cut by its end, with its read;
    a read span of another thread outside any flush."""
    host = [Event("perfbench.window", 0, 1000),
            Event("serve.flush", 100, 200),       # host 100 - 60 = 40
            Event("serve.gather", 100, 120),
            Event("serve.score", 120, 180),
            Event("serve.resolve", 180, 200),
            Event("serve.flush", 300, 450),       # host 150 - 100 = 50
            Event("serve.predict", 320, 420),
            Event("serve.score", 500, 520),
            Event("serve.flush", 900, 1100),      # clipped: 100 - 50 = 50
            Event("serve.predict", 950, 1050)]
    progs = [Event(f"jit_p.{i}", 10 * i, 10 * i + 5) for i in range(9)]
    return Trace({"/device:TPU:0": {"ops": progs, "programs": progs}}, host)


def _ctx(tr, **kw):
    from perfbench import tracing
    win = tracing.window(tr)
    return dict({"trace": tr, "win": win,
                 "programs": tracing.programs(tr, win)}, **kw)


def test_flush_host_is_each_flush_less_its_read():
    tr = synthetic()
    ctx = {"trace": _ctx(tr)}
    assert spans.host_ns([(100, 200), (300, 450), (900, 1000)],
                         [(120, 180), (320, 420), (500, 520),
                          (950, 1000)]) == [40, 50, 50]
    # the last flush is clipped to the window, with its read
    assert _reader("flush_host_ms").read(ctx) == \
        pytest.approx((40 + 50 + 50) / 3 * 1e-6)


def test_a_read_crossing_the_flush_end_is_not_its_child():
    assert spans.host_ns([(0, 100)], [(50, 150)]) == [100]
    assert spans.host_ns([(0, 100)], [(10, 30), (20, 40)]) == [70]


def test_programs_per_flush():
    ctx = {"trace": _ctx(synthetic())}
    assert _reader("programs_per_flush").read(ctx) == pytest.approx(9 / 3)


@pytest.mark.parametrize("name", ["flush_host_ms", "programs_per_flush"])
def test_trace_readers_find_nothing(name):
    r = _reader(name)
    assert r.read({"trace": None}) is None
    # the parent program: no flush spans in the trace
    bare = Trace(synthetic().devices, [Event("perfbench.window", 0, 1000),
                                       Event("serve.score", 10, 20)])
    assert r.read({"trace": _ctx(bare)}) is None
    # no device plane: no programs to count
    if name == "programs_per_flush":
        assert r.read({"trace": _ctx(Trace({}, synthetic().host))}) is None


@pytest.fixture
def registry():
    reg = obs_registry.Registry()
    old = obs_registry.set_default(reg)
    yield reg
    obs_registry.set_default(old)


def test_queue_wait_reader(registry):
    h = registry.histogram("figmn_serve_queue_wait_seconds")
    for s in (0.010, 0.020, 0.030):
        h.observe(s)
    r = _reader("read_queue_wait_ms")
    assert r.read({"rows": 3}) == pytest.approx(20.0)
    # more popped than answered (a request that failed) still reads
    assert r.read({"rows": 2}) == pytest.approx(20.0)
    # fewer: something missed the counter
    assert r.read({"rows": 4}) is None


def test_queue_wait_reader_without_the_histogram(registry):
    r = _reader("read_queue_wait_ms")
    assert r.read({"rows": 3}) is None
    assert not any(m.name == "figmn_serve_queue_wait_seconds"
                   for m in registry.collect())    # read, never created
    registry.histogram("figmn_serve_queue_wait_seconds")
    assert r.read({"rows": 0}) is None              # created, never fed


NEW = ("read_queue_wait_ms", "flush_host_ms", "programs_per_flush")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("bench")
    for kind in ("drivers", "metrics"):
        shutil.copytree(os.path.join(catalog.ROOT, kind), r / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic", "limits"):
        (r / kind).mkdir()
    cfg = catalog.load_json("configs", "mnist-k1024")
    cfg.update(features=6, classes=3, dim=9, kmax=8, chunk=4, name="tiny")
    (r / "configs" / "tiny.json").write_text(json.dumps(cfg))
    read = catalog.load_json("traffic", "read")
    read.update(knee_per_s=250, load=0.8, admission={"max_batch": 4},
                checked_per_kind=10 ** 6, drain_s=30)
    (r / "traffic" / "read.json").write_text(json.dumps(read))
    (r / "limits" / "tiny.read.json").write_text(
        json.dumps(catalog.load_json("limits", "mnist-k1024.read")))
    return str(r)


def test_a_traced_read_run_reads_the_new_metrics(root, registry):
    with open(os.path.join(os.path.dirname(catalog.ROOT),
                           "BENCHMARK.json")) as f:
        real = json.load(f)
    per_layer = [dict(m, workloads=["tiny.read"])
                 for m in real["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in per_layer] == list(NEW)
    bench = {"workloads": [{"name": "tiny.read", "config": "tiny",
                            "traffic": "read", "chips": 1}],
             "end_to_end": [{"name": "read_p95_ms", "unit": "ms",
                             "workloads": ["tiny.read"]}],
             "per_layer": per_layer}
    res = run.run_cell(bench, "tiny.read", 2 ** 31 + 91, 0.6, True,
                       root=root, check_chips=False)
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["read_queue_wait_ms"] > 0
    assert 0 < m["flush_host_ms"] < 1e3 * res["device"]["window_s"]
    # the CPU profiler writes no device plane: programs only on a chip
    assert ("programs_per_flush" in m) == bool(res["breakdown"]["device_ops"])
