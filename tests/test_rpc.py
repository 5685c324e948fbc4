"""ISSUE 10 suite: wire codec, RPC framing, worker processes, and the
process-placement fleet honouring the threaded fleet's contracts.

Layers, cheapest first:

  codec      bit-identity round trip of FIGMNState/export_pool trees
             through the versioned blob (shared by RPC frames and
             on-disk payloads); corruption detection.
  wire       frame round trip over a socketpair; digest verification;
             silence -> WorkerTimeout.
  protocol   config docs (FIGMNConfig / RuntimeConfig / FaultPlan)
             surviving JSON.
  worker     one real worker process driven through the action
             vocabulary (module-scoped: spawns are jax-import priced).
  fleet      placement="process" vs placement="thread" on the same
             stream — bit-identical replica states; scale-up mass
             conservation over the wire; kill-one-worker supervised
             recovery with the exact mass identity.
  manifest   incarnation-namespaced checkpoint dirs: a restarted fleet
             never reads a previous run's steps except through an
             explicit pinned resume.
"""
import dataclasses
import os
import socket

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.checkpoint import codec  # noqa: E402
from repro.core import figmn  # noqa: E402
from repro.core.types import FIGMNConfig  # noqa: E402
from repro.fleet import FleetConfig, FleetCoordinator, sp_mass  # noqa: E402
from repro.ft import RetryPolicy, SupervisorConfig  # noqa: E402
from repro.rpc import (RpcConfig, WorkerClient, protocol,  # noqa: E402
                       wire)
from repro.stream import (DriftConfig, LifecycleConfig,  # noqa: E402
                          RuntimeConfig)

pytestmark = pytest.mark.fleet

D, KMAX = 4, 16


def _draw(n, seed=0, d=D):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 6.0, (4, d))
    x = centers[rng.integers(0, 4, n)] + rng.normal(0, 1.0, (n, d))
    return x.astype(np.float32)


def _cfg(sample=None):
    sigma = (figmn.sigma_from_data(jnp.asarray(sample), 1.0)
             if sample is not None else None)
    return FIGMNConfig(kmax=KMAX, dim=D, beta=0.1, delta=1.0,
                       vmin=10 ** 9, spmin=0.0, update_mode="exact",
                       sigma_ini=sigma)


# ---------------------------------------------------------------------------
# codec: the wire-serialisation satellite
# ---------------------------------------------------------------------------

def _fit_state(n=256, seed=1):
    cfg = _cfg(_draw(64, seed))
    state = figmn.fit(cfg, figmn.init_state(cfg),
                      jnp.asarray(_draw(n, seed)))
    return cfg, state


def test_codec_state_round_trip_bit_identical():
    cfg, state = _fit_state()
    blob = codec.encode_tree(state, meta={"state_epoch": 7})
    back = codec.decode_tree(blob, template=figmn.init_state(cfg))
    for name in ("mu", "lam", "logdet", "sp", "v", "active"):
        a = np.asarray(getattr(state, name))
        b = np.asarray(getattr(back, name))
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)   # BIT identical, not close
    man = codec.decode_manifest(blob)
    assert man["meta"]["state_epoch"] == 7


def test_codec_numpy_leaves_stay_numpy():
    """64-bit host counters must not round through jnp (silent downcast
    under no-x64) — template-typed decode keeps numpy leaves numpy."""
    tree = {"counters": np.arange(5, dtype=np.int64),
            "wall": np.float64(3.5),
            "dev": jnp.ones((3,), jnp.float32)}
    blob = codec.encode_tree(tree)
    back = codec.decode_tree(blob, template=tree)
    assert isinstance(back["counters"], np.ndarray)
    assert back["counters"].dtype == np.int64
    np.testing.assert_array_equal(back["counters"], tree["counters"])


def test_codec_detects_payload_corruption():
    _, state = _fit_state()
    blob = bytearray(codec.encode_tree(state))
    blob[-20] ^= 0xFF
    with pytest.raises(codec.CodecError):
        codec.decode_tree(bytes(blob))


def test_codec_rejects_bad_magic():
    with pytest.raises(codec.CodecError):
        codec.decode_tree(b"NOPE" + b"\x00" * 64)


# ---------------------------------------------------------------------------
# wire framing
# ---------------------------------------------------------------------------

def _pair():
    return socket.socketpair()


def test_wire_frame_round_trip():
    a, b = _pair()
    payload = os.urandom(65536)
    wire.send_frame(a, {"action": "x", "args": {"k": 1}}, payload)
    header, got = wire.recv_frame(b, timeout_s=5.0)
    assert header["action"] == "x" and header["args"] == {"k": 1}
    assert got == payload
    a.close(); b.close()


def test_wire_numpy_scalars_in_headers():
    a, b = _pair()
    wire.send_frame(a, {"n": np.int64(3), "t": np.float32(0.5),
                        "v": np.arange(2)})
    header, _ = wire.recv_frame(b, timeout_s=5.0)
    assert header["n"] == 3 and header["v"] == [0, 1]
    a.close(); b.close()


def test_wire_detects_corrupted_payload():
    a, b = _pair()
    payload = b"abcdef" * 100
    header = {"action": "x"}
    # hand-roll the frame with a wrong digest
    import json as _json
    h = dict(header, payload_blake2="0" * 32)
    hj = _json.dumps(h).encode()
    a.sendall(wire._HEADER.pack(wire.MAGIC, wire.WIRE_VERSION, len(hj),
                                len(payload)) + hj + payload)
    with pytest.raises(wire.WireProtocolError, match="digest"):
        wire.recv_frame(b, timeout_s=5.0)
    a.close(); b.close()


def test_wire_silence_is_timeout_death_is_died():
    a, b = _pair()
    with pytest.raises(wire.WorkerTimeout):
        wire.recv_frame(b, timeout_s=0.05)
    a.close()
    with pytest.raises(wire.WorkerDied):
        wire.recv_frame(b, timeout_s=1.0)
    b.close()


def test_wire_rejects_version_skew():
    a, b = _pair()
    a.sendall(wire._HEADER.pack(wire.MAGIC, 99, 2, 0) + b"{}")
    with pytest.raises(wire.WireProtocolError, match="version"):
        wire.recv_frame(b, timeout_s=5.0)
    a.close(); b.close()


# ---------------------------------------------------------------------------
# protocol: config docs over JSON
# ---------------------------------------------------------------------------

def test_protocol_figmn_config_round_trip():
    cfg = _cfg(_draw(64, 3))
    doc = protocol.figmn_config_to_doc(cfg)
    import json as _json
    back = protocol.figmn_config_from_doc(_json.loads(_json.dumps(doc)))
    for f in dataclasses.fields(cfg):
        a, b = getattr(cfg, f.name), getattr(back, f.name)
        if f.name == "sigma_ini":
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=0)
        else:
            assert a == b, f.name


def test_protocol_runtime_config_round_trip():
    rcfg = RuntimeConfig(
        chunk=64, lifecycle=LifecycleConfig(every=2),
        drift=DriftConfig(window=8), checkpoint_every=2,
        chunk_retry=RetryPolicy(max_retries=2, base_delay_s=0.01))
    doc = protocol.runtime_config_to_doc(rcfg)
    import json as _json
    back = protocol.runtime_config_from_doc(_json.loads(_json.dumps(doc)))
    assert back.chunk == 64
    assert back.lifecycle == rcfg.lifecycle
    assert back.drift == rcfg.drift
    assert back.chunk_retry == rcfg.chunk_retry


# ---------------------------------------------------------------------------
# one real worker process, driven through the action vocabulary
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def worker(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("worker_ckpt"))
    cfg = _cfg(_draw(64, 5))
    rcfg = RuntimeConfig(chunk=64, checkpoint_dir=d)
    w = WorkerClient(0, protocol.figmn_config_to_doc(cfg),
                     protocol.runtime_config_to_doc(rcfg),
                     RpcConfig())
    yield w, cfg
    w.close()


def test_worker_ping(worker):
    w, _ = worker
    res, _ = w.call("ping")
    assert res["rid"] == 0 and res["pid"] != os.getpid()
    assert res["protocol_version"] == protocol.PROTOCOL_VERSION


def test_worker_ingest_streams_chunk_heartbeats(worker):
    w, _ = worker
    events = []
    res, _ = w.call(
        "ingest_chunk",
        payload=codec.encode_tree({"rows": _draw(256, 6)}),
        on_event=events.append, timeout_s=120.0)
    assert res["summary"]["total_points"] >= 256
    # 256 points / chunk 64 -> 4 chunk boundary events streamed
    assert len(events) == 4
    assert sum(e["n_points"] for e in events) == 256
    assert res["total_points"] == res["summary"]["total_points"]


def test_worker_pool_round_trip_and_epoch(worker):
    w, _ = worker
    res, blob = w.call("export_pool")
    epoch = res["state_epoch"]
    res2, _ = w.call("import_pool", payload=blob)
    assert res2["state_epoch"] > epoch        # import bumps the epoch
    _, blob2 = w.call("export_pool")
    st1 = codec.decode_tree(blob)
    st2 = codec.decode_tree(blob2)
    for k in st1:
        np.testing.assert_array_equal(st1[k], st2[k])


def test_worker_checkpoint_resume_shared_fs(worker):
    w, _ = worker
    res, _ = w.call("checkpoint")
    step = res["step"]
    assert step is not None
    res2, _ = w.call("resume", args={"step": step})
    assert res2["resumed"] is True


def test_worker_error_reply_preserves_type(worker):
    w, _ = worker
    res, _ = w.call("resume", args={"step": 10 ** 9})
    assert res["resumed"] is False            # missing step: False, no err
    with pytest.raises(protocol.RemoteError) as ei:
        w.call("no_such_action")
    assert ei.value.remote_type == "ProtocolError"
    res, _ = w.call("ping")                   # worker survived the error
    assert res["rid"] == 0


def test_worker_metrics_dump_merges(worker):
    from repro.obs import export as obs_export
    w, _ = worker
    res, _ = w.call("metrics")
    dump = res["dump"]
    assert dump["metrics"], "worker registry should not be empty"
    merged = obs_export.merge_dumps([dump, dump])
    by_key = {(e["name"], tuple(sorted(e["labels"].items())))
              for e in merged["metrics"]}
    assert len(by_key) == len(merged["metrics"])
    # doubling a counter dump doubles the value
    for e in dump["metrics"]:
        if e["kind"] == "counter" and e.get("value", 0) > 0:
            m = next(x for x in merged["metrics"]
                     if x["name"] == e["name"]
                     and x["labels"] == e["labels"])
            assert m["value"] == pytest.approx(2 * e["value"])
            break
    text = obs_export.prometheus_text_from_dump(merged)
    assert "# TYPE" in text


def test_worker_resume_step_false_not_error(worker):
    w, _ = worker
    res, _ = w.call("resume", args={"step": None})
    assert res["resumed"] is True


# ---------------------------------------------------------------------------
# process fleet == threaded fleet (the acceptance contract)
# ---------------------------------------------------------------------------

def test_process_placement_refused_on_an_accelerator(monkeypatch):
    """A coordinator that runs on an accelerator holds its chip: worker
    processes could not reach it, so process placement fails before any
    worker is spawned."""
    import jax
    spawned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(WorkerClient, "__init__",
                        lambda self, *a, **k: spawned.append(a))
    with pytest.raises(RuntimeError, match="placement='process'"):
        FleetCoordinator(_cfg(_draw(64)), FleetConfig(placement="process"),
                         RuntimeConfig(chunk=64))
    assert spawned == []


@pytest.mark.slow
def test_process_fleet_matches_threaded_fleet(tmp_path):
    xs = _draw(768, 7)
    hold = _draw(256, 8)
    cfg = _cfg(xs[:128])
    rcfg = RuntimeConfig(chunk=64)
    fk = dict(n_replicas=2, router="hash", consolidate_every=1)

    fl_t = FleetCoordinator(cfg, FleetConfig(**fk), rcfg)
    fl_p = FleetCoordinator(
        cfg, FleetConfig(placement="process",
                         checkpoint_dir=str(tmp_path), **fk), rcfg)
    try:
        fl_t.ingest(xs)
        fl_p.ingest(xs)
        # replica states bit-identical: same stream, same router, same
        # arithmetic — the wire moved the computation, not the numbers
        for rt, rp in zip(fl_t.replicas, fl_p.replicas):
            np.testing.assert_array_equal(np.asarray(rt.state.sp),
                                          np.asarray(rp.state.sp))
            np.testing.assert_array_equal(np.asarray(rt.state.mu),
                                          np.asarray(rp.state.mu))
        ll_t = float(np.mean(np.asarray(fl_t.score(hold))))
        ll_p = float(np.mean(np.asarray(fl_p.score(hold))))
        assert abs(ll_t - ll_p) <= 0.05
        # scale-up over RPC conserves active mass exactly
        mass0 = sum(float(sp_mass(r.state)) for r in fl_p.replicas)
        assert fl_p.scale_up(0, reason="test")
        mass1 = sum(float(sp_mass(r.state)) for r in fl_p.replicas)
        assert mass0 == mass1
        assert fl_p.replicas[-1].alive
        # scale-down releases the worker process
        retired = fl_p.replicas[-1]
        assert fl_p.scale_down(fl_p.replica_ids[-1], 0, reason="test")
        assert not retired.alive
    finally:
        fl_t.close()
        fl_p.close()


@pytest.mark.slow
def test_killed_worker_recovers_with_exact_mass_identity(tmp_path):
    cfg = _cfg(_draw(128, 9))
    rcfg = RuntimeConfig(chunk=40, checkpoint_every=1)
    scfg = SupervisorConfig(heartbeat_timeout_s=15.0,
                            retry=RetryPolicy(max_retries=1,
                                              base_delay_s=0.01))
    fl = FleetCoordinator(
        cfg, FleetConfig(n_replicas=3, router="hash", consolidate_every=2,
                         placement="process", supervisor=scfg,
                         checkpoint_dir=str(tmp_path)), rcfg)
    try:
        ingested = 0
        for i in range(2):
            fl.ingest(_draw(240, 10 + i))
            ingested += 240
        fl.replicas[1].kill()                  # SIGKILL mid-stream
        for i in range(4):                     # detect + recover window
            fl.ingest(_draw(240, 20 + i))
            ingested += 240
        s = fl.summary()
        assert s["quarantined_replicas"] == []
        assert all(r.alive for r in fl.replicas)
        mass = sum(float(sp_mass(r.state)) for r in fl.replicas)
        lhs = (mass + s["supervisor_points_lost"]
               - s["supervisor_points_replayed"])
        assert abs(lhs - ingested) / ingested < 1e-5
        # the failure was classed worker_dead, not crash
        dump = fl.fleet_metrics()
        dead = [e for e in dump["metrics"]
                if e["name"] == "figmn_replica_failures_total"
                and e["labels"].get("reason") == "worker_dead"]
        assert dead and dead[0]["value"] >= 1
    finally:
        fl.close()


# ---------------------------------------------------------------------------
# incarnation-namespaced checkpoint dirs (satellite: restart safety)
# ---------------------------------------------------------------------------

def _mini_fleet(root, **kw):
    cfg = _cfg(_draw(64, 11))
    return cfg, FleetCoordinator(
        cfg, FleetConfig(n_replicas=2, router="hash", consolidate_every=1,
                         checkpoint_dir=root, **kw),
        RuntimeConfig(chunk=64, checkpoint_every=1))


def test_restarted_fleet_never_reads_previous_incarnation(tmp_path):
    """The stale-ceiling fix: a NEW fleet on the SAME checkpoint root
    allocates fresh incarnation dirs, so its replicas see NO steps from
    the previous run (only an explicit resume() pins them back)."""
    root = str(tmp_path)
    cfg, fl1 = _mini_fleet(root)
    fl1.ingest(_draw(256, 12))
    fl1.checkpoint()
    assert fl1.replicas[0].ckpt.latest_step() is not None
    sp1 = np.asarray(fl1.replicas[0].state.sp)
    fl1.close()

    _, fl2 = _mini_fleet(root)
    # incarnations moved past the first run's:
    assert fl2._incarnations[0] > fl1._incarnations[0]
    # fresh dirs: no inherited steps, no stale restore ceilings
    assert fl2.replicas[0].ckpt.latest_step() is None
    # explicit resume pins the manifest's incarnations and restores
    assert fl2.resume()
    assert fl2._incarnations == fl1._incarnations
    np.testing.assert_array_equal(
        np.asarray(fl2.replicas[0].state.sp), sp1)
    fl2.close()


def test_incarnation_dirs_are_namespaced_on_disk(tmp_path):
    root = str(tmp_path)
    _, fl = _mini_fleet(root)
    fl.ingest(_draw(128, 13))
    fl.checkpoint()
    d = fl._replica_dir(0)
    assert os.path.basename(d).startswith("inc_")
    assert os.path.basename(os.path.dirname(d)) == "replica_0"
    assert any(n.startswith("step_") for n in os.listdir(d))
    fl.close()


def test_scale_up_allocates_fresh_incarnation(tmp_path):
    root = str(tmp_path)
    # plant a fake previous life for the id scale-up will allocate
    old = os.path.join(root, "replica_2", "inc_0")
    os.makedirs(old)
    _, fl = _mini_fleet(root)
    fl.ingest(_draw(256, 14))
    assert fl.scale_up(0, reason="test")
    new_id = fl.replica_ids[-1]
    assert new_id == 2
    assert fl._incarnations[2] == 1           # past the planted inc_0
    assert fl.replicas[-1].ckpt.latest_step() is None
    fl.close()


def test_legacy_manifest_resumes_bare_dirs(tmp_path):
    """A pre-incarnation manifest (no 'incarnations' key) must resume
    from the bare replica_<rid> dirs it described."""
    import json
    root = str(tmp_path)
    cfg, fl = _mini_fleet(root)
    fl.ingest(_draw(256, 15))
    fl.checkpoint()
    sp = np.asarray(fl.replicas[0].state.sp)
    man_path = os.path.join(root, "fleet_manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    incs = man.pop("incarnations")
    # move each replica's steps to the legacy bare location
    import shutil
    for rid_s, inc in incs.items():
        base = os.path.join(root, f"replica_{rid_s}")
        inc_dir = os.path.join(base, f"inc_{inc}")
        for name in os.listdir(inc_dir):
            shutil.move(os.path.join(inc_dir, name),
                        os.path.join(base, name))
        os.rmdir(inc_dir)
    with open(man_path, "w") as f:
        json.dump(man, f)
    fl.close()

    _, fl2 = _mini_fleet(root)
    assert fl2.resume()
    assert fl2._incarnations == {0: None, 1: None}
    np.testing.assert_array_equal(
        np.asarray(fl2.replicas[0].state.sp), sp)
    fl2.close()
