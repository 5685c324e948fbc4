"""Device-calibrated dispatch (stream.costmodel): table persistence,
decision determinism, the bit-compat no-table fallback to the PR-6
heuristic, the non-overridable vmem launch guard, table-driven regime
flips, and the gather/scatter HLO traffic accounting the predictions
rest on."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import devices
from repro.core import figmn, inference
from repro.core.types import FIGMNConfig
from repro.distributed import hlo_analysis
from repro.kernels import figmn_stream
from repro.obs import prof
from repro.stream import costmodel, ingest


@pytest.fixture
def tpu_kind(monkeypatch):
    """Decisions *for* a v5e made on this CPU host: the "tpu" platform
    reports the v5e's device kind, every other platform its own."""
    real = devices.device_kind
    monkeypatch.setattr(devices, "device_kind", lambda platform=None:
                        "TPU v5 lite" if platform == "tpu" else real(platform))


def _cfg(k=16, d=8, c=0, **kw):
    defaults = dict(kmax=k, dim=d, beta=0.1, delta=1.0, shortlist_c=c,
                    sigma_ini=np.ones((d,), np.float32))
    defaults.update(kw)
    return FIGMNConfig(**defaults)


def _cell(kind, path, k, d, c, n, measured_s, predicted_s=None):
    return {"kind": kind, "path": path, "k": k, "d": d, "c": c, "n": n,
            "measured_s": measured_s,
            "per_point_s": measured_s / max(n, 1),
            "hlo": None, "compute_s": None, "memory_s": None,
            "predicted_s": predicted_s,
            "bottleneck": "memory" if predicted_s else None}


def _table(cells, dkey=None):
    t = costmodel.CostTable(meta={"backend": jax.default_backend(),
                                  "device_key": costmodel.device_key()})
    dkey = dkey or costmodel.device_key()
    for c in cells:
        t.add_cell(dkey, c)
    return t


# -- persistence ----------------------------------------------------------

def test_save_load_round_trip(tmp_path):
    t = _table([_cell("ingest", "scan", 16, 8, 0, 128, 1e-3),
                _cell("ingest", "sparse", 16, 8, 4, 128, 2e-3)])
    p = str(tmp_path / "table.json")
    t.save(p)
    t2 = costmodel.CostTable.load(p)
    assert t2.entries == t.entries
    assert t2.meta == t.meta


def test_unknown_version_raises(tmp_path):
    doc = _table([_cell("ingest", "scan", 16, 8, 0, 128, 1e-3)]).to_doc()
    doc["cost_table_version"] = 999
    with pytest.raises(ValueError, match="version"):
        costmodel.CostTable.from_doc(doc)


def test_merge_keeps_faster_measurement_and_unions_devices():
    dk = costmodel.device_key()
    a = _table([_cell("ingest", "scan", 16, 8, 0, 128, 2e-3)])
    b = _table([_cell("ingest", "scan", 16, 8, 0, 128, 1e-3),
                _cell("ingest", "scan", 64, 8, 0, 128, 5e-3)])
    b.add_cell("other|jax-0", _cell("ingest", "scan", 16, 8, 0, 128, 9e-3))
    m = a.merge(b)
    cell = m.lookup(dk, "ingest", "scan", k=16, d=8, n=128)
    assert cell["measured_s"] == 1e-3          # min wins over a's 2e-3
    assert len(m.cells(dk, "ingest", "scan")) == 2
    assert "other|jax-0" in m.device_keys()
    # merge is non-destructive
    assert a.lookup(dk, "ingest", "scan", k=16, d=8,
                    n=128)["measured_s"] == 2e-3


def test_from_any_accepts_none_table_path_dict(tmp_path):
    t = _table([_cell("ingest", "scan", 16, 8, 0, 128, 1e-3)])
    p = str(tmp_path / "t.json")
    t.save(p)
    assert costmodel.CostTable.from_any(None) is None
    assert costmodel.CostTable.from_any(t) is t
    assert costmodel.CostTable.from_any(p).entries == t.entries
    assert costmodel.CostTable.from_any(t.to_doc()).entries == t.entries
    with pytest.raises(TypeError):
        costmodel.CostTable.from_any(42)


# -- no-table fallback: bit-compat with the PR-6 heuristic ----------------

def _pr6_heuristic(cfg, vmem_budget, requested, backend, chunk=256):
    """The pre-costmodel select_path, reimplemented verbatim: the contract
    the no-table fallback is pinned to (the working set is what the
    kernel holds in VMEM at the chunk size)."""
    if requested == "sparse" or (requested == "auto"
                                 and cfg.shortlist_c > 0):
        return "sparse"
    if requested in ("scan", "vmem"):
        return requested
    working_set = figmn_stream.vmem_bytes(cfg.kmax, cfg.dim, chunk)
    if (cfg.update_mode == "exact" and working_set <= vmem_budget
            and backend == "tpu"):
        return "vmem"
    return "scan"


def test_no_table_decisions_pin_pr6_heuristic_across_grid(tpu_kind):
    budgets = (None, 1024, 12 * 2 ** 20, 1 << 30)
    cfgs = [_cfg(16, 8), _cfg(16, 8, c=4), _cfg(512, 64),
            _cfg(512, 64, c=16), _cfg(64, 16, update_mode="joseph")]
    for cfg, budget, device in itertools.product(
            cfgs, budgets, ("cpu", "tpu", None)):
        reqs = ["auto", "scan", "vmem"]
        if cfg.shortlist_c > 0:
            reqs.append("sparse")
        for requested in reqs:
            d = costmodel.decide(cfg, requested=requested,
                                 vmem_budget=budget, device=device,
                                 cost_table=None)
            eff_budget = d.vmem_budget
            backend = device if device else jax.default_backend()
            want = _pr6_heuristic(cfg, eff_budget, requested, backend)
            assert d.path == want, (cfg.kmax, cfg.dim, cfg.shortlist_c,
                                    requested, budget, device)
            # and the live select_path agrees (it IS the fallback)
            assert d.path == ingest.select_path(
                cfg, vmem_budget=eff_budget, requested=requested,
                device=backend)
            assert d.reason in ("forced", "heuristic")


def test_cpu_vmem_budget_falls_back_to_constant(tpu_kind):
    # the budget is the device kind's table entry: the CPU has no VMEM,
    # the v5e its 128 MiB; an explicit budget still wins
    assert costmodel.resolve_vmem_budget(None, "cpu") == (0, "device")
    assert costmodel.resolve_vmem_budget(None, "tpu") == (
        devices.lookup("TPU v5 lite").vmem_bytes, "device")
    assert costmodel.resolve_vmem_budget(4096, "cpu") == (4096, "config")


@pytest.mark.parametrize("lookup", ["peaks", "vmem_budget"])
def test_unknown_device_kind_raises(monkeypatch, lookup):
    """Neither a roofline peak nor the kernel's VMEM budget is ever
    guessed for a device the table does not know."""
    monkeypatch.setattr(devices, "device_kind",
                        lambda platform=None: "TPU v99")
    with pytest.raises(devices.UnknownDeviceError, match="TPU v99"):
        if lookup == "peaks":
            prof.roofline_terms({"flops": 1.0, "traffic_bytes": 1.0},
                                devices.device_kind())
        else:
            costmodel.resolve_vmem_budget(None, "tpu")


# -- determinism ----------------------------------------------------------

def test_decisions_deterministic_and_stable_across_save_load(tmp_path):
    t = _table([_cell("ingest", "scan", 16, 8, 0, 128, 1e-3),
                _cell("ingest", "sparse", 16, 8, 4, 128, 2e-3),
                _cell("ingest", "scan", 64, 16, 0, 128, 4e-3),
                _cell("ingest", "sparse", 64, 16, 8, 128, 1e-3)])
    p = str(tmp_path / "t.json")
    t.save(p)
    t2 = costmodel.CostTable.load(p)
    for cfg in (_cfg(16, 8, c=4), _cfg(64, 16, c=8), _cfg(100, 12, c=6)):
        first = costmodel.decide(cfg, chunk=128, cost_table=t)
        for table in (t, t2, p):
            again = costmodel.decide(cfg, chunk=128, cost_table=table)
            assert again.path == first.path
            assert again.reason == first.reason
            assert again.candidates == first.candidates


def test_lookup_tie_break_is_deterministic():
    # two cells equidistant from the query resolve by cell key, not by
    # insertion order
    dk = costmodel.device_key()
    a = _cell("ingest", "scan", 8, 8, 0, 128, 1e-3)
    b = _cell("ingest", "scan", 32, 8, 0, 128, 2e-3)
    t_ab = _table([a, b])
    t_ba = _table([b, a])
    # query k=16: log1p(8),log1p(32) are NOT equidistant from log1p(16);
    # use the actual midpoint in log1p space for a true tie
    k_mid = int(round(np.expm1((np.log1p(8) + np.log1p(32)) / 2)))
    got_ab = t_ab.lookup(dk, "ingest", "scan", k=k_mid, d=8, n=128)
    got_ba = t_ba.lookup(dk, "ingest", "scan", k=k_mid, d=8, n=128)
    assert got_ab == got_ba


# -- table-driven decisions ----------------------------------------------

def test_table_flips_scan_vs_sparse_per_measurements():
    cfg = _cfg(16, 8, c=4)          # heuristic says sparse
    scan_fast = _table([_cell("ingest", "scan", 16, 8, 0, 128, 1e-4),
                        _cell("ingest", "sparse", 16, 8, 4, 128, 5e-4)])
    sparse_fast = _table([_cell("ingest", "scan", 16, 8, 0, 128, 5e-4),
                          _cell("ingest", "sparse", 16, 8, 4, 128, 1e-4)])
    d1 = costmodel.decide(cfg, chunk=128, cost_table=scan_fast)
    assert (d1.path, d1.reason) == ("scan", "table")
    assert d1.heuristic_path == "sparse"
    d2 = costmodel.decide(cfg, chunk=128, cost_table=sparse_fast)
    assert (d2.path, d2.reason) == ("sparse", "table")


def test_forced_path_ignores_table():
    cfg = _cfg(16, 8, c=4)
    scan_fast = _table([_cell("ingest", "scan", 16, 8, 0, 128, 1e-4),
                        _cell("ingest", "sparse", 16, 8, 4, 128, 5e-4)])
    d = costmodel.decide(cfg, requested="sparse", chunk=128,
                         cost_table=scan_fast)
    assert (d.path, d.reason) == ("sparse", "forced")


def test_no_matching_cells_falls_back_with_reason():
    cfg = _cfg(16, 8, c=4)
    t = costmodel.CostTable()       # empty: no cells for this device
    d = costmodel.decide(cfg, cost_table=t)
    assert d.path == "sparse"       # == heuristic
    assert d.reason == "no_table_entry"


def test_oversized_working_set_never_selects_vmem(tpu_kind):
    """The launch-correctness guard survives calibration: a table claiming
    vmem is fastest cannot launch a kernel whose working set exceeds the
    budget (or a non-TPU backend)."""
    cfg = _cfg(512, 64, update_mode="exact")    # kernel needs ≈ 41 MB
    cells = [_cell("ingest", "vmem", 512, 64, 0, 128, 1e-9),
             _cell("ingest", "scan", 512, 64, 0, 128, 1e-3)]
    lying = _table(cells)
    for c in cells:        # table covers the tpu key too (CostTable keys
        lying.add_cell(costmodel.device_key("tpu"), c)   # per device)
    # budget below the working set: vmem not a candidate on ANY backend
    for device in ("cpu", "tpu"):
        d = costmodel.decide(cfg, vmem_budget=1 << 20, device=device,
                             cost_table=lying)
        assert d.path != "vmem"
    # big budget but CPU backend: still guarded
    d = costmodel.decide(cfg, vmem_budget=1 << 30, device="cpu",
                         cost_table=lying)
    assert d.path != "vmem"
    # big budget AND tpu backend: now (and only now) the table may pick it
    d = costmodel.decide(cfg, vmem_budget=1 << 30, device="tpu",
                         cost_table=lying)
    assert (d.path, d.reason) == ("vmem", "table")


def test_decide_predict_requires_both_cells():
    cfg = _cfg(16, 8, c=4)
    dk = costmodel.device_key()
    half = _table([_cell("predict", "sparse", 16, 8, 4, 256, 1e-4)])
    d = costmodel.decide_predict(cfg, c=4, n=256, cost_table=half)
    assert (d.path, d.reason) == ("sparse", "no_table_entry")
    both = _table([_cell("predict", "sparse", 16, 8, 4, 256, 1e-4),
                   _cell("predict", "dense", 16, 8, 0, 256, 1e-5)])
    d = costmodel.decide_predict(cfg, c=4, n=256, cost_table=both)
    assert (d.path, d.reason) == ("dense", "table")
    assert costmodel.decide_predict(cfg, c=0, n=256,
                                    cost_table=both).path == "dense"


# -- routed predict: table-says-dense is bit-identical to dense ----------

def test_predict_routed_table_dense_matches_dense_bits():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 2.0, (160, 6)).astype(np.float32)
    cfg = _cfg(8, 6, c=4, vmin=1e9, spmin=0.0,
               sigma_ini=figmn.sigma_from_data(jnp.asarray(x), 1.0))
    state = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(x))
    xs_in, targets = x[:32, :-1], [cfg.dim - 1]
    dense_fast = _table([_cell("predict", "dense", 8, 6, 0, 32, 1e-5),
                         _cell("predict", "sparse", 8, 6, 4, 32, 1e-3)])
    routed = inference.predict_batch_routed(cfg, state, xs_in, targets,
                                            c=4, cost_table=dense_fast)
    dense = inference.predict_batch(cfg, state, xs_in, targets)
    assert (np.asarray(routed) == np.asarray(dense)).all()


# -- HLO traffic accounting under the predictions ------------------------

def _hlo_of(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


_SCATTER_TAIL = ("update_window_dims={1}, inserted_window_dims={0}, "
                 "scatter_dims_to_operand_dims={0}, index_vector_dim=1, "
                 "to_apply=%add_f32")

# 1 MiB operand, 8 rows (2 KiB) updated: the C≪K sparse-path write-back
_SCATTER_HLO = f"""HloModule m

ENTRY %main (p0: f32[4096,64], p1: s32[8,1], p2: f32[8,64]) -> f32[4096,64] {{
  %p0 = f32[4096,64]{{1,0}} parameter(0)
  %p1 = s32[8,1]{{1,0}} parameter(1)
  %p2 = f32[8,64]{{1,0}} parameter(2)
  ROOT %scatter.1 = f32[4096,64]{{1,0}} scatter(f32[4096,64]{{1,0}} %p0, s32[8,1]{{1,0}} %p1, f32[8,64]{{1,0}} %p2), {_SCATTER_TAIL}
}}
"""

_SCATTER_FUSION_HLO = f"""HloModule m

%fused_scatter (param_0: f32[4096,64], param_1: s32[8,1], param_2: f32[8,64]) -> f32[4096,64] {{
  %param_0 = f32[4096,64]{{1,0}} parameter(0)
  %param_1 = s32[8,1]{{1,0}} parameter(1)
  %param_2 = f32[8,64]{{1,0}} parameter(2)
  ROOT %scatter.2 = f32[4096,64]{{1,0}} scatter(f32[4096,64]{{1,0}} %param_0, s32[8,1]{{1,0}} %param_1, f32[8,64]{{1,0}} %param_2), {_SCATTER_TAIL}
}}

ENTRY %main (p0: f32[4096,64], p1: s32[8,1], p2: f32[8,64]) -> f32[4096,64] {{
  %p0 = f32[4096,64]{{1,0}} parameter(0)
  %p1 = s32[8,1]{{1,0}} parameter(1)
  %p2 = f32[8,64]{{1,0}} parameter(2)
  ROOT %fusion.1 = f32[4096,64]{{1,0}} fusion(f32[4096,64]{{1,0}} %p0, s32[8,1]{{1,0}} %p1, f32[8,64]{{1,0}} %p2), kind=kLoop, calls=%fused_scatter
}}
"""

OPERAND_B = 4096 * 64 * 4
UPDATE_B = 8 * 64 * 4
INDEX_B = 8 * 1 * 4


def test_scatter_traffic_is_update_rows_not_operand_copy():
    """In-place scatter on a large operand must charge read+write of the
    touched update windows plus the index reads, NOT an operand+result
    copy — the fix that makes sparse-path predictions scale with C
    instead of K."""
    traffic = hlo_analysis.analyze(_SCATTER_HLO)["traffic_bytes"]
    assert traffic == 2 * UPDATE_B + INDEX_B
    assert traffic < OPERAND_B


def test_fused_scatter_destination_not_charged_full_read():
    """A fusion parameter consumed only as a scatter destination is
    updated in place: its read side is the update bytes, never the full
    (K, D, D) pool."""
    traffic = hlo_analysis.analyze(_SCATTER_FUSION_HLO)["traffic_bytes"]
    # fusion-boundary result + in-place destination updates + the small
    # index and update operands read in full
    assert traffic == OPERAND_B + 2 * UPDATE_B + INDEX_B + UPDATE_B
    # strictly below the pre-fix accounting (destination read in full)
    assert traffic < 2 * OPERAND_B


def test_gather_traffic_scales_with_result_not_operand():
    big = jnp.ones((4096, 64), jnp.float32)
    idx = jnp.arange(8, dtype=jnp.int32)

    def f(big, idx):
        return jnp.take(big, idx, axis=0) * 2.0

    traffic = hlo_analysis.analyze(_hlo_of(f, big, idx))["traffic_bytes"]
    operand_bytes = 4096 * 64 * 4
    assert 0 < traffic < operand_bytes
