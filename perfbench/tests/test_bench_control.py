"""The control, the plain reference in bfloat16 in the program's place,
fails the cells' committed limits; here at a size a test run can hold
(PERF.md gives its readings on the chip at the cells' own sizes)."""
import pytest

from perfbench import catalog, control

SMALL = {"features": 502, "classes": 10, "dim": 512, "kmax": 16,
         "chunk": 16}


def _config(name):
    cfg = catalog.load_json("configs", name)
    cfg.update(SMALL)
    return cfg


def _fails(gaps, limits):
    return sorted(k for k, v in gaps.items() if not v <= limits[k])


@pytest.mark.parametrize("cell", ["mnist-k1024.ingest",
                                  "cifar10-k64.ingest"])
def test_the_bfloat16_reference_fails_an_ingest_cell(cell):
    name = cell.split(".")[0]
    gaps = control.ingest_gaps(_config(name), 2 ** 31 + 5, 4)
    assert _fails(gaps, catalog.load_json("limits", cell))


def test_the_bfloat16_reference_fails_the_read_cell():
    traffic = dict(catalog.load_json("traffic", "read"),
                   checked_per_kind=32)
    gaps = control.read_gaps(_config("mnist-k1024"), traffic, 2 ** 31 + 6)
    assert _fails(gaps, catalog.load_json("limits", "mnist-k1024.read")) \
        == ["label_gap", "score_gap"]
