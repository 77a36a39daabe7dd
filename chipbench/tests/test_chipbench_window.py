"""The whole run of a cell, at the reduced preset on the CPU: the window
loop, the metrics, and the check that decides ``correct`` — which a
planted fault in the timed path, and the float8 control, must fail."""
import pytest

from chipbench import bench
from chipbench.control import FAULTS
from chipbench.tests.cells import PEAKS, reduced_cell

SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    return tmp_path_factory.mktemp("store")


def _run(cell, store, tmp_path, trace=False, **kw):
    import time
    return bench.run(cell, SEED, 2.0, trace, time.perf_counter(),
                     store_dir=store, trace_dir=tmp_path / "trace",
                     peaks=PEAKS, **kw)


@pytest.mark.parametrize("name", ["chat", "offline"])
def test_a_run_is_correct_and_reports_its_metrics(name, store, tmp_path):
    cell = reduced_cell(name)
    out = _run(cell, store, tmp_path, control=True)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["compiles_in_window"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] <= gap["limit"]
    # the control, the reference in float8 put in the program's place,
    # comes out not correct through the same comparison
    assert out["control"]["correct"] is False
    ctl = out["control"]["checks"]["widest_logit_gap"]
    assert ctl["limit"] == gap["limit"] and ctl["value"] > 10 * gap["limit"]


def test_a_traced_run_reports_per_layer_metrics(store, tmp_path):
    cell = reduced_cell("chat")
    out = _run(cell, store, tmp_path, trace=True)
    assert out["correct"] is True
    assert "queue_wait_p50_ms.chat" in out["metrics"]
    assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch,
                                                  tmp_path):
    FAULTS[fault](monkeypatch.setattr)
    out = _run(reduced_cell("chat"), tmp_path / "store", tmp_path)
    assert out["correct"] is False
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]
