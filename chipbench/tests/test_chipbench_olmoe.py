"""The olmoe-1b-7b cell at the reduced preset on the CPU: a whole run comes
out correct and its float8 control and planted faults do not; its work
counts against hand arithmetic; its per-layer readers on hand-made
scope readings; and the scopes its decode programs carry."""
import json
import re
import time
from pathlib import Path

import pytest

from chipbench import bench, decode_time, spans
from chipbench.control import FAULTS
from chipbench.tests.cells import PEAKS
from chipbench.tests.olmoe_cells import NAME, reduced_cell

SEED = 2**31 + 1616
CFG = json.loads((Path(bench.HERE) / "configs/olmoe-1b-7b.json").read_text())
O = bench.load_module(bench.HERE / "models/olmoe.py", "model")


def _run(store, tmp_path, **kw):
    return bench.run(reduced_cell(), SEED, 2.0, False, time.perf_counter(),
                     store_dir=store, trace_dir=tmp_path / "trace",
                     peaks=PEAKS, **kw)


def test_a_run_is_correct_and_its_control_is_not(tmp_path):
    out = _run(tmp_path / "store", tmp_path, control=True)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"setup_s", "ttft_p50_ms"}
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert out["control"]["correct"] is False
    ctl = out["control"]["checks"]["widest_logit_gap"]
    assert ctl["value"] > 10 * gap["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch,
                                                  tmp_path):
    FAULTS[fault](monkeypatch.setattr)
    out = _run(tmp_path / "store", tmp_path)
    assert out["correct"] is False
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_cell_is_declared_with_its_metrics():
    cell = bench.load_cell(NAME)
    assert cell.chips == 4 and cell.config["engine"]["shard"] == {
        "n_devices": 4}
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "ttft_p50_ms"}
    assert {m["name"] for m in cell.per_layer} == {
        "moe_share.olmoe_chat", "moe_roofline.olmoe_chat",
        "collective_share.olmoe_chat", "mfu.olmoe_chat"}
    from repro.models import registry
    O.check_program_config(cell.config, registry.get_config("olmoe-1b-7b"))


# -- work counts ------------------------------------------------------------------

# 16 layers of q, k, v, o (2048 x 2048 each), the router (2048 x 64) and
# 8 of 64 experts of 3 x 2048 x 1024
ATTN = 4 * 2048 * 2048
EXPERT = 3 * 2048 * 1024
LINEAR = 16 * (ATTN + 2048 * 64 + 8 * EXPERT)
HEAD = 50304 * 2048
NORMS = 16 * (2 * 2048 + 2 * 2048) + 2048


def test_parameter_and_cache_sizes():
    assert O.linear_params(CFG) == LINEAR
    # 16 layers x (k, v) x 16 heads x 128 x 2 bytes, a quarter a chip
    assert O.kv_bytes_per_token(CFG) == 131_072 == 4 * 32_768
    weights = 16 * (ATTN + 2048 * 64 + 64 * EXPERT) + 2 * HEAD + NORMS
    assert O.weight_bytes(CFG) == 2 * (weights - HEAD)
    assert 2 * weights == pytest.approx(13.84e9, rel=2e-3)


def test_prefill_and_decode_work():
    flops, nbytes = O.prefill_work(CFG, 384)
    assert flops == 2 * LINEAR * 384 + 2 * 16 * 16 * 128 * 384 * 385 \
        + 2 * HEAD
    assert nbytes == O.weight_bytes(CFG) + 384 * 131_072
    flops, nbytes = O.decode_work(CFG, [100, 900], steps=3)
    assert flops == 2 * (2 * LINEAR + 2 * HEAD) + 4 * 16 * 16 * 128 * 1000
    assert nbytes == 3 * O.weight_bytes(CFG) + 131_072 * 1000


def test_moe_decode_work_is_one_chips_share():
    flops, nbytes = O.moe_decode_work(CFG, tokens=64, steps=8)
    # each token's 8 experts over 4 chips; 16 held experts and the router
    # per layer and step
    assert flops == 64 * 16 * 8 * 2 * EXPERT / 4
    assert nbytes == 8 * 16 * (16 * EXPERT + 2048 * 64) * 2
    assert nbytes == pytest.approx(8 * 3.22e9 / 1, rel=2e-2)


# -- the readers ------------------------------------------------------------------

class Win:
    """A traced window as the readers see it: the scope readings that
    ``spans.read_run`` keeps on it, and the traced steps."""

    def __init__(self, scoped, steps, step_modules):
        self.engine_spans = {"decode_modules": ["jit_serve_step",
                                                "jit_decode_horizon_step"],
                             "scoped": scoped}
        self.steps = steps
        self.trace = {"step_modules": step_modules}


def _win():
    scoped = {
        "jit_decode_horizon_step": {
            ("mlp", "fusion.3"): 0.030, ("mlp", "all-reduce.2"): 0.004,
            ("paged_kv/attend", "paged_decode_attention.1"): 0.006,
            (None, "all-reduce-start.7"): 0.001,
            (None, "all-reduce-done.7"): 0.002, (None, "copy.9"): 0.050},
        "jit_serve_step": {("mlp", "fusion.3"): 0.005, (None, "copy.1"): 0.002},
        "jit_prefill_slot": {("mlp", "fusion.1"): 1.0},
    }
    steps = [bench.StepRec(0, 0.0, decode_contexts=[500] * 64,
                           decode_steps=8),
             bench.StepRec(1, 0.0, prefills=[900], decode_contexts=[600] * 10,
                           decode_steps=1),
             bench.StepRec(2, 0.0)]
    step_modules = {0: {"jit_decode_horizon_step": [0.1] * 4},
                    1: {"jit_prefill_slot": [0.03] * 4,
                        "jit_serve_step": [0.01] * 4}}
    return Win(scoped, steps, step_modules)


def _metric(name, win):
    return bench.load_metric(name).read(win, bench.load_cell(NAME), PEAKS)


def test_moe_share_reads_the_mlp_scope_of_decode():
    total = 0.030 + 0.004 + 0.006 + 0.001 + 0.002 + 0.050 + 0.005 + 0.002
    assert _metric("moe_share.olmoe_chat", _win()) == pytest.approx(
        100 * (0.030 + 0.004 + 0.005) / total)


def test_collective_share_finds_collectives_by_name():
    total = 0.030 + 0.004 + 0.006 + 0.001 + 0.002 + 0.050 + 0.005 + 0.002
    assert _metric("collective_share.olmoe_chat", _win()) == pytest.approx(
        100 * (0.004 + 0.001 + 0.002) / total)
    for op in ("all-reduce.3", "all-gather-start.1", "all-to-all",
               "reduce-scatter.2", "collective-permute-done.4"):
        assert decode_time.is_collective(op), op
    for op in ("fusion.3", "copy.1", "all-reducer.1", "reduce.4"):
        assert not decode_time.is_collective(op), op


def test_moe_roofline_over_the_traced_decode_dispatches():
    f0, b0 = O.moe_decode_work(CFG, 64, 8)
    f1, b1 = O.moe_decode_work(CFG, 10, 1)
    least = sum(max(f / PEAKS["flops_per_s"], b / PEAKS["bytes_per_s"])
                for f, b in ((f0, b0), (f1, b1)))
    assert _metric("moe_roofline.olmoe_chat", _win()) == pytest.approx(
        100 * least / (0.030 + 0.004 + 0.005))


def test_the_readers_give_nothing_without_scopes():
    win = _win()
    win.engine_spans["scoped"] = {}
    for name in ("moe_share.olmoe_chat", "moe_roofline.olmoe_chat",
                 "collective_share.olmoe_chat"):
        assert _metric(name, win) is None, name


def test_the_moe_layer_is_scoped_under_mlp(tmp_path):
    eng, _ = bench.build_engine(reduced_cell(), SEED, tmp_path)
    for prog in ("decode", "decode_horizon"):
        text = eng.programs[prog].program.compiled.as_text()
        op_names = set(re.findall(r'op_name="([^"]*)"', text))
        for part in ("moe/route", "moe/experts", "moe/combine"):
            under = [o for o in op_names if part in o]
            assert under, (prog, part)
            assert {spans.scope_of(o) for o in under} == {"mlp"}, (prog, part)
