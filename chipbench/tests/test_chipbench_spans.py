"""The engine's spans and the programs' scopes: the reduction on hand-made
events, the spans of a short window on the CPU, and the scopes in the
compiled decode programs."""
import dataclasses
import glob
import re

import pytest

from chipbench import bench, spans, trace
from chipbench.tests.cells import PEAKS, reduced_cell

MS = 1e6   # ns
SEED = 2**31 + 1313


def _trace():
    """Two steps.  Step 0 admits (dispatch, wait, place) and decodes, step 1
    decodes; the device runs one prefill and two decodes, and idles while
    the host is in the pager, waits on a result, stamps, and at the ends."""
    host = [
        ("bench.step.0", 0 * MS, 10 * MS, {}),
        ("engine.step", 0 * MS, 10 * MS, {}),
        ("engine.admit", 0 * MS, 5 * MS, {}),
        ("engine.pager", 0 * MS, 1 * MS, {"rid": 4}),
        ("engine.dispatch.prefill_slot", 1 * MS, 0.5 * MS,
         {"rid": 4, "prompt_len": 9}),
        ("engine.wait.prefill_slot", 1.5 * MS, 3 * MS,
         {"rid": 4, "prompt_len": 9}),
        ("engine.place", 4.5 * MS, 0.5 * MS, {"rid": 4}),
        ("engine.dispatch.decode", 5 * MS, 0.5 * MS, {"active": 1}),
        ("engine.wait.decode", 5.5 * MS, 4 * MS, {"active": 1}),
        ("engine.emit", 9.5 * MS, 0.5 * MS, {}),
        ("bench.stamp", 10 * MS, 2 * MS, {}),
        ("bench.step.1", 12 * MS, 8 * MS, {}),
        ("engine.step", 12 * MS, 8 * MS, {}),
        ("engine.dispatch.decode", 12 * MS, 1 * MS, {"active": 1}),
        ("engine.wait.decode", 13 * MS, 6 * MS, {"active": 1}),
        ("engine.telemetry", 19 * MS, 1 * MS, {}),
    ]
    modules = [("jit_prefill_slot(3)", 1.2 * MS, 2.8 * MS, {}),
               ("jit_serve_step(4)", 5.6 * MS, 3 * MS, {}),
               ("jit_serve_step(4)", 12.5 * MS, 5 * MS, {})]
    ops = [("fusion.1", 1.2 * MS, 2.8 * MS, {}),
           ("%fusion.7 = bf16[32]{0}", 5.6 * MS, 1 * MS, {}),
           ("copy.3", 6.6 * MS, 2 * MS, {}),
           ("%while.3 = (s32[])", 12.5 * MS, 5 * MS, {}),
           ("fusion.7", 12.5 * MS, 2 * MS, {}),
           ("fusion.8", 14.5 * MS, 3 * MS,
            {"tf_op": "jit(serve_step)/while/body/mlp/dot_general"})]
    return {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops}}, host


def _plain(devices):
    """The device events as ``trace.reduce`` takes them."""
    return {p: {ln: [(n, s, d) for n, s, d, _ in evs]
                for ln, evs in lines.items()}
            for p, lines in devices.items()}


def test_engine_spans_leave_the_bench_readings_alone():
    devices, host = _trace()
    bench_spans = [e for e in host if e[0].startswith("bench.")]
    alone = trace.reduce(_plain(devices), spans.bench_only(bench_spans))
    nested = trace.reduce(_plain(devices), spans.bench_only(host))
    assert nested == alone
    # even handed every span, the window, busy time and the steps' modules
    # come from the bench.* spans alone
    every = trace.reduce(_plain(devices), [e[:3] for e in host])
    for key in ("busy_s", "window_s", "step_modules"):
        assert every[key] == alone[key]


def test_idle_goes_to_the_innermost_span():
    devices, host = _trace()
    at = spans.innermost(host)
    assert at(0.5 * MS) == "engine.pager"
    assert at(2 * MS) == "engine.wait.prefill_slot"
    assert at(4.7 * MS) == "engine.place"
    assert at(9.7 * MS) == "engine.emit"
    assert at(11 * MS) == "bench.stamp"
    assert at(19.5 * MS) == "engine.telemetry"
    assert at(21 * MS) is None
    by_span = spans.idle_split(devices, host)["by_span"]
    assert by_span == {"engine.pager": pytest.approx(1.2e-3),
                       "engine.place": pytest.approx(1.6e-3),
                       "bench.stamp": pytest.approx(3.9e-3),
                       "engine.wait.decode": pytest.approx(2.5e-3)}


def test_the_idle_parts_make_up_the_idle_share():
    devices, host = _trace()
    split = spans.idle_split(devices, host)
    red = trace.reduce(_plain(devices), spans.bench_only(host))

    class Win:
        trace = red
    assert split["window_s"] == red["window_s"]
    assert split["busy_s"] == pytest.approx(red["busy_s"])
    parts = {k: 100 * v / split["window_s"]
             for k, v in split["idle_s"].items()}
    assert sum(parts.values()) == pytest.approx(trace.idle_share(Win()))
    assert parts["wait"] == pytest.approx(100 * 2.5e-3 / 20e-3)
    assert parts["engine"] == pytest.approx(100 * 2.8e-3 / 20e-3)
    assert parts["harness"] == pytest.approx(100 * 3.9e-3 / 20e-3)


def test_a_plane_that_runs_nothing_is_not_a_device():
    """A TPU profile also holds ``/device:CUSTOM:Megascale Trace``, with no
    line: it is left out, not read as a device idle all window."""
    devices, host = _trace()
    names = {v: k for k, v in trace.programs().items()}
    op_names = {"jit_serve_step": {
        "fusion.7": "jit(serve_step)/while/body/paged_kv/gather/gather"}}
    both = dict(devices, **{"/device:CUSTOM:Megascale Trace": {}})
    assert spans.idle_split(both, host) == spans.idle_split(devices, host)
    assert spans.scope_seconds(both, host, op_names) == \
        spans.scope_seconds(devices, host, op_names)
    assert spans.clock_check(both, host, names) == \
        spans.clock_check(devices, host, names)
    # trace.reduce averages over every /device: plane, so its idle share
    # reads 50 points plus half the device's own
    idle = spans.idle_split(devices, host)
    own = 100 * (1 - idle["busy_s"] / idle["window_s"])

    class Win:
        trace = trace.reduce(_plain(both), spans.bench_only(host))
    assert trace.idle_share(Win()) == pytest.approx(50 + own / 2)


def test_the_shares_are_left_out_without_engine_spans():
    devices, host = _trace()
    bench_spans = [e for e in host if e[0].startswith("bench.")]

    class Win:
        pass
    win = Win()
    win.engine_spans = {"idle": spans.idle_split(devices, bench_spans),
                        "engine_spans": False}
    assert spans.idle_share_of(win, "wait") is None
    win.engine_spans["engine_spans"] = True
    assert spans.idle_share_of(win, "wait") == 0.0


def test_self_time_is_the_span_less_its_children():
    _, host = _trace()
    got = spans.self_times(host)
    assert got["engine.admit"] == [pytest.approx(0.0)]
    assert sorted(got["engine.step"]) == pytest.approx([0.0, 0.0])
    assert sorted(got["engine.wait.decode"]) == pytest.approx([4e-3, 6e-3])
    assert "bench.stamp" not in got


def test_the_clock_check_counts_a_module_outside_its_span():
    devices, host = _trace()
    names = {v: k for k, v in trace.programs().items()}
    assert spans.clock_check(devices, host, names) == (3, 0, 0.0)
    modules = devices["/device:TPU:0"]["XLA Modules"]
    # the second decode starts 0.2 ms before its dispatch span
    modules[2] = ("jit_serve_step(4)", 11.8 * MS, 5 * MS, {})
    checked, outside, worst = spans.clock_check(devices, host, names)
    assert (checked, outside) == (3, 1)
    assert worst == pytest.approx(0.2e-3)
    bench_spans = [e for e in host if e[0].startswith("bench.")]
    assert spans.clock_check(devices, bench_spans, names) is None


def test_paged_kv_share_over_scoped_ops():
    devices, host = _trace()
    op_names = {"jit_serve_step": {
        "fusion.7": "jit(serve_step)/while/body/paged_kv/gather/gather",
        "copy.3": "jit(serve_step)/while/body/dynamic_update_slice"}}
    scoped = spans.scope_seconds(devices, host, op_names)
    decode = scoped["jit_serve_step"]
    # the while loop holds fusion.7 and fusion.8 and is counted through them
    assert spans.by_scope(decode) == {
        "paged_kv/gather": pytest.approx(3e-3), None: pytest.approx(2e-3),
        "mlp": pytest.approx(3e-3)}
    assert spans.paged_kv_share(scoped, ["jit_serve_step"]) == \
        pytest.approx(100 * 3 / 8)
    # a program built without scopes gives no reading
    assert spans.paged_kv_share(
        spans.scope_seconds(devices, host, {}), ["jit_prefill_slot"]) is None


def test_scope_of_a_path():
    assert spans.scope_of(
        "jit(f)/while/body/closed_call/paged_kv/attend/div") == \
        "paged_kv/attend"
    assert spans.scope_of("jit(f)/logits/sample/argmax") == "sample"
    assert spans.scope_of("jit(f)/paged_kv/dot") is None
    assert spans.scope_of(None) is None


def test_op_names_from_hlo_text():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation.2 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(f)/neg"}
}

ENTRY %main.3 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/mlp/neg"}
  ROOT %copy.1 = f32[4]{0} copy(%fusion.2)
}
"""
    names = spans.hlo_op_names(text)
    assert names == {"neg.1": "jit(f)/neg", "fusion.2": "jit(f)/mlp/neg"}


def test_span_arguments_are_split_from_the_name():
    assert spans._split_args("engine.pager#rid=3#") == (
        "engine.pager", {"rid": "3"})
    assert spans._split_args("engine.step") == ("engine.step", {})


# -- the engine on the CPU ----------------------------------------------------------

@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """A short chat window of the reduced cell, traced whole."""
    cell = reduced_cell("chat")
    eng, recorder = bench.build_engine(
        cell, SEED, tmp_path_factory.mktemp("store"))
    bench.warm_up(eng, eng.cfg.vocab_size)
    trace_dir = tmp_path_factory.mktemp("trace")
    win = bench.run_window(eng, recorder, cell.mix, SEED, 2.0,
                           eng.cfg.vocab_size, trace_dir)
    path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    _, host = spans.load(path[0])
    return eng, win, host, trace_dir


def test_the_engine_spans_nest_in_the_harness_steps(window):
    _, win, host, _ = window
    names = {n for n, _, _, _ in host}
    for want in ("engine.step", "engine.admit", "engine.pager",
                 "engine.dispatch.prefill_slot", "engine.wait.prefill_slot",
                 "engine.place", "engine.emit", "engine.telemetry"):
        assert want in names, want
    assert names & {"engine.dispatch.decode", "engine.dispatch.decode_horizon"}
    steps = [(s, s + d) for n, s, d, _ in host if n.startswith("bench.step.")]
    for n, s, d, args in host:
        if n.startswith("engine."):
            assert any(a <= s and s + d <= b for a, b in steps), n
        if n in ("engine.pager", "engine.place") or n.endswith(
                ".prefill_slot"):
            assert "rid" in args, n


def test_each_wait_follows_its_dispatch(window):
    _, _, host, _ = window
    for prog in ("prefill_slot", "decode", "decode_horizon"):
        evs = sorted((s, n.split(".")[1], s + d) for n, s, d, _ in host
                     if n in (f"engine.dispatch.{prog}",
                              f"engine.wait.{prog}"))
        kinds = [k for _, k, _ in evs]
        assert kinds == ["dispatch", "wait"] * (len(evs) // 2), prog
        for (_, _, end), (start, _, _) in zip(evs[::2], evs[1::2]):
            assert start >= end


def test_admitted_prompt_lengths_match_the_harness(window):
    _, win, host, _ = window
    steps = {int(n.rsplit(".", 1)[1]): (s, s + d)
             for n, s, d, _ in host if n.startswith("bench.step.")}
    got = {i: [] for i in steps}
    for n, s, d, args in host:
        if n == "engine.dispatch.prefill_slot":
            i = next(i for i, (a, b) in steps.items() if a <= s < b)
            got[i].append(int(args["prompt_len"]))
    assert sum(map(len, got.values())) > 0
    for st in win.steps:
        if st.index in got:
            assert sorted(got[st.index]) == sorted(st.prefills), st.index


def test_the_decode_programs_carry_the_scopes(window):
    eng, _, _, _ = window
    for prog in ("decode", "decode_horizon"):
        text = eng.programs[prog].program.compiled.as_text()
        op_names = set(re.findall(r'op_name="([^"]*)"', text))
        found = {spans.scope_of(o) for o in op_names}
        assert {"paged_kv/write", "paged_kv/gather", "paged_kv/attend",
                "mlp", "logits", "sample"} <= found, prog
    # the same maps from the executables loaded in the process
    op_names = spans.loaded_op_names(["jit_serve_step",
                                      "jit_decode_horizon_step"])
    for module in ("jit_serve_step", "jit_decode_horizon_step"):
        assert any(spans.scope_of(o) == "paged_kv/gather"
                   for o in op_names[module].values()), module


def test_a_reader_finds_the_profile_of_its_own_window(window, tmp_path,
                                                       monkeypatch):
    _, win, _, trace_dir = window
    mine = dataclasses.replace(win, trace=trace.reduce_dir(trace_dir))
    monkeypatch.setattr(bench, "TRACE_DIR", tmp_path)
    assert spans.read_run(mine) is None          # no profile there
    monkeypatch.setattr(bench, "TRACE_DIR", trace_dir)
    other = dataclasses.replace(mine, trace=dict(
        mine.trace, window_s=mine.trace["window_s"] + 1e-9))
    assert spans.read_run(other) is None         # another run's profile
    got = spans.read_run(mine)
    assert got["engine_spans"] is True
    assert got["hlo_modules"] == ["jit_decode_horizon_step",
                                  "jit_serve_step"]
    assert got["self_s"]["engine.step"]
    assert spans.read_run(mine) is got           # read once per window


def test_a_traced_run_reads_and_reports_the_new_metrics(tmp_path, capfd,
                                                        monkeypatch):
    monkeypatch.setattr(bench, "TRACE_DIR", tmp_path / "trace")
    cell = reduced_cell("chat")
    declared = {m["name"] for m in cell.per_layer}
    assert {"idle_wait_share.chat", "idle_host_share.chat",
            "paged_kv_share.chat"} <= declared
    import time
    out = bench.run(cell, SEED, 2.0, True, time.perf_counter(),
                    store_dir=tmp_path / "store",
                    trace_dir=tmp_path / "trace", peaks=PEAKS)
    assert out["correct"] is True
    assert set(out["metrics"]) <= declared
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    err = capfd.readouterr().err
    assert "spans: median self time per step: " in err
    assert ("spans: scopes from the HLO text of the loaded "
            "jit_decode_horizon_step, jit_serve_step") in err
    assert "spans: clock check: " in err
    assert "spans: engine step median: " in err
