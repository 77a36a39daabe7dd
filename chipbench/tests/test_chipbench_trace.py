"""The reduction from trace events to busy time, per-step program time
and idle gaps, on a small hand-made trace."""
import pytest

from chipbench import trace

MS = 1e6   # ns


def _trace():
    """Two steps on the host; on the device a prefill in step 0 and a
    decode in step 1, with an idle gap during the stamp between them."""
    host = [("bench.step.0", 0 * MS, 10 * MS),
            ("bench.stamp", 10 * MS, 2 * MS),
            ("bench.step.1", 12 * MS, 8 * MS)]
    modules = [("jit_prefill_slot(3)", 1 * MS, 8 * MS),
               ("jit_serve_step(4)", 13 * MS, 5 * MS)]
    ops = [("fusion.1", 1 * MS, 4 * MS), ("fusion.2", 5 * MS, 4 * MS),
           ("%while.3 = (s32[]{:T(128)})", 13 * MS, 5.5 * MS),
           ("fusion.1", 13.5 * MS, 5 * MS)]
    return {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops}}, host


def test_busy_idle_and_window():
    red = trace.reduce(*_trace())
    assert red["window_s"] == pytest.approx(20e-3)
    assert red["busy_s"] == pytest.approx(13.5e-3)


def test_program_time_per_step():
    red = trace.reduce(*_trace())
    assert red["step_modules"][0] == {"jit_prefill_slot": [8e-3]}
    assert red["step_modules"][1] == {"jit_serve_step": [5e-3]}


def test_idle_gaps_go_to_the_host_span_that_holds_them():
    red = trace.reduce(*_trace())
    idle = dict(red["breakdown"]["idle_gaps"])
    # 0..1 and 18.5..20 inside steps, 9..13 mostly in the stamp (its
    # midpoint 11 lies there)
    assert idle["bench.stamp"] == pytest.approx(4e-3)
    assert idle["bench.step"] == pytest.approx(2.5e-3)
    # the loop holds fusion.1 and is counted through it
    ops = dict(red["breakdown"]["device_ops"])
    assert ops == {"fusion.1": pytest.approx(9e-3),
                   "fusion.2": pytest.approx(4e-3)}


def test_union_merges_overlaps():
    assert trace.union([(3, 5), (0, 1), (4, 8), (8, 9)]) == [(0, 1), (3, 9)]


def test_idle_share_and_shares_of_a_window():
    class Win:
        pass
    win = Win()
    win.trace = trace.reduce(*_trace())
    assert trace.idle_share(win) == pytest.approx(32.5)
    win.trace = dict(win.trace, busy_s=0.0)
    assert trace.idle_share(win) is None


def test_a_recorded_cpu_trace_reads(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(2):
        with jax.profiler.TraceAnnotation(f"bench.step.{i}"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    import glob
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    devices, host = trace.load_events(path[0])
    assert sorted(n for n, _, _ in host) == ["bench.step.0", "bench.step.1"]
    red = trace.reduce(devices, host)
    assert red["window_s"] > 0
