"""From a profiler trace to device busy time, per-step program time and
idle gaps, and from those to the per-layer metrics.

The reduction works on plain events ``(name, start_ns, duration_ns)``:
:func:`load_events` reads them from the ``.xplane.pb`` that
``jax.profiler`` writes, and :func:`reduce` does the arithmetic, so a test
can feed it a small hand-made trace.

* Device events are those of the planes named ``/device:...``: the line
  ``XLA Ops`` gives busy time and the top operations (leaves only, named
  by their HLO text up to the first layout), the line ``XLA Modules``
  gives each program execution.
* Host events are the harness's own ``jax.profiler.TraceAnnotation``
  spans, named ``bench.*``; ``bench.step.<i>`` is step ``i`` of the window.
* A program execution belongs to the step whose host span holds its
  start: each step blocks on its dispatches' results.
* An idle gap (no operation on the device) is charged to the host span
  that holds its midpoint, or to ``outside`` when none does.
"""
from __future__ import annotations

import bisect
import glob
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
Event = Tuple[str, float, float]          # name, start_ns, duration_ns
TOP = 10


def programs() -> Dict[str, str]:
    """Serving program -> the name of its compiled module in a trace."""
    return json.loads((HERE / "programs.json").read_text())


def load_events(path: str) -> Tuple[Dict[str, Dict[str, List[Event]]],
                                      List[Event]]:
    """({device plane: {line: events}}, host ``bench.*`` events)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            devices[plane.name] = {
                line.name: [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return devices, host


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(devices: Dict[str, Dict[str, List[Event]]], host: List[Event]
           ) -> dict:
    """Busy and window seconds (averaged over the devices), each step's
    device seconds per module, and the breakdown of the traced window."""
    if not host:
        raise ValueError("the trace holds no bench.* host span")
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    steps = sorted((int(n.rsplit(".", 1)[1]), s, s + d)
                   for n, s, d in host if n.startswith("bench.step."))
    starts = [s for _, s, _ in steps]
    spans = sorted((s, s + d, n.rsplit(".", 1)[0] if n.startswith(
        "bench.step.") else n) for n, s, d in host)
    span_starts = [a for a, _, _ in spans]

    busy_ns, op_ns, gaps = [], defaultdict(float), defaultdict(float)
    step_modules: Dict[int, Dict[str, List[float]]] = defaultdict(
        lambda: defaultdict(list))
    for lines in devices.values():
        ops = lines.get("XLA Ops", [])
        merged = _clip(union([(s, s + d) for _, s, d in ops]), lo, hi)
        busy_ns.append(sum(b - a for a, b in merged))
        ops = sorted(ops, key=lambda e: (e[1], -e[2]))
        for i, (name, s, d) in enumerate(ops):
            # a leaf: no later operation starts inside it (an operation that
            # holds others, such as a loop, is counted through them)
            leaf = i + 1 == len(ops) or ops[i + 1][1] >= s + d
            if leaf and lo <= s < hi:
                op_ns[name.split("{")[0]] += d
        for (_, a), (b, _) in zip([(lo, lo)] + merged, merged + [(hi, hi)]):
            if b > a:
                gaps[_host_at(spans, span_starts, (a + b) / 2)] += b - a
        for name, s, d in lines.get("XLA Modules", []):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < steps[i][2]:
                step_modules[steps[i][0]][name.split("(")[0]].append(d / 1e9)
    n = max(len(devices), 1)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "step_modules": {i: dict(m) for i, m in step_modules.items()},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in top],
            "idle_gaps": [[k, v / n / 1e9] for k, v in idle]},
    }


def _host_at(spans, starts, t) -> str:
    """The ``bench.*`` span that holds time ``t`` (the harness's spans do
    not overlap)."""
    i = bisect.bisect_right(starts, t) - 1
    return spans[i][2] if i >= 0 and spans[i][1] >= t else "outside"


def reduce_dir(trace_dir: Path) -> dict:
    paths = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(*load_events(sorted(paths)[-1]))


# -- readings shared by the per-layer metrics ---------------------------------------

def traced_steps(win, modules: List[str]):
    """(step record, {module: [seconds]}) of every traced step that ran one
    of ``modules``."""
    sm = win.trace["step_modules"]
    for st in win.steps:
        got = sm.get(st.index, {})
        if any(m in got for m in modules):
            yield st, got


def prefill_share(win, cell, peaks) -> Optional[float]:
    """Least time for the real prompts' work over ``prefill_slot`` device
    time, in percent, over the traced steps."""
    mod = programs()["prefill_slot"]
    least = spent = 0.0
    for st, got in traced_steps(win, [mod]):
        if len(got[mod]) != len(st.prefills):
            continue
        for plen in st.prefills:
            f, b = cell.model.prefill_work(cell.config, plen)
            least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        spent += sum(got[mod])
    return 100.0 * least / spent if spent > 0 else None


def decode_share(win, cell, peaks) -> Optional[float]:
    """Least time for the weights and the live keys and values each decode
    dispatch needed, over ``decode`` + ``decode_horizon`` device time."""
    names = programs()
    mods = [names["decode"], names["decode_horizon"]]
    least = spent = 0.0
    for st, got in traced_steps(win, mods):
        ran = [d for m in mods for d in got.get(m, [])]
        if len(ran) != 1 or not st.decode_contexts:
            continue
        f, b = cell.model.decode_work(cell.config, st.decode_contexts,
                                      st.decode_steps)
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        spent += ran[0]
    return 100.0 * least / spent if spent > 0 else None


def model_flops_share(win, cell, peaks) -> Optional[float]:
    """Model FLOPs of all prefill and decode work in the traced steps over
    the serving programs' device time at the chip's peak, in percent."""
    mods = list(programs().values())
    flops = spent = 0.0
    for st, got in traced_steps(win, mods):
        flops += sum(cell.model.prefill_work(cell.config, p)[0]
                     for p in st.prefills)
        if st.decode_contexts:
            flops += cell.model.decode_work(cell.config, st.decode_contexts,
                                            st.decode_steps)[0]
        spent += sum(d for m in mods for d in got.get(m, []))
    return 100.0 * flops / (spent * peaks["flops_per_s"]) if spent else None


def idle_share(win) -> Optional[float]:
    """None when the trace holds no device operation at all."""
    w, busy = win.trace["window_s"], win.trace["busy_s"]
    return 100.0 * (1.0 - busy / w) if w > 0 and busy > 0 else None
