"""Engine spans and program scopes in a traced run: where the device's idle
time goes, and how much of decode's device time is paged-KV work.

The engine (``repro.launch.serve``) wraps its host work in
``jax.profiler.TraceAnnotation`` spans named ``engine.*``: ``engine.step``,
``engine.admit``, ``engine.pager``, ``engine.dispatch.<program>`` (the
enqueue), ``engine.wait.<program>`` (blocking on the result),
``engine.place``, ``engine.emit`` and ``engine.telemetry``.  The programs
name their work with ``jax.named_scope`` (``paged_kv/write``,
``paged_kv/gather``, ``paged_kv/attend``, ``mlp``, ``logits``, ``sample``),
which lands in the ``op_name`` metadata of each operation of the compiled
module.  This module reads both from the profile that ``chipbench/trace.py``
reduces:

* each idle gap of the device goes to the innermost host span holding its
  midpoint: a wait on a result (``engine.wait.*``), other engine work (any
  other ``engine.*`` span), or the rest (a ``bench.*`` span or none);
* each leaf operation of a decode module goes to its scope, found by its
  instruction name in that module's compiled HLO text, or else in the
  event's own ``tf_op`` / ``long_name`` stat.

The window and the steps are bounded by the ``bench.*`` spans, as in
``trace.py``; ``trace.py`` reads only those, so its readings are the same
whether the engine's spans are in the trace or not.  Only device planes
with an ``XLA Ops`` line count: a TPU profile also holds planes that run
nothing (``/device:CUSTOM:Megascale Trace``), which would read as a device
idle for the whole window.

A reader is handed the window, the cell and the peaks.  The profile is the
one under ``bench.TRACE_DIR`` that ``trace.reduce_dir`` reads, if its
``bench.*`` window is the one the harness reduced into ``win.trace``; the
HLO text comes from the executables loaded in this process
(``live_executables`` of the backend).
"""
from __future__ import annotations

import bisect
import glob
import re
import statistics
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import trace

Span = Tuple[str, float, float, dict]     # name, start_ns, duration_ns, args
Op = Tuple[str, float, float, dict]       # name, start_ns, duration_ns, stats
SCOPES = ("paged_kv/write", "paged_kv/gather", "paged_kv/attend", "mlp",
          "logits", "sample")
DECODE_PROGRAMS = ("decode", "decode_horizon")
CLOCK_SLACK_NS = 50e3
TOP = 5
XLA_OPS = "XLA Ops"


# -- reading a profile --------------------------------------------------------------

def _split_args(name: str) -> Tuple[str, dict]:
    """``name#k=v,k2=v2#`` -> (``name``, {k: v}); a plain name has none."""
    base, _, rest = name.partition("#")
    args = {}
    for kv in rest.strip("#").split(","):
        k, eq, v = kv.partition("=")
        if eq:
            args[k] = v
    return base, args


def load(path: str) -> Tuple[Dict[str, Dict[str, List[Op]]], List[Span]]:
    """({device plane: {line: ops}}, host ``bench.*`` and ``engine.*``
    spans with their arguments)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            devices[plane.name] = {
                line.name: [(e.name, e.start_ns, e.duration_ns,
                             dict(e.stats)) for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.", "engine.")):
                        name, args = _split_args(e.name)
                        args.update(e.stats)
                        host.append((name, e.start_ns, e.duration_ns, args))
    return devices, host


def bench_only(host: Iterable[Span]) -> List[trace.Event]:
    """The ``bench.*`` spans as ``trace.reduce`` takes them."""
    return [(n, s, d) for n, s, d, _ in host if n.startswith("bench.")]


def _window(host: List[Span]) -> Tuple[float, float]:
    spans = bench_only(host)
    if not spans:
        raise ValueError("the trace holds no bench.* host span")
    return (min(s for _, s, _ in spans), max(s + d for _, s, d in spans))


def _label(name: str) -> str:
    return "bench.step" if name.startswith("bench.step.") else name


def cores(devices: Dict[str, Dict[str, List[Op]]]
          ) -> List[Dict[str, List[Op]]]:
    """The lines of each device plane that runs operations: the planes
    with an ``XLA Ops`` line."""
    return [lines for lines in devices.values() if XLA_OPS in lines]


# -- the host spans -----------------------------------------------------------------

def innermost(host: List[Span]):
    """A function of time (ns) to the name of the innermost host span that
    holds it, or ``None``.  The spans of one thread nest."""
    times, labels = [], []
    stack: List[Tuple[str, float]] = []

    def pop_until(t):
        while stack and stack[-1][1] <= t:
            end = stack.pop()[1]
            times.append(end)
            labels.append(stack[-1][0] if stack else None)

    for name, s, d, _ in sorted(host, key=lambda e: (e[1], -e[2])):
        pop_until(s)
        stack.append((_label(name), s + d))
        times.append(s)
        labels.append(_label(name))
    pop_until(float("inf"))

    def at(t: float) -> Optional[str]:
        i = bisect.bisect_right(times, t) - 1
        return labels[i] if i >= 0 else None
    return at


def kind_of(span: Optional[str]) -> str:
    """``wait``, ``engine`` or ``harness`` (a ``bench.*`` span, or none)."""
    if span is not None and span.startswith("engine.wait."):
        return "wait"
    if span is not None and span.startswith("engine."):
        return "engine"
    return "harness"


def idle_split(devices: Dict[str, Dict[str, List[Op]]], host: List[Span]
               ) -> dict:
    """Idle seconds of the window, averaged over the devices that run
    operations, by the kind of the innermost host span at each gap's
    midpoint and by span."""
    lo, hi = _window(host)
    at = innermost(host)
    kinds: Dict[str, float] = defaultdict(float)
    by_span: Dict[str, float] = defaultdict(float)
    busy = 0.0
    planes = cores(devices)
    for lines in planes:
        merged = trace._clip(trace.union(
            [(s, s + d) for _, s, d, _ in lines[XLA_OPS]]), lo, hi)
        busy += sum(b - a for a, b in merged)
        for (_, a), (b, _) in zip([(lo, lo)] + merged, merged + [(hi, hi)]):
            if b > a:
                span = at((a + b) / 2)
                kinds[kind_of(span)] += b - a
                by_span[span or "outside"] += b - a
    n = max(len(planes), 1)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "idle_s": {k: kinds[k] / n / 1e9
                       for k in ("wait", "engine", "harness")},
            "by_span": {k: v / n / 1e9 for k, v in
                        sorted(by_span.items(), key=lambda kv: -kv[1])}}


def self_times(host: List[Span]) -> Dict[str, List[float]]:
    """For each ``engine.*`` span name, its self time (duration less its
    child spans') summed per ``bench.step.<i>``, in seconds, one entry per
    step in which it ran."""
    per_step: Dict[Tuple[str, str], float] = defaultdict(float)
    stack: List[list] = []     # [label, end, children_ns, step, duration]
    out: Dict[str, List[float]] = defaultdict(list)

    def close(frame):
        label, end, children, step, dur = frame
        if stack:
            stack[-1][2] += dur
        if step is not None and label.startswith("engine."):
            per_step[(step, label)] += (dur - children) / 1e9

    for name, s, d, _ in sorted(host, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        step = name if name.startswith("bench.step.") else (
            stack[0][3] if stack else None)
        stack.append([_label(name), s + d, 0.0, step, d])
    while stack:
        close(stack.pop())
    for (_, label), v in per_step.items():
        out[label].append(v)
    return dict(out)


def clock_check(devices, host: List[Span], module_program: Dict[str, str]
                ) -> Optional[Tuple[int, int, float]]:
    """(module executions checked, executions that start before their
    ``engine.dispatch.<program>`` span or end after the matching
    ``engine.wait.<program>`` span by more than 50 us, the largest such
    offset in seconds).  ``None`` when the trace has no dispatch span."""
    lo, hi = _window(host)
    by_prog: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
    for name, s, d, _ in host:
        for kind in ("dispatch", "wait"):
            prefix = f"engine.{kind}."
            if name.startswith(prefix):
                by_prog[name[len(prefix):]].append((kind, s, s + d))
    if not by_prog:
        return None
    ivs: Dict[str, List[Tuple[float, float]]] = {}
    for prog, evs in by_prog.items():
        evs.sort(key=lambda e: e[1])
        pairs, start = [], None
        for kind, s, e in evs:
            if kind == "dispatch":
                start = s
            elif start is not None:
                pairs.append((start, e))
                start = None
        ivs[prog] = pairs
    starts = {prog: [a for a, _ in pairs] for prog, pairs in ivs.items()}
    checked = outside = 0
    worst = 0.0
    for lines in cores(devices):
        for name, s, d, _ in lines.get("XLA Modules", []):
            prog = module_program.get(name.split("(")[0])
            if not ivs.get(prog) or s < lo or s + d > hi:
                continue
            # the nearer of the spans that start on either side of it
            i = bisect.bisect_right(starts[prog], s)
            checked += 1
            off = min(max(a - s, s + d - b)
                      for a, b in ivs[prog][max(i - 1, 0):i + 1])
            if off > CLOCK_SLACK_NS:
                outside += 1
                worst = max(worst, off)
    return checked, outside, worst / 1e9


# -- the program scopes -------------------------------------------------------------

_INSTR = re.compile(r'\s*(?:ROOT\s+)?%([^\s=]+)\s*=')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata in one module's HLO text."""
    names: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        op = _OP_NAME.search(line) if m is not None else None
        if op is not None:
            names[m.group(1)] = op.group(1)
    return names


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost of ``SCOPES`` named in an ``op_name`` path."""
    if not op_name:
        return None
    parts = op_name.split("/")
    found = None
    for i in range(len(parts)):
        for sc in SCOPES:
            n = sc.count("/") + 1
            if "/".join(parts[i:i + n]) == sc:
                found = sc
    return found


def _instruction(op_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names: ``%fusion.3 = ...``
    or ``fusion.3``."""
    return op_name.lstrip("%").split(" ", 1)[0].split("{", 1)[0]


def _event_op_name(stats: dict) -> Optional[str]:
    """The ``op_name`` an event's own ``tf_op`` or ``long_name`` stat
    gives, where the trace carries one."""
    tf_op, long_name = stats.get("tf_op"), stats.get("long_name")
    if isinstance(long_name, str):
        m = _OP_NAME.search(long_name)
        if m is not None:
            return m.group(1)
    return tf_op if isinstance(tf_op, str) and tf_op else None


Scoped = Dict[Tuple[Optional[str], str], float]


def scope_seconds(devices, host: List[Span],
                  op_names: Dict[str, Dict[str, str]]) -> Dict[str, Scoped]:
    """Leaf-operation device seconds in the window, averaged over the
    devices that run operations: {module: {(scope, instruction): seconds}},
    with scope ``None`` for an operation under none.  ``op_names`` is each
    module's instruction -> ``op_name`` map."""
    lo, hi = _window(host)
    out: Dict[str, Scoped] = defaultdict(lambda: defaultdict(float))
    planes = cores(devices)
    for lines in planes:
        mods = sorted((s, s + d, name.split("(")[0])
                      for name, s, d, _ in lines.get("XLA Modules", []))
        starts = [s for s, _, _ in mods]
        ops = sorted(lines[XLA_OPS], key=lambda e: (e[1], -e[2]))
        for i, (name, s, d, stats) in enumerate(ops):
            leaf = i + 1 == len(ops) or ops[i + 1][1] >= s + d
            if not leaf or not lo <= s < hi:
                continue
            j = bisect.bisect_right(starts, s) - 1
            if j < 0 or s >= mods[j][1]:
                continue
            module, instr = mods[j][2], _instruction(name)
            op_name = op_names.get(module, {}).get(instr)
            if op_name is None:
                op_name = _event_op_name(stats)
            out[module][(scope_of(op_name), instr)] += d
    n = max(len(planes), 1)
    return {m: {k: v / n / 1e9 for k, v in got.items()}
            for m, got in out.items()}


def by_scope(seconds: Scoped) -> Dict[Optional[str], float]:
    out: Dict[Optional[str], float] = defaultdict(float)
    for (scope, _), v in seconds.items():
        out[scope] += v
    return dict(out)


def paged_kv_share(scoped: Dict[str, Scoped], modules: Iterable[str]
                   ) -> Optional[float]:
    """Leaf-op device time under ``paged_kv/*`` over all leaf-op device
    time of ``modules``, in percent; ``None`` when no operation of theirs
    carries a scope (a program built without them)."""
    total = paged = 0.0
    any_scope = False
    for m in modules:
        for scope, v in by_scope(scoped.get(m, {})).items():
            total += v
            any_scope |= scope is not None
            if scope is not None and scope.startswith("paged_kv/"):
                paged += v
    return 100.0 * paged / total if total > 0 and any_scope else None


# -- one traced run -----------------------------------------------------------------

def loaded_op_names(modules: Iterable[str]) -> Dict[str, Dict[str, str]]:
    """Each of ``modules`` -> its instruction -> ``op_name`` map, from the
    executables loaded in this process; where several share a module name,
    the newest loaded (a process that serves holds one of each)."""
    from jax.extend.backend import get_backend
    wanted, out = set(modules), {}
    for ex in get_backend().live_executables():     # newest first
        for mod in ex.hlo_modules():
            if mod.name in wanted and mod.name not in out:
                out[mod.name] = hlo_op_names(mod.to_string())
    return out


def analyse(devices, host: List[Span]) -> dict:
    """Every reading of one profile: the idle split, the engine spans'
    self times, the clock check and the decode modules' time by scope."""
    module_program = {v: k for k, v in trace.programs().items()}
    decode = [m for m, p in module_program.items() if p in DECODE_PROGRAMS]
    op_names = loaded_op_names(decode)
    scoped = scope_seconds(devices, host, op_names)
    has_engine = any(n.startswith("engine.") for n, _, _, _ in host)
    return {"idle": idle_split(devices, host), "engine_spans": has_engine,
            "self_s": self_times(host),
            "clock": clock_check(devices, host, module_program),
            "decode_modules": decode, "hlo_modules": sorted(op_names),
            "scoped": scoped,
            "paged_kv_share": paged_kv_share(scoped, decode)}


def _traced_steps(win) -> Tuple[List[float], List[float]]:
    """Durations of the window's engine steps inside and before its traced
    part, in seconds."""
    from chipbench import bench
    trace_at = win.t_end - min(bench.TRACE_SECONDS, win.seconds)
    traced = [st.t1 - st.t0 for st in win.steps
              if trace_at <= st.t0 < win.t_end]
    untraced = [st.t1 - st.t0 for st in win.steps if st.t0 < trace_at]
    return traced, untraced


def profile_of(win):
    """(devices, host spans) of the profile ``win.trace`` was reduced from:
    the one under ``bench.TRACE_DIR`` that ``trace.reduce_dir`` reads, if
    its ``bench.*`` window is the same; ``None`` when it is not there (a
    run traced elsewhere)."""
    from chipbench import bench
    paths = glob.glob(str(Path(bench.TRACE_DIR) / "**" / "*.xplane.pb"),
                      recursive=True)
    if win.trace is None or not paths:
        return None
    devices, host = load(sorted(paths)[-1])
    if not bench_only(host):
        return None
    lo, hi = _window(host)
    if (hi - lo) / 1e9 != win.trace["window_s"]:
        return None
    return devices, host


def read_run(win) -> Optional[dict]:
    """The analysis of the traced run that ``win`` belongs to, made once
    and kept on the window; ``None`` when its profile is not found."""
    got = getattr(win, "engine_spans", None)
    if got is not None:
        return got
    profile = profile_of(win)
    if profile is None:
        return None
    got = analyse(*profile)
    win.engine_spans = got
    report(got, *_traced_steps(win))
    return got


def idle_share_of(win, kind: str) -> Optional[float]:
    """Idle share of the window under a host span of ``kind``, in percent;
    ``None`` without engine spans or device operations."""
    got = read_run(win)
    if got is None or not got["engine_spans"]:
        return None
    idle = got["idle"]
    if idle["busy_s"] <= 0 or idle["window_s"] <= 0:
        return None
    return 100.0 * idle["idle_s"][kind] / idle["window_s"]


# -- the report on standard error ---------------------------------------------------

def _ms(xs: List[float]) -> str:
    return f"{1e3 * statistics.median(xs)!r} ms" if xs else "none"


def report(got: dict, traced: List[float], untraced: List[float]):
    def log(msg):
        print(f"spans: {msg}", file=sys.stderr, flush=True)

    idle = got["idle"]
    w = idle["window_s"]
    if idle["busy_s"] <= 0:
        log("no operation ran on a device in the traced window")
    else:
        parts = ", ".join(
            f"{what} {idle['idle_s'][k]!r} s "
            f"({100 * idle['idle_s'][k] / w!r} %)"
            for k, what in (("wait", "waiting on a result"),
                            ("engine", "engine host work"),
                            ("harness", "harness or no span")))
        log(f"idle {sum(idle['idle_s'].values())!r} s of a {w!r} s window: "
            + parts)
        log("idle by innermost span: " + ", ".join(
            f"{k} {v!r} s" for k, v in list(idle["by_span"].items())[:10]))
    if got["self_s"]:
        log("median self time per step: " + ", ".join(
            f"{k} {_ms(v)} ({len(v)} steps)" for k, v in
            sorted(got["self_s"].items(), key=lambda kv: -sum(kv[1]))))
    decode = got["decode_modules"]
    log("scopes from the HLO text of the loaded "
        + (", ".join(got["hlo_modules"]) or "no decode module"))
    secs: Dict[tuple, float] = defaultdict(float)
    for m in decode:
        for k, v in got["scoped"].get(m, {}).items():
            secs[k] += v
    scopes = by_scope(secs)
    total = sum(scopes.values())
    if total > 0:
        log(f"decode device seconds by scope ({', '.join(decode)}): "
            + ", ".join(f"{k} {v!r}" for k, v in scopes.items()
                        if k is not None)
            + f"; unscoped {scopes.get(None, 0.0)!r} of {total!r} "
            f"({100 * scopes.get(None, 0.0) / total!r} %)")
        top = sorted(((v, op) for (sc, op), v in secs.items() if sc is None),
                     reverse=True)[:TOP]
        log("largest unscoped decode operations: " + ", ".join(
            f"{op} {v!r} s" for v, op in top))
    clock = got["clock"]
    if clock is not None:
        checked, outside, worst = clock
        log(f"clock check: {outside} of {checked} module executions lie "
            f"outside their dispatch-to-wait span by more than 50 us "
            f"(largest offset {1e6 * worst!r} us)")
    log(f"engine step median: {_ms(traced)} over {len(traced)} traced "
        f"steps, {_ms(untraced)} over {len(untraced)} untraced steps")
