"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell is comes from files found by name: the cell's entry in
``BENCHMARK.json``, its configuration (``chipbench/configs/<name>.json``,
whose ``reference`` names the model module under ``chipbench/models/``),
its traffic mix (``chipbench/mixes/<name>.json``) and its per-layer
metrics (``chipbench/metrics/<name>.py``).

The window is driven through the engine's own entry points: requests are
handed to ``ServingEngine.submit`` when they are due, with their due time
as ``arrival_time`` on the engine clock, and the engine advances by
``ServingEngine.step``.  After every step the harness stamps the tokens
that became visible; latencies count from the due time.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT / "src"))

from chipbench import traffic  # noqa: E402

STORE_DIR = HERE / ".store"          # the serving programs, compiled once
TRACE_DIR = HERE / ".trace"          # the profiler's output of a traced run
TRACE_SECONDS = 6.0                  # the traced part: the window's last 6 s
SAMPLE_REQUESTS = 8                  # requests the reference checks per run


def load_module(path: Path, what: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{what}_{path.stem}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, metric_dir: Path = HERE / "metrics"):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, or
    the reader of its quantity, ``metrics/<quantity>.py``, for a name
    ``<quantity>.<use>`` that splits one quantity by the end-to-end metric
    it moves."""
    path = Path(metric_dir) / f"{name}.py"
    if not path.is_file():
        path = Path(metric_dir) / f"{name.split('.')[0]}.py"
    return load_module(path, "metric")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict             # the configuration file's contents
    mix: dict
    traffic: str
    end_to_end: List[dict]
    per_layer: List[dict]

    @functools.cached_property
    def model(self):
        return load_module(HERE / "models" / f"{self.config['reference']}.py",
                           "model")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((Path(root) / entry["file"]).read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=w["chips"], config=config,
                mix=traffic.load_mix(w["traffic"]), traffic=w["traffic"],
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


# -- records ---------------------------------------------------------------------

@dataclass
class ReqRec:
    """What the harness saw of one request."""
    rid: int
    due: float                    # perf_counter time it was due
    req: object                   # the engine's Request
    token_times: List[float] = field(default_factory=list)
    t_prefill_start: Optional[float] = None


@dataclass
class StepRec:
    """One ``ServingEngine.step``: its host span, the prompts it admitted
    and the work its decode dispatch did (each token's attended context
    length)."""
    index: int
    t0: float
    t1: float = 0.0
    prefills: List[int] = field(default_factory=list)     # prompt lengths
    decode_contexts: List[int] = field(default_factory=list)
    decode_steps: int = 0          # in-graph steps the decode dispatch needed


class Recorder:
    """The engine's ``trace=`` hook: keeps when each request's
    ``prefill_slot`` dispatch started, on the host clock."""

    def __init__(self):
        self.prefill_start: Dict[int, float] = {}

    def _ignore(self, *args, **kw):
        pass

    on_boot = on_submit = on_admit = on_done = _ignore

    def on_dispatch(self, program, wall_s, active=0, tokens=0, **extra):
        if program == "prefill_slot":
            self.prefill_start.setdefault(extra["rid"],
                                          time.perf_counter() - wall_s)


@dataclass
class Window:
    """Everything one measured window recorded."""
    t0: float
    seconds: float
    requests: Dict[int, ReqRec]
    steps: List[StepRec]
    refused: int
    late_s: List[float]            # how late each submit was, seconds
    decode_steps: int              # engine counters over the window
    decode_tokens: int
    in_progress: int = 0           # requests still decoding when it ended
    unserved: int = 0              # requests with no first token at the cap
    trace: Optional[dict] = None   # reduced device trace of a traced run

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds


# -- the engine --------------------------------------------------------------------

def engine_config(config: dict, seed: int):
    from repro.engine_config import EngineConfig
    prog = config["program"]
    return EngineConfig.from_dict(dict(config["engine"],
                                       reduced=prog["reduced"],
                                       seed=int(seed) % 2**31,
                                       clock="wall"))


def build_engine(cell: Cell, seed: int, store_dir: Path = STORE_DIR):
    """The engine of the cell over the weights of ``seed``, its programs
    taken from (or compiled into) the program store."""
    from repro.core import ProgramStore
    from repro.launch.serve import ServingEngine
    from repro.models import registry
    prog = cell.config["program"]
    mcfg = registry.get_config(prog["arch"], reduced=prog["reduced"])
    cell.model.check_program_config(cell.config, mcfg)
    params = cell.model.program_params(cell.config, seed, mcfg.padded_vocab)
    recorder = Recorder()
    eng = ServingEngine(prog["arch"], engine_config(cell.config, seed),
                        params=params, store=ProgramStore(store_dir),
                        trace=recorder)
    return eng, recorder


def warm_up(eng, vocab: int):
    """Run every program and host path the window will use: admissions
    into every slot, single-step decode while a request waits, fused
    horizons, and releases."""
    rng = np.random.default_rng(0)
    n = eng.batch + 2
    for i in range(n):
        eng.submit(rng.integers(0, vocab, 1 + i % 7, dtype=np.int32),
                   max_new=2 + (eng.horizon or 1) * (1 + i % 3))
    while eng.step():
        pass
    eng.drain_completed()


# -- the window --------------------------------------------------------------------

def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_window(eng, recorder: Recorder, mix: dict, seed: int,
               seconds: float, vocab: int, trace_dir: Optional[Path] = None
               ) -> Window:
    """Drive the engine for ``seconds`` of traffic, then drain."""
    items = traffic.make_requests(mix, seed, seconds, vocab)
    backlog = (mix["backlog_per_slot"] * eng.batch
               if mix["kind"] == "backlog" else None)
    requests: Dict[int, ReqRec] = {}
    live: Dict[int, ReqRec] = {}
    steps: List[StepRec] = []
    late: List[float] = []
    refused = 0
    nxt = 0
    tracing = False
    ds0, dt0 = eng.decode_steps, eng.decode_tokens
    t0 = time.perf_counter()
    t_end = t0 + seconds
    eng_off = eng.now() - (time.perf_counter() - t0)   # engine clock at t0
    # the trace covers the window's end; writing it out, which takes
    # seconds, happens after the window has closed
    trace_at = t_end - min(TRACE_SECONDS, seconds)

    def submit(item, due: float):
        nonlocal refused
        now = time.perf_counter()
        req = eng.submit(item.prompt, item.max_new,
                         arrival_time=min(eng_off + (due - t0), eng.now()))
        late.append(now - due)
        if req is None:
            refused += 1
            return
        rec = ReqRec(req.rid, due, req)
        requests[req.rid] = live[req.rid] = rec

    def one_step():
        st = StepRec(len(steps), time.perf_counter())
        with _annotate(f"bench.step.{st.index}"):
            eng.step()
        st.t1 = time.perf_counter()
        steps.append(st)
        with _annotate("bench.stamp"):
            for rid in list(live):
                rec = live[rid]
                got = len(rec.req.generated)
                had = len(rec.token_times)
                if got > had:
                    if had == 0:
                        rec.t_prefill_start = recorder.prefill_start.get(rid)
                        st.prefills.append(int(rec.req.prompt_len))
                    # generated[j], j >= 1, came from a decode step that
                    # attended prompt_len + j positions
                    js = range(max(had, 1), got)
                    st.decode_contexts.extend(rec.req.prompt_len + j
                                              for j in js)
                    st.decode_steps = max(st.decode_steps, len(js))
                    rec.token_times.extend([st.t1] * (got - had))
                if rec.req.done:
                    del live[rid]

    while True:
        now = time.perf_counter()
        if trace_dir is not None and not tracing and now >= trace_at:
            import jax
            jax.profiler.start_trace(str(trace_dir))
            tracing = True
        if now >= t_end:
            break
        with _annotate("bench.submit"):
            if backlog is None:
                while nxt < len(items) and t0 + items[nxt].due <= now:
                    submit(items[nxt], t0 + items[nxt].due)
                    nxt += 1
            else:
                while len(eng.queue) < backlog and nxt < len(items):
                    submit(items[nxt], now)
                    nxt += 1
        if eng.has_work:
            one_step()
        else:
            wait = (t0 + items[nxt].due - now) if nxt < len(items) else 1e-3
            with _annotate("bench.wait"):
                time.sleep(min(max(wait, 0.0), t_end - now, 2e-3))
    if tracing:
        import jax
        jax.profiler.stop_trace()
    ds1, dt1 = eng.decode_steps, eng.decode_tokens

    if not mix.get("drain", True):
        # a batch job's unfinished work: queued requests never started are
        # withdrawn, running ones are left as they stand
        for rec in list(live.values()):
            if eng.withdraw(rec.rid) is not None:
                del requests[rec.rid]
                del live[rec.rid]
    else:
        # every request due in the window is served its first token; the
        # rest of an answer still decoding then is due after the window
        cap = time.perf_counter() + mix["drain_cap_s"]
        while (any(not r.token_times for r in live.values())
               and time.perf_counter() < cap and eng.has_work):
            one_step()
    live = {rid: r for rid, r in live.items() if not r.req.done}
    win = Window(t0=t0, seconds=seconds, requests=requests, steps=steps,
                 refused=refused, late_s=late, decode_steps=ds1 - ds0,
                 decode_tokens=dt1 - dt0, in_progress=len(live),
                 unserved=sum(1 for r in live.values() if not r.token_times))
    return win


# -- end-to-end metrics ------------------------------------------------------------

def ttft_s(win: Window) -> List[float]:
    return [r.token_times[0] - r.due for r in win.requests.values()
            if r.token_times]


def itl_s(win: Window) -> List[float]:
    """Every gap between two visible tokens of one request, where the
    later token became visible inside the window."""
    gaps = []
    for r in win.requests.values():
        t = np.asarray(r.token_times)
        if len(t) > 1:
            g = np.diff(t)
            gaps.extend(g[t[1:] <= win.t_end].tolist())
    return gaps


def output_tokens_in_window(win: Window) -> int:
    return sum(int(np.sum(np.asarray(r.token_times) <= win.t_end))
               for r in win.requests.values())


def pct(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def _ms(xs, q):
    return None if not xs else 1e3 * pct(xs, q)


END_TO_END = {
    "ttft_p50_ms": lambda w: _ms(ttft_s(w), 50),
    "itl_p95_ms": lambda w: _ms(itl_s(w), 95),
    "output_tok_s": lambda w: output_tokens_in_window(w) / w.seconds,
}


# -- the check ---------------------------------------------------------------------

def pick_sample(win: Window, seed: int, n: int = SAMPLE_REQUESTS):
    """Finished requests to check: the one with the most served tokens,
    and others drawn from the seed."""
    done = sorted((r for r in win.requests.values()
                   if r.req.done and r.token_times),
                  key=lambda r: (-len(r.req.generated), r.rid))
    if not done:
        return []
    rest = done[1:]
    rng = np.random.default_rng([int(seed) % 2**63, 99])
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[0]] + [rest[i] for i in sorted(picks)]


def check(cell: Cell, seed: int, sample, control: bool = False) -> dict:
    """Compare what the timed path served with the plain reference: the
    widest gap by which a served token's reference logit lies below the
    reference's best.  Returns the readings and, with ``control``, the
    readings of the control: the float8 reference's first picks at every
    position of the same requests, in the program's place."""
    model = cell.model
    w = model.reference_weights(cell.config, seed)
    length = cell.config["engine"]["max_len"]
    gaps, gaps8 = [], []
    for r in sample:
        g, g8 = model.logit_gaps(cell.config, w, r.req.prompt,
                                 r.req.generated, length, control=control)
        gaps.append(g)
        gaps8.append(g8)
    del w
    n = int(sum(len(g) for g in gaps))
    out = {"widest_logit_gap": float(max(g.max() for g in gaps)),
           "served_tokens_checked": n, "requests_checked": len(sample)}
    if control:
        out["control"] = {"widest_logit_gap": float(max(g.max()
                                                        for g in gaps8)),
                          "served_tokens_checked": n,
                          "requests_checked": len(sample)}
    return out


def verdict(readings: dict, bad_streams: int, limits: dict):
    """``correct`` and each number compared beside its limit."""
    checks = {
        "widest_logit_gap": {"value": readings.get("widest_logit_gap"),
                             "limit": limits["widest_logit_gap"]},
        "served_tokens_checked": {
            "value": readings.get("served_tokens_checked", 0),
            "limit": limits["min_served_tokens_checked"]},
        "bad_streams": {"value": bad_streams, "limit": 0},
    }
    correct = (checks["widest_logit_gap"]["value"] is not None
               and checks["widest_logit_gap"]["value"]
               <= limits["widest_logit_gap"]
               and checks["served_tokens_checked"]["value"]
               >= limits["min_served_tokens_checked"]
               and bad_streams == 0)
    return bool(correct), checks


def stream_errors(win: Window, vocab: int) -> int:
    """Finished requests whose stream is not max_new valid ids."""
    bad = 0
    for r in win.requests.values():
        if r.req.done:
            g = r.req.generated
            if len(g) != r.req.max_new or min(g) < 0 or max(g) >= vocab:
                bad += 1
    return bad


# -- one run -----------------------------------------------------------------------

def peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), n_chips)}


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, store_dir: Path = STORE_DIR,
        trace_dir: Path = TRACE_DIR, control: bool = False,
        peaks: Optional[dict] = None) -> dict:
    """One run of ``cell``; returns the result line's object.  With
    ``control`` it also holds the control's verdict under ``control``.
    ``peaks`` stands in for the table's entry of this device (tests on the
    CPU)."""
    import shutil
    compiles = _count_compiles()

    t_build = time.perf_counter()
    eng, recorder = build_engine(cell, seed, store_dir)
    vocab = eng.cfg.vocab_size
    t_warm = time.perf_counter()
    warm_up(eng, vocab)
    programs = eng.syscore.report()["programs"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    t_setup = time.perf_counter()
    setup_s = t_setup - t_process
    log(f"set-up: {t_build - t_process!r} s to the engine (imports, the "
        f"chip), {t_warm - t_build!r} s weights and engine, "
        f"{t_setup - t_warm!r} s warm-up")
    n_compiles0 = compiles[0]
    win = run_window(eng, recorder, cell.mix, seed, seconds, vocab,
                     trace_dir if trace else None)
    compiled_in_window = compiles[0] - n_compiles0
    errors = stream_errors(win, vocab)
    sample = pick_sample(win, seed)
    memory = peak_bytes()
    device = device_info(cell.chips)
    device["memory_peak_bytes"] = memory
    lag = np.asarray(win.late_s) if win.late_s else np.zeros(1)
    log(f"generator lateness: p50 {1e3 * np.median(lag)!r} ms, max "
        f"{1e3 * lag.max()!r} ms over {len(win.late_s)} submits")
    log(f"window: {len(win.steps)} steps, {win.decode_steps} decode "
        f"dispatches, {win.decode_tokens} decode tokens, "
        f"{len(win.requests)} requests, {win.refused} refused, "
        f"{win.unserved} with no first token at the cap, "
        f"{win.in_progress} still decoding at the end; compiles inside "
        f"the window: {compiled_in_window}")
    ttft = ttft_s(win)
    if ttft:
        log("ttft ms: " + ", ".join(f"p{q} {_ms(ttft, q)!r}"
                                    for q in (50, 90, 95))
            + f" over {len(ttft)} requests")
    log("programs: " + ", ".join(f"{k} {v['source']}"
                                 for k, v in programs.items()))

    if trace:
        from chipbench import trace as trace_mod
        win.trace = trace_mod.reduce_dir(trace_dir)
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(
                win, cell, peaks or _peaks(device))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] in END_TO_END:
                v = END_TO_END[m["name"]](win)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the engine and its cache go before the reference runs
    del eng, recorder
    gc.collect()
    t_ref = time.perf_counter()
    readings = check(cell, seed, sample, control=control) if sample else {}
    log(f"reference: {time.perf_counter() - t_ref!r} s for "
        f"{readings.get('served_tokens_checked', 0)} served tokens")

    limits = cell.config["limits"]
    correct, checks = verdict(readings, errors, limits)
    out = {"correct": correct, "attempted": len(win.requests) + win.refused,
           "failed": win.refused + win.unserved, "metrics": metrics,
           "device": device, "compiles_in_window": compiled_in_window}
    if control:
        c_correct, c_checks = verdict(readings.get("control", {}), 0, limits)
        out["control"] = {"correct": c_correct, "checks": c_checks}
        for k, c in c_checks.items():
            log(f"control check {k}: {c['value']!r} (limit {c['limit']!r})")
    if trace:
        out["breakdown"] = win.trace["breakdown"]
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    out["checks"] = checks
    return out


def use_caches():
    """JAX's persistent compilation cache, at the checkout's fixed path
    (or ``JAX_COMPILATION_CACHE_DIR``), for every program however quick
    to compile: only a run's first set-up compiles."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _peaks(device: dict) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device["kind"] not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device['kind']!r} in "
                       f"chipbench/peaks.json")
    return table["devices"][device["kind"]]


def _count_compiles() -> List[int]:
    """A counter of backend compiles, bumped by JAX's monitoring events."""
    import jax
    box = [0]

    def on_event(event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return box
