"""syscore — the persistent executor (paper §3.3, contribution C2).

The Epiphany redesign split the monolithic program into a resident *syscore*
(loaded once, cores spin in a wait state) and hot-loadable *usrcore* segments
(application kernels copied into running cores, re-executed on a signal).

TPU/JAX analogue:
  * syscore     = this object: live mesh + sharding rules + hostcall daemon +
                  UVA buffer registry, initialized ONCE per job.
  * usrcore     = an AOT-compiled XLA executable (``jit(...).lower().compile()``)
                  installed from a typed :class:`ProgramSpec`.  ``hot_load``
                  returns a callable :class:`ProgramHandle` without disturbing
                  programs that are executing.
  * re-execute  = calling the handle: dispatch of the cached executable with
                  donated buffers — no re-trace, no re-compile, no re-load.
                  This is the 73 ms -> 40 us path of Table 1.

Programs in *global memory* (paper's fast-load tier) are the job of
:class:`~repro.core.program_store.ProgramStore`: attach one to the Syscore
and ``hot_load`` deserializes a previously stored executable instead of
compiling (``stats.load_s`` vs ``stats.compile_s``), falling back to
compile-and-store on any miss.  The old string-keyed ``execute("key", ...)``
survives as a deprecation shim over the handles.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax

from repro.core.program_store import (ProgramHandle, ProgramSpec,
                                      ProgramStore)
from repro.sharding import make_rules, tree_shardings, tree_structs

# CALL_METRIC name codes for program-lifecycle telemetry (engine-level codes
# 1..3 live in repro.launch.serve; schema table in README)
METRIC_PROGRAM_COMPILE_MS = 4     # hot_load paid a full lower+compile
METRIC_PROGRAM_LOAD_MS = 5        # hot_load revived a stored executable


class UnknownProgramError(KeyError):
    """Lookup of a program key that is not installed in this Syscore."""

    def __init__(self, key: str, installed):
        self.key = key
        self.installed = sorted(installed)
        listing = ", ".join(repr(k) for k in self.installed) or "<none>"
        super().__init__(
            f"program {key!r} is not installed in this Syscore; "
            f"installed programs: [{listing}]")

    def __str__(self):
        return self.args[0]


@dataclass
class ProgramStats:
    lower_s: float = 0.0
    compile_s: float = 0.0
    load_s: float = 0.0            # hot-load (deserialize/install) time
    store_s: float = 0.0           # serialize + write to the program store
    executions: int = 0
    serialized_bytes: int = 0


@dataclass
class Program:
    key: str
    compiled: Any                  # jax.stages.Compiled
    stats: ProgramStats = field(default_factory=ProgramStats)
    fingerprint: str = ""          # ProgramSpec content fingerprint
    source: str = "compile"        # "compile" | "store" | "serialized"
    serializable: Optional[bool] = None   # None = not yet attempted


class Syscore:
    """Persistent executor: initialize once, hot-load programs, re-execute.

    ``store`` attaches the global-memory tier: hot loads first try to
    deserialize from it and compiles write back into it.
    """

    def __init__(self, mesh: Optional[jax.sharding.Mesh] = None,
                 rules: Optional[dict] = None,
                 store: Optional[ProgramStore] = None):
        self.mesh = mesh
        self.rules = rules if rules is not None else make_rules()
        self.store = store
        self.programs: Dict[str, Program] = {}
        self._t_boot = time.perf_counter()
        # interoperability services (C5) are part of the resident system code
        from repro.core.hostcall import HostCallTable
        from repro.core.uva import UVARegistry
        self.hostcalls = HostCallTable()
        self.uva = UVARegistry()

    # -- registry -----------------------------------------------------------
    def lookup(self, key: str) -> Program:
        try:
            return self.programs[key]
        except KeyError:
            raise UnknownProgramError(key, self.programs) from None

    def handle(self, key: str) -> ProgramHandle:
        """A handle for an already-installed program (raises otherwise)."""
        self.lookup(key)
        return ProgramHandle(self, key)

    # -- program lifecycle --------------------------------------------------
    def hot_load(self, spec: Union[ProgramSpec, str],
                 fn: Optional[Callable] = None,
                 abstract_args: Optional[Tuple] = None,
                 *, donate_argnums: Tuple[int, ...] = (),
                 out_shardings=None, context: str = "") -> ProgramHandle:
        """Install the program described by ``spec`` and return its handle.

        With an attached :class:`ProgramStore`, a stored executable for the
        same (fingerprint, mesh, jax environment) is deserialized — the
        global-memory load path, ``stats.load_s`` — instead of compiled;
        a compile writes its result back to the store.  Installation never
        interrupts running programs: the registry swap is the last, atomic
        step (the paper's invariant — user segments may be overwritten only
        while execution is held in system code).

        The legacy positional form ``hot_load(key, fn, abstract_args, ...)``
        is accepted and wrapped into a ProgramSpec.
        """
        if isinstance(spec, ProgramSpec):
            if (fn is not None or abstract_args is not None or donate_argnums
                    or out_shardings is not None or context):
                raise ValueError(
                    "hot_load(ProgramSpec, ...) takes no legacy arguments; "
                    "fold fn/abstract_args/donate_argnums/out_shardings/"
                    "context into the spec itself")
        else:
            spec = ProgramSpec(key=spec, fn=fn, abstract_args=abstract_args,
                               donate_argnums=tuple(donate_argnums),
                               out_shardings=out_shardings, context=context)
        prog = self._load_from_store(spec) if self.store is not None else None
        if prog is None:
            prog = self._compile(spec)
            if self.store is not None:
                self._store_program(spec, prog)
        self.programs[spec.key] = prog         # atomic install
        return ProgramHandle(self, spec.key)

    def _compile(self, spec: ProgramSpec) -> Program:
        structs = tree_structs(spec.abstract_args)
        t0 = time.perf_counter()
        if self.mesh is not None and not getattr(self.mesh, "empty", False):
            shardings = tree_shardings(spec.abstract_args, self.rules,
                                       self.mesh)
            out_shardings = spec.out_shardings
            if out_shardings is None and \
                    getattr(spec, "out_logical", None) is not None:
                # resolve the spec's logical output tree against this
                # syscore's rules + mesh: the donated cache keeps its input
                # sharding (no per-dispatch reshard) and small host-read
                # outputs come back replicated
                out_shardings = tree_shardings(spec.out_logical, self.rules,
                                               self.mesh)
            with jax.set_mesh(self.mesh):
                jf = jax.jit(spec.fn, in_shardings=shardings,
                             out_shardings=out_shardings,
                             donate_argnums=spec.donate_argnums)
                lowered = jf.lower(*structs)
                t1 = time.perf_counter()
                compiled = lowered.compile()
        else:
            jf = jax.jit(spec.fn, donate_argnums=spec.donate_argnums)
            lowered = jf.lower(*structs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
        t2 = time.perf_counter()
        prog = Program(key=spec.key, compiled=compiled,
                       fingerprint=spec.fingerprint, source="compile")
        prog.stats.lower_s = t1 - t0
        prog.stats.compile_s = t2 - t1
        from repro.core.hostcall import CALL_METRIC
        self.hostcalls.dispatch(CALL_METRIC, METRIC_PROGRAM_COMPILE_MS,
                                1e3 * (t2 - t0))
        return prog

    def _load_from_store(self, spec: ProgramSpec) -> Optional[Program]:
        entry = self.store.get(spec, self.mesh)
        if entry is None:
            return None
        payload, in_tree, out_tree = entry
        t0 = time.perf_counter()
        try:
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            compiled = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=self._execution_devices())
        except Exception:
            # stale/incompatible entry that slipped past the env key —
            # reclassify the lookup as a miss and recompile
            self.store.hits -= 1
            self.store.misses += 1
            self.store.errors += 1
            return None
        prog = Program(key=spec.key, compiled=compiled,
                       fingerprint=spec.fingerprint, source="store")
        prog.stats.load_s = time.perf_counter() - t0
        prog.stats.serialized_bytes = len(payload)
        from repro.core.hostcall import CALL_METRIC
        self.hostcalls.dispatch(CALL_METRIC, METRIC_PROGRAM_LOAD_MS,
                                1e3 * prog.stats.load_s)
        return prog

    def _execution_devices(self):
        """The devices a program of this Syscore runs on: its mesh's, or
        the default device.  A deserialized executable must be loaded onto
        exactly these — left to itself it spans every visible device, and a
        1-device program then expects one shard per device."""
        if self.mesh is not None and not getattr(self.mesh, "empty", False):
            return list(self.mesh.devices.flat)
        return jax.devices()[:1]

    def _store_program(self, spec, prog: Program,
                       store: Optional[ProgramStore] = None) -> bool:
        """Write a compiled program to global memory; programs whose
        executables cannot be serialized (e.g. host callbacks capture
        unpicklable state) are marked, counted and skipped, never fatal —
        and never re-attempted."""
        store = store if store is not None else self.store
        if prog.serializable is False:
            return False
        t0 = time.perf_counter()
        try:
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(prog.compiled)
            store.put(spec, payload, in_tree, out_tree, self.mesh)
        except Exception:
            prog.serializable = False
            store.skipped += 1
            store.errors += 1
            return False
        prog.serializable = True
        prog.stats.serialized_bytes = len(payload)
        prog.stats.store_s = time.perf_counter() - t0
        return True

    def install_serialized(self, key: str, payload: bytes, in_tree,
                           out_tree) -> ProgramHandle:
        """Hot-load a previously serialized executable (program 'in global
        memory').  The cost scales with the executable size only — the C3/C4
        load path."""
        from jax.experimental.serialize_executable import deserialize_and_load
        t0 = time.perf_counter()
        compiled = deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=self._execution_devices())
        prog = Program(key=key, compiled=compiled, source="serialized")
        prog.stats.load_s = time.perf_counter() - t0
        prog.stats.serialized_bytes = len(payload)
        from repro.core.hostcall import CALL_METRIC
        self.hostcalls.dispatch(CALL_METRIC, METRIC_PROGRAM_LOAD_MS,
                                1e3 * prog.stats.load_s)
        self.programs[key] = prog
        return ProgramHandle(self, key)

    def serialize(self, key: str):
        """Program -> (payload, in_tree, out_tree) for global-memory storage."""
        from jax.experimental.serialize_executable import serialize
        prog = self.lookup(key)
        payload, in_tree, out_tree = serialize(prog.compiled)
        prog.stats.serialized_bytes = len(payload)
        return payload, in_tree, out_tree

    def persist(self, store: Optional[ProgramStore] = None) -> int:
        """Serialize every installed program into ``store`` (default: the
        attached store) under its recorded fingerprint; returns how many
        were newly written.  Programs without a fingerprint or that refuse
        to serialize are skipped."""
        store = store if store is not None else self.store
        if store is None:
            return 0
        written = 0
        for prog in self.programs.values():
            if not prog.fingerprint:
                continue
            spec = _FingerprintOnlySpec(prog.key, prog.fingerprint)
            if store.contains(spec, self.mesh):
                continue
            if self._store_program(spec, prog, store):
                written += 1
        return written

    def evict(self, key: str):
        self.lookup(key)
        del self.programs[key]

    # -- execution (deprecation shim over ProgramHandle) ---------------------
    def execute(self, key: str, *args):
        """Deprecated string-keyed re-execute; use the ProgramHandle
        returned by ``hot_load`` (or ``handle(key)``) instead."""
        warnings.warn(
            "Syscore.execute(key, ...) is deprecated; call the "
            "ProgramHandle returned by hot_load()/handle() instead",
            DeprecationWarning, stacklevel=2)
        return ProgramHandle(self, key)(*args)

    def execute_blocking(self, key: str, *args):
        warnings.warn(
            "Syscore.execute_blocking(key, ...) is deprecated; use "
            "handle(key).block(...) instead",
            DeprecationWarning, stacklevel=2)
        return ProgramHandle(self, key).block(*args)

    # -- introspection -------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        rep = {
            "uptime_s": time.perf_counter() - self._t_boot,
            "programs": {
                k: {"lower_s": p.stats.lower_s,
                    "compile_s": p.stats.compile_s,
                    "load_s": p.stats.load_s,
                    "store_s": p.stats.store_s,
                    "executions": p.stats.executions,
                    "serialized_bytes": p.stats.serialized_bytes,
                    "source": p.source,
                    "fingerprint": p.fingerprint[:12]}
                for k, p in self.programs.items()},
            "hostcalls": self._hostcall_summary(),
        }
        if self.store is not None:
            rep["store"] = self.store.report()
        return rep

    def _hostcall_summary(self) -> Dict[str, Any]:
        """Aggregate view of the CALL_METRIC / CALL_STEP_REPORT channels —
        the serving engine reports TTFT / decode latency / slot occupancy
        here, so the resident executor can answer "how busy am I" without
        any engine-side state."""
        metrics = {
            code: {"count": len(vals),
                   "mean": sum(vals) / len(vals),
                   "last": vals[-1]}
            for code, vals in self.hostcalls.metrics.items() if vals}
        stamps = [t for t in self.hostcalls.step_stamps if t is not None]
        return {"metrics": metrics,
                "step_reports": len(self.hostcalls.step_times),
                # monotonic per-dispatch stamps (CALL_STEP_REPORT arg 3):
                # span covers the window since the last drain, so a
                # supervisor can turn step walls into utilization without
                # engine-side state
                "step_stamps": len(stamps),
                "step_span_s": (stamps[-1] - stamps[0]) if len(stamps) > 1
                               else 0.0,
                "log_lines": len(self.hostcalls.log_lines)}


class _FingerprintOnlySpec:
    """Duck-typed ProgramSpec substitute for ``persist``: the fingerprint is
    already known, so no fn/abstract-args are needed to key the store."""

    __slots__ = ("key", "fingerprint")

    def __init__(self, key: str, fingerprint: str):
        self.key = key
        self.fingerprint = fingerprint


def cold_execute(fn: Callable, *args):
    """eSDK-analogue baseline: full trace+compile+run on every invocation
    (jit cache defeated with a fresh wrapper).  Used by bench_load_exec."""
    def wrapper(*a):
        return fn(*a)
    return jax.jit(wrapper)(*args)
