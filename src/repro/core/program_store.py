"""program_store — typed program handles and the global-memory program tier.

The paper's fastest path (§3.3, Table 1) assumes programs already live in
*global memory*: installing one into the resident syscore costs a copy that
scales with the binary size (hot load, ~1 ms), and re-execution costs a
signal (40 µs) — only the eSDK baseline pays the full 73 ms load on every
run.  The JAX analogue of "program in global memory" is a serialized XLA
executable on disk: a rebooted :class:`~repro.core.syscore.Syscore`
deserializes its programs instead of re-tracing and re-compiling them.

Three pieces:

``ProgramSpec``
    Typed description of a hot-loadable program — fn, abstract args,
    donation, out-shardings — with a stable *content fingerprint* that
    survives process reboots (hash of the fn's source, the flattened
    abstract-arg tree, donation/sharding config and a caller-supplied
    context string for anything the closure captures, e.g. ``repr(cfg)``).

``ProgramHandle``
    The callable returned by ``Syscore.hot_load``: dispatches the cached
    executable (the re-execute path) and owns the per-program stats.
    Handles follow the registry, so a hot swap under the same key is
    picked up by existing handles atomically.

``ProgramStore``
    Disk-backed map from (fingerprint, mesh shape, device count, jax/jaxlib
    version, backend) to a serialized executable, written atomically.  A
    miss — including version skew, topology change or a corrupt payload —
    falls back to compile-and-store; programs that cannot be serialized
    (host callbacks capture unpicklable state) are skipped, never fatal.
    Every entry that fails to read or deserialize, and every executable
    that fails to serialize, counts in ``ProgramStore.errors``, so a boot
    that was meant to be warm can check that nothing fell back.
"""
from __future__ import annotations

import hashlib
import inspect
import itertools
import json
import os
import pickle
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np


# ---------------------------------------------------------------------------
# ProgramSpec
# ---------------------------------------------------------------------------
def _fn_source(fn: Callable) -> str:
    """Best-effort stable identity for ``fn``: its source text, else its
    qualified name — plus any *scalar* closure cells.

    Factory-made programs (``make_decode_horizon_step(cfg, rules, horizon,
    eos_id)`` and friends) all share the inner def's source text, so two
    closures differing only in a captured static (a horizon length, an EOS
    id, a cache length, a ring flag) would otherwise fingerprint
    identically unless every caller remembers to fold the static into
    ``ProgramSpec.context``.  Hashing primitive cell contents
    (int/float/bool/str/bytes/None) closes that silent-collision hole;
    structured captures (config objects, rules dicts) remain the caller's
    job via ``context``."""
    try:
        src = inspect.getsource(fn)
    except (OSError, TypeError):
        src = getattr(fn, "__qualname__", repr(fn))
    cells = getattr(fn, "__closure__", None)
    code = getattr(fn, "__code__", None)
    if cells and code is not None:
        scalars = []
        for name, cell in zip(code.co_freevars, cells):
            try:
                v = cell.cell_contents
            except ValueError:          # cell not yet filled
                continue
            if v is None or isinstance(v, (bool, int, float, str, bytes)):
                scalars.append(f"{name}={v!r}")
        if scalars:
            src += "\n# closure: " + ", ".join(scalars)
    return src


def _leaf_desc(path, leaf) -> str:
    """One abstract-arg leaf -> a stable text line (path, shape, dtype and —
    when the leaf is a LogicalArray — its logical axes)."""
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = np.dtype(getattr(leaf, "dtype", np.float32)).str
    logical = getattr(leaf, "logical", None)
    return f"{'/'.join(parts)}:{shape}:{dtype}:{logical}"


@dataclass(frozen=True, eq=False)
class ProgramSpec:
    """Typed description of a hot-loadable program.

    ``context`` carries everything the fingerprint cannot see through
    ``fn`` — values the closure captures (model config, optimizer config,
    cache length).  ``repr`` of the frozen config dataclasses is the
    idiomatic content.  Equality and hashing go by content fingerprint
    (the generated dataclass ``__eq__`` would choke on the dict-valued
    abstract-arg trees).

    ``out_logical`` optionally carries the OUTPUT pytree as LogicalArrays:
    when the compiling Syscore holds a mesh, it resolves them against its
    sharding rules into explicit ``out_shardings`` (pinning e.g. the
    donated cache's output sharding to its input sharding, so dispatches
    never reshard).  Mesh-less compiles ignore it.  ``out_shardings``
    remains the escape hatch for pre-resolved shardings.
    """
    key: str
    fn: Callable
    abstract_args: Tuple
    donate_argnums: Tuple[int, ...] = ()
    out_shardings: Any = None
    context: str = ""
    out_logical: Any = None

    def __eq__(self, other):
        return (isinstance(other, ProgramSpec)
                and self.fingerprint == other.fingerprint)

    def __hash__(self):
        return hash(self.fingerprint)

    @property
    def fingerprint(self) -> str:
        cached = getattr(self, "_fingerprint", None)
        if cached is None:
            from repro.sharding import LogicalArray
            leaves = jax.tree_util.tree_flatten_with_path(
                self.abstract_args,
                is_leaf=lambda x: isinstance(x, LogicalArray))[0]
            h = hashlib.sha256()
            h.update(_fn_source(self.fn).encode())
            for path, leaf in leaves:
                h.update(_leaf_desc(path, leaf).encode())
            h.update(repr(tuple(self.donate_argnums)).encode())
            h.update(repr(self.out_shardings).encode())
            if self.out_logical is not None:
                h.update(repr(self.out_logical).encode())
            h.update(self.context.encode())
            cached = h.hexdigest()
            object.__setattr__(self, "_fingerprint", cached)
        return cached


# ---------------------------------------------------------------------------
# ProgramHandle
# ---------------------------------------------------------------------------
class ProgramHandle:
    """Callable façade over one installed program of a Syscore.

    ``handle(*args)`` is the paper's re-execute path: a registry lookup and
    a cached-executable dispatch.  The handle resolves through the
    registry on every call, so a hot swap of the same key (install is the
    last, atomic step of ``hot_load``) retargets live handles without any
    coordination — and an evicted key fails with the registry's clear
    error instead of a stale dispatch.
    """

    __slots__ = ("_syscore", "key")

    def __init__(self, syscore, key: str):
        self._syscore = syscore
        self.key = key

    @property
    def program(self):
        return self._syscore.lookup(self.key)

    @property
    def stats(self):
        return self.program.stats

    def __call__(self, *args):
        prog = self._syscore.lookup(self.key)
        out = prog.compiled(*args)
        prog.stats.executions += 1
        return out

    def block(self, *args):
        """Call and block until the device result is ready."""
        return jax.block_until_ready(self(*args))

    def serialize(self):
        return self._syscore.serialize(self.key)

    def evict(self):
        self._syscore.evict(self.key)

    def __repr__(self):
        try:
            p = self.program
            return (f"ProgramHandle({self.key!r}, source={p.source!r}, "
                    f"executions={p.stats.executions})")
        except KeyError:
            return f"ProgramHandle({self.key!r}, evicted)"


# ---------------------------------------------------------------------------
# ProgramStore
# ---------------------------------------------------------------------------
_CODE_VERSION_CACHE: Optional[str] = None


def _code_version() -> str:
    """Content hash of the repro package's own source: the ProgramSpec
    fingerprint only sees the top-level fn's text, not its transitive
    callees (model forward, step helpers), so any edit to the package must
    invalidate stored executables.  Hashed once per process."""
    global _CODE_VERSION_CACHE
    if _CODE_VERSION_CACHE is None:
        h = hashlib.sha256()
        root = Path(__file__).resolve().parent.parent   # src/repro
        for p in sorted(root.rglob("*.py")):
            h.update(str(p.relative_to(root)).encode())
            h.update(p.read_bytes())
        _CODE_VERSION_CACHE = h.hexdigest()[:16]
    return _CODE_VERSION_CACHE


def _env_key() -> Tuple[str, ...]:
    """The environment half of the store key: an executable only revives
    under the jax/jaxlib/backend — and repo code — that produced it."""
    import jaxlib
    backend = jax.default_backend()
    return (jax.__version__, getattr(jaxlib, "__version__", "?"), backend,
            str(jax.device_count()), _code_version())


def _mesh_desc(mesh) -> str:
    if mesh is None or getattr(mesh, "empty", False):
        return "nomesh"
    return ",".join(f"{n}={s}" for n, s in
                    zip(mesh.axis_names, mesh.devices.shape))


class ProgramStore:
    """Persistent 'global memory' for serialized executables.

    Layout (one entry per (fingerprint, mesh, environment) digest)::

        <dir>/<digest>.pkl     pickled (payload, in_tree, out_tree)
        <dir>/<digest>.json    {key, fingerprint, mesh, env, bytes, time}

    Writes are atomic (tmp + rename) so a crashed writer never corrupts a
    warm-boot path; reads tolerate any unpickle failure by reporting a
    miss (the caller recompiles and overwrites).

    Concurrent sharing: ONE store directory may be open in many executors
    at once (a serving cluster's replicas and their failover reboots all
    warm-load from the same dir).  The safety contract:

      * every write lands under a unique temp name (pid + per-process
        sequence — two same-process executors never collide) and becomes
        visible only via an atomic ``os.replace``, so a reader sees either
        the old complete entry or the new complete entry, never a partial;
      * racing writers of the same digest are last-writer-wins — both
        payloads decode the same program, so either outcome is correct;
      * a reader that loses a race with ``clear()`` (file vanishes between
        the existence check and the open) reports a plain miss;
      * corruption of a shared entry degrades exactly one executor to the
        compile-and-store path, which atomically heals the entry for
        everyone else; executors that already installed from it are
        unaffected (the deserialized executable owns no file handle).
    """

    def __init__(self, directory):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.skipped = 0          # programs that refused to serialize
        self.errors = 0           # failed serializes + unreadable or
                                  # undeserializable entries

    # -- keying -------------------------------------------------------------
    def digest(self, spec: ProgramSpec, mesh=None) -> str:
        h = hashlib.sha256()
        h.update(spec.fingerprint.encode())
        h.update(_mesh_desc(mesh).encode())
        h.update("|".join(self._env_key()).encode())
        return h.hexdigest()[:24]

    def _env_key(self) -> Tuple[str, ...]:
        return _env_key()

    # -- read path ----------------------------------------------------------
    def get(self, spec: ProgramSpec, mesh=None):
        """(payload, in_tree, out_tree) on a hit; None on miss/corruption."""
        p = self.directory / (self.digest(spec, mesh) + ".pkl")
        if not p.exists():
            self.misses += 1
            return None
        try:
            with p.open("rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
        except Exception:
            self.misses += 1
            self.errors += 1
            return None
        self.hits += 1
        return payload, in_tree, out_tree

    def contains(self, spec: ProgramSpec, mesh=None) -> bool:
        return (self.directory / (self.digest(spec, mesh) + ".pkl")).exists()

    # -- write path ---------------------------------------------------------
    _tmp_seq = itertools.count()     # class-wide: unique across same-process
                                     # stores sharing one directory

    def _atomic_write(self, name: str, write_fn) -> Path:
        """Write ``<dir>/<name>`` atomically: ``write_fn(fileobj)`` into a
        unique temp file, then ``os.replace`` into place (overwrites a
        racing writer's entry whole — never interleaves with it)."""
        final = self.directory / name
        tmp = self.directory / \
            f".tmp_{name}_{os.getpid()}_{next(self._tmp_seq)}"
        try:
            with tmp.open("wb") as f:
                write_fn(f)
            os.replace(tmp, final)
        finally:
            tmp.unlink(missing_ok=True)
        return final

    def put(self, spec: ProgramSpec, payload: bytes, in_tree, out_tree,
            mesh=None) -> Path:
        digest = self.digest(spec, mesh)
        final = self._atomic_write(
            digest + ".pkl",
            lambda f: pickle.dump((payload, in_tree, out_tree), f,
                                  protocol=pickle.HIGHEST_PROTOCOL))
        meta = {"key": spec.key, "fingerprint": spec.fingerprint,
                "mesh": _mesh_desc(mesh), "env": self._env_key(),
                "bytes": len(payload), "time": time.time()}
        self._atomic_write(
            digest + ".json",
            lambda f: f.write(json.dumps(meta, indent=1).encode()))
        self.puts += 1
        return final

    # -- management ---------------------------------------------------------
    def entries(self) -> Dict[str, Dict[str, Any]]:
        out = {}
        for meta_path in sorted(self.directory.glob("*.json")):
            try:
                out[meta_path.stem] = json.loads(meta_path.read_text())
            except Exception:
                continue
        return out

    def clear(self):
        for p in self.directory.glob("*.pkl"):
            p.unlink(missing_ok=True)
        for p in self.directory.glob("*.json"):
            p.unlink(missing_ok=True)

    def report(self) -> Dict[str, Any]:
        entries = self.entries()
        return {"dir": str(self.directory), "entries": len(entries),
                "bytes": sum(e.get("bytes", 0) for e in entries.values()),
                "hits": self.hits, "misses": self.misses,
                "puts": self.puts, "skipped": self.skipped,
                "errors": self.errors}
