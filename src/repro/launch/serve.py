"""Continuous-batching serving engine on the persistent executor.

The serving engine realizes the paper's execution model end-to-end:

  * syscore boots once; ``prefill``, ``prefill_slot`` and ``decode``
    programs are hot-loaded as separate usrcore segments (C2);
  * switching between programs costs a registry lookup (paper: re-execute
    40 us vs full reload 73 ms) — in particular ADMISSION of a new request
    into a running batch is a re-execute of ``prefill_slot``, never a
    recompile;
  * model weights can be placement-classified (C1): resident (usrcore),
    host-streamed (usrmem) or paged on demand (dynamic, C4 — MoE experts);
  * request/response buffers live in the UVA registry (C5) so host code
    reads generations with ordinary numpy indexing;
  * engine telemetry (TTFT, decode latency, occupancy) flows through the
    numbered hostcall table (C5) of the resident syscore.

True continuous batching (v2): every batch row ("slot") carries its own
absolute position in the cache tree's per-slot ``pos`` vector, decode
attention masks each row up to its own valid length, and finished slots
are refilled from a bounded arrival-time queue BETWEEN decode steps — a
newly admitted request is prefilled into its slot by the hot-loaded
``prefill_slot`` program while the other slots' state is untouched (the
hot-load invariant: mutate user segments only between executions).  Mixed-
length traffic therefore never drains the batch the way the eSDK loader
serialized kernels.

Exactness: admission is always per-slot (batch-1 prefill scattered into
the live cache), so every request's greedy output is token-for-token
identical to a batch-of-1 decode of the same prompt
(``reference_generate``).  Note right-padded prefill is position-exact for
attention layers (pads are masked); for recurrent layers (SSM/RG-LRU) the
padded tail enters the state, which is still engine/reference-consistent
because both sides pad to the same ``prefill_len``.

Paged KV (v3): with ``paged=True`` the per-slot KV cache becomes fixed-
size blocks in a capacity-bounded device arena addressed through a block
table in the cache tree (C4 — the data-page instantiation of
``__dynamic_call``; see ``repro.core.paging``).  Admission defers under
arena pressure, preempted requests swap to a host-DRAM tier and swap back
in on refill (a page fault if their blocks were evicted), and the total
KV footprint the engine can serve is bounded by host memory, not device
memory — token-exactly.

Speculative decoding (v4): with ``spec_k=K`` each engine iteration
proposes up to K draft tokens per slot from the request's own history
(n-gram prompt lookup, ``repro.spec``) and scores them ALL in one
execution of a fourth hot-loaded ``verify`` program — the Table-1
re-execute arithmetic applied to the decode loop: up to K+1 decode
dispatches collapse into one.  Verification accepts each row's longest
greedy-matching prefix and rolls rejected state back in-program (KV
``pos`` truncation + byte restore, paged block-table scatter restore,
recurrent-state snapshot select), so the emitted stream is token-for-
token IDENTICAL to the non-speculative engine no matter how wrong the
drafts are.  In paged mode, speculative blocks are over-allocated before
the verify call (``PagedKVManager.grow``) and reclaimed on rejection
(``trim_to_base``).

Fused decode horizons (v5): with ``horizon=H`` the engine hot-loads a
``decode_horizon`` program that runs H decode iterations in ONE dispatch
(``lax.scan`` of the same per-token decode step, in-graph greedy
feedback, per-slot termination masking), returning a device-side event
buffer — emitted tokens, per-slot finish step, occupancy — in one
transfer.  Host bookkeeping (admissions, paged-arena pressure,
preemption, metrics) happens only at horizon boundaries, and the horizon
adaptively shrinks to a single plain ``decode`` dispatch while an
eligible request is waiting in the queue — a queued request never waits
behind a fused dispatch (a wall-clock arrival landing MID-horizon still
waits out the remainder of that horizon, at most H-1 decode steps; that
bounded tail is the one TTFT cost of fusing).
Output streams stay token-for-token identical to the step-at-a-time
engine; the host boundary is simply crossed once per horizon, not once
per token — the paper's "keep control resident on the device" lesson
applied to the generation loop itself.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import steps as steps_lib
from repro.core import ProgramStore, Syscore
from repro.core.hostcall import CALL_BATCH, CALL_METRIC, CALL_STEP_REPORT
from repro.core.syscore import (METRIC_PROGRAM_COMPILE_MS,
                                METRIC_PROGRAM_LOAD_MS)
from repro.engine_config import (EngineConfig, HorizonConfig, PagingConfig,
                                 PrefixConfig, ShardConfig, SpecConfig)
from repro.launch.mesh import serving_mesh
from repro.models import registry, transformer
from repro.sharding import make_rules, tree_shardings
from repro.spec import NGramProposer

# CALL_METRIC name codes used by the engine (schema documented in README)
METRIC_TTFT_MS = 1        # time-to-first-token per request, ms
METRIC_DECODE_MS = 2      # per decode-step wall latency, ms
METRIC_OCCUPANCY = 3      # active slots / batch, per decode step
# (codes 4/5 are program-lifecycle telemetry, repro.core.syscore)
METRIC_PAGE_FAULT = 6     # paged KV swap-in copied blocks from host (value
                          # = blocks moved), per fault
METRIC_ARENA_OCCUPANCY = 7  # resident arena blocks / capacity, per decode step
METRIC_SPEC_ACCEPT = 8    # accepted / proposed draft tokens, per verify step
METRIC_HORIZON_TOKENS = 9  # tokens emitted per fused decode-horizon dispatch
METRIC_PREFIX_HIT = 10    # prompt tokens served from shared prefix blocks
                          # (value = matched tokens), per warm admission

# Host spans on the profiler's own clock, the one the device's events are
# on: ``engine.step`` around a whole step, and inside it ``engine.admit``,
# ``engine.pager`` (each call into the pager), ``engine.dispatch.<program>``
# (the enqueue), ``engine.wait.<program>`` (blocking on that dispatch's
# result), ``engine.place``, ``engine.emit`` and ``engine.telemetry``.  A
# span is a TraceMe: about a microsecond, and nothing recorded, when no
# profiler session is active.
_span = jax.profiler.TraceAnnotation


@dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S_p,) int32
    max_new: int = 16
    arrival_time: float = 0.0        # engine-clock time at which it may start
    generated: List[int] = field(default_factory=list)
    done: bool = False
    prompt_len: int = 0
    slot: int = -1
    t_submit: float = 0.0            # wall-clock timestamps
    t_first: Optional[float] = None  # None until the request is placed
    t_done: Optional[float] = None   # None until it finishes
    needs_resume: bool = False       # preempted: KV lives in the pager, not
                                     # a slot; re-admission swaps in instead
                                     # of prefilling
    gen_at_admit: int = 0            # len(generated) at last (re)admission

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token; ``None`` for a request that was never
        placed (still queued, rejected, or killed before admission) —
        never garbage computed from a placeholder timestamp."""
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def latency_s(self) -> Optional[float]:
        """Submit-to-done wall latency; ``None`` until finished."""
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit


class ServingEngine:
    """Continuous-batching engine over three hot-loaded programs.

    Configuration (Executor API v3)
    -------------------------------
    The engine is configured by ONE frozen value object::

        ServingEngine(arch, EngineConfig(
            batch=8, max_len=256,
            paging=PagingConfig(kv_block=8, arena_blocks=96),
            spec=SpecConfig(k=3), horizon=HorizonConfig(length=4),
            shard=ShardConfig(n_devices=8)))

    See :mod:`repro.engine_config` for every knob: ``PagingConfig`` is the
    paged KV-cache arena, ``SpecConfig`` speculative decoding,
    ``HorizonConfig`` fused decode horizons, ``ShardConfig`` the
    tensor-parallel mesh the programs compile against.  Subsystem
    semantics are documented in the module docstring above (v3/v4/v5) and
    on the sub-configs themselves.

    Runtime objects stay keyword arguments — a config describes *what* to
    build, never holds device state:

    params: a pre-initialized parameter tree (else ``config.seed`` inits
        one).  On a sharded engine the tree is device_put to the rule
        shardings either way.
    mesh: a live mesh overriding ``config.shard`` (tests/benchmarks that
        build their own topologies).
    store: an open :class:`ProgramStore` ("global memory").  A warm boot
        deserializes every program from it instead of recompiling (stats:
        ``load_s > 0, compile_s == 0``); a cold boot compiles and writes
        back.  Store entries are keyed per mesh shape, so each
        ``ShardConfig.n_devices`` warm-boots independently.
        ``config.store_dir`` is declarative shorthand.

    Tensor parallelism: with ``shard.n_devices > 1`` the engine builds a
    1-D ``serving_mesh`` and compiles all programs with the logical-axis
    rules resolved against it — weights and KV shard over heads (head_dim
    where heads don't divide), the paged arena shards its channel axes
    while block identity stays replicated, so the host-side pager and
    scheduler are mesh-agnostic.  Token streams are greedy-exact vs the
    1-device engine (asserted per family in ``tests/test_tp.py``).

    The legacy 18-kwarg surface (``batch=``, ``paged=``, ``spec_k=``, ...)
    survives one release behind a ``DeprecationWarning`` and maps through
    :meth:`EngineConfig.from_legacy_kwargs`.
    """

    def __init__(self, arch: str, config: Optional[EngineConfig] = None, *,
                 params=None, mesh=None,
                 store: Optional[ProgramStore] = None,
                 prefix_store=None, fault_hook=None, trace=None, **legacy):
        if config is None:
            config = EngineConfig.from_legacy_kwargs(**legacy)
            if legacy:
                warnings.warn(
                    "ServingEngine(**kwargs) is deprecated; pass "
                    "config=EngineConfig(...) (repro.engine_config)",
                    DeprecationWarning, stacklevel=2)
        elif legacy:
            raise TypeError(
                "ServingEngine: pass either config=EngineConfig(...) or "
                f"legacy keyword arguments, not both: {sorted(legacy)}")
        self.config = config
        self.arch = arch
        # injectable fault hook (cluster serving): called with the engine
        # step count at the top of every tick(); raising SimulatedFailure
        # (repro.runtime.fault) models this replica crashing mid-serving
        self.fault_hook = fault_hook
        # injectable trace recorder (runtime.autotune.TraceLog): observes
        # submits, admissions, decode-path dispatches and completions so a
        # serving run can be replay-simulated under different knobs.  A
        # None trace costs one attribute test per event.
        self.trace = trace
        self.reduced = config.reduced
        self.cfg = registry.get_config(arch, reduced=config.reduced)
        assert not self.cfg.is_encdec, "decoder-only serving engine"
        self.rules = make_rules(fsdp=config.shard.fsdp)
        self.batch = config.batch
        self.max_len = config.max_len
        self.prefill_len = config.resolved_prefill_len
        self.eos_id = config.eos_id
        self.max_queue = config.max_queue
        self.clock = config.clock
        self.group_prefill = config.group_prefill
        if mesh is None and config.shard.n_devices > 1:
            mesh = serving_mesh(config.shard.n_devices, config.shard.axis)
        self.mesh = mesh
        if store is None and config.store_dir is not None:
            store = ProgramStore(config.store_dir)
        self.syscore = Syscore(mesh=mesh, rules=self.rules, store=store)
        mod = steps_lib.model_module(self.cfg)
        self.params = params if params is not None else mod.init_params(
            self.cfg, jax.random.PRNGKey(config.seed))

        # hot-load the programs once (C2).  prefill = whole-batch prefill
        # (cold restore / registry compat); prefill_slot = one-slot
        # admission into a live batch; decode = one greedy token for every
        # slot at its own position; verify / decode_horizon per config.
        # With a store attached, a warm boot installs all of them by
        # deserialization — no recompiles.
        cfg = self.cfg
        self.paged = config.paged
        self.timeslice = config.paging.timeslice if config.paged else None
        self.pager = None
        self.prefix_cfg = config.prefix
        self.prefix_store = None
        self._prefix_tier1 = False
        self.prefix_suffix = (config.resolved_prefix_suffix
                              if config.prefix is not None else 0)
        self.spec_k = config.spec_k
        self.spec_ngram = config.spec.ngram if config.spec is not None else 2
        self.horizon = config.horizon_length
        if self.spec_k is not None:
            assert not self.group_prefill, \
                "group_prefill rewrites every slot; incompatible with the " \
                "speculative non-ring cache layout"
        if self.paged:
            assert not self.group_prefill, \
                "group_prefill rewrites every slot; incompatible with paging"
            self.kv_block = config.paging.kv_block
            self.blocks_per_slot = self.max_len // self.kv_block
            self.arena_blocks = config.paging.resolved_arena_blocks(
                self.batch, self.max_len)
        specs = steps_lib.serve_program_specs(cfg, self.rules, config)
        if self.mesh is not None:
            # the sharded engine's params live sharded exactly as the
            # programs expect them (same rules, same resolver as the
            # Syscore's in_shardings) — hot dispatches never reshard
            self.params = jax.device_put(self.params, tree_shardings(
                transformer.abstract_params(cfg), self.rules, self.mesh))
        self.programs = {name: self.syscore.hot_load(spec)
                         for name, spec in specs.items()}
        self._prefill = self.programs.get("prefill")
        self._prefill_slot = self.programs["prefill_slot"]
        self._prefill_offset = self.programs.get("prefill_offset")
        self._decode = self.programs["decode"]
        self._verify = self.programs.get("verify")
        self._decode_horizon = self.programs.get("decode_horizon")

        self._cache_shardings = None
        if self.mesh is not None:
            c_abstract = specs["decode"].abstract_args[1]
            self._cache_shardings = tree_shardings(c_abstract, self.rules,
                                                   self.mesh)
        if self.paged:
            from repro.core.paging import (PagedKVManager, PrefixStore,
                                           leaf_kind)
            self.caches = self._new_caches(functools.partial(
                transformer.init_paged_cache, cfg, self.batch, self.max_len,
                kv_block=self.kv_block, arena_blocks=self.arena_blocks))
            if self.prefix_cfg is not None and prefix_store is None:
                # engine-private store; a cluster supervisor passes ONE
                # shared PrefixStore so prefixes survive replica failover
                prefix_store = PrefixStore()
            self.prefix_store = (prefix_store if self.prefix_cfg is not None
                                 else None)
            self.pager = PagedKVManager(
                self.arena_blocks,
                transformer.paged_block_bytes(cfg, self.kv_block),
                uva=self.syscore.uva,
                kv_block=self.kv_block,
                prefix_store=self.prefix_store,
                on_fault=lambda blocks: self.syscore.hostcalls.dispatch(
                    CALL_METRIC, METRIC_PAGE_FAULT, float(blocks)))
            if self.prefix_cfg is not None:
                # the warm (skip-prefill) path requires byte-identical
                # suffix recompute down the single-token decode path:
                # recurrent-state families must replay the whole prompt to
                # rebuild their state at the divergence point, and MoE
                # routing reduces over different shapes in batched prefill
                # vs one-token decode (top-k flips on low-bit drift).
                # Both take the tier-2 path instead — full prefill over
                # read-only shared blocks: storage deduplicated, compute
                # identical, provably exact for every family
                kinds = {leaf_kind(p) for p, _ in
                         jax.tree_util.tree_flatten_with_path(self.caches)[0]}
                self._prefix_tier1 = ("kv" in kinds and "state" not in kinds
                                      and self.cfg.n_experts == 0)
        else:
            self.caches = self._new_caches(functools.partial(
                transformer.init_cache, cfg, self.batch, self.max_len,
                ring=self.spec_k is None))
        self._proposers: Dict[int, NGramProposer] = {}
        self.spec_steps = 0            # verify-program executions
        self.draft_tokens = 0          # drafts proposed (engine lifetime)
        self.accepted_drafts = 0       # drafts accepted (engine lifetime)
        self.preemptions = 0
        self.swap_ins = 0
        self.prefix_admissions = 0     # admissions that mapped shared blocks
        self.warm_admissions = 0       # of those, warm-path (skip-prefill)
        self.prefix_tokens_reused = 0  # prompt tokens never re-prefilled
        self.slots: List[Optional[Request]] = [None] * self.batch
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self.steps = 0                 # engine iterations (incl. idle ticks)
        self.decode_steps = 0          # decode-path program dispatches
        self.decode_tokens = 0         # tokens emitted by the decode path
        self.horizon_steps = 0         # decode_horizon executions
        self.horizon_tokens = 0        # tokens emitted by fused horizons
        self.admitted = 0
        self.rejected = 0
        self.refill_admissions = 0     # admissions while other slots active
        self._n_submitted = 0
        self.draining = False          # quiescing: no new admissions, the
                                       # in-flight batch runs to completion
        self._t0 = time.perf_counter()
        if self.trace is not None:
            self.trace.on_boot(arch, config)

    # -- clock ----------------------------------------------------------------
    def now(self) -> float:
        if self.clock == "step":
            return float(self.steps)
        return time.perf_counter() - self._t0

    # -- request management ---------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int = 16,
               arrival_time: float = 0.0,
               rid: Optional[int] = None) -> Optional[Request]:
        """Enqueue a request; None if the bounded admission queue is full.

        ``rid`` pins the request id instead of taking the next engine-local
        one — a cluster router assigns GLOBAL ids so a request keeps its
        identity across replicas and failover replays (the internal
        counter advances past any pinned id, so later default submissions
        never collide)."""
        if self.draining or len(self.queue) >= self.max_queue:
            self.rejected += 1
            return None
        prompt = np.asarray(prompt, np.int32)[-self.prefill_len:]
        max_new = min(max_new, self.max_len - len(prompt))
        if self.paged and self._blocks_needed(len(prompt), max_new) > \
                self.arena_blocks:
            self.rejected += 1       # can never fit the arena, even alone
            return None
        if rid is None:
            rid = self._n_submitted
        req = Request(rid=int(rid), prompt=prompt, max_new=max_new,
                      arrival_time=arrival_time, prompt_len=len(prompt),
                      t_submit=time.perf_counter())
        self._n_submitted = max(self._n_submitted, int(rid) + 1)
        bisect.insort(self.queue, req,
                      key=lambda r: (r.arrival_time, r.rid))
        if self.trace is not None:
            self.trace.on_submit(req)
        return req

    def _place(self, slot: int, req: Request, last_logits: np.ndarray):
        """Post-prefill bookkeeping shared by both admission paths."""
        with _span("engine.place", rid=req.rid):
            first = int(np.argmax(last_logits[: self.cfg.vocab_size]))
            req.generated.append(first)
            if self.spec_k is not None:
                # per-slot proposer state: one prompt-lookup index per
                # request, created at first admission, fed as tokens append,
                # surviving preempt/resume round trips (keyed by rid, not
                # slot)
                prop = self._proposers[req.rid] = NGramProposer(
                    self.spec_ngram)
                prop.observe(req.prompt.tolist())
                prop.observe([first])
            req.t_first = time.perf_counter()
            req.slot = slot
            req.gen_at_admit = len(req.generated)
            self.slots[slot] = req
            self.admitted += 1
            # a refill = admission into a batch that is already mid-flight:
            # some other slot's request has decoded past its prefill token
            # and is still going.  Wave admissions (fresh batch, whether at
            # boot or after a full drain) don't count — those are the seed
            # engine's drain-then-refill schedule.
            if any(s is not None and s is not req and len(s.generated) > 1
                   for s in self.slots):
                self.refill_admissions += 1
            self.syscore.hostcalls.dispatch(
                CALL_METRIC, METRIC_TTFT_MS, 1e3 * req.ttft_s)
            if self.trace is not None:
                self.trace.on_admit(req)
            self._maybe_finish(req)   # max_new == 1 or instant EOS

    def _new_caches(self, init):
        """The cache tree ``init()`` makes, where it lives: on a mesh each
        device makes only its own shards (the whole tree of a model spread
        over several chips need not fit one of them)."""
        if self._cache_shardings is None:
            return init()
        return jax.jit(init, out_shardings=self._cache_shardings)()

    def _pin_caches(self):
        """Re-pin the cache tree to its compiled program shardings before a
        dispatch.  Host-side mutation between executions (pager block moves,
        ``pos`` writes) can leave a leaf on a default single-device sharding,
        which an AOT-compiled executable rejects; device_put restores the
        committed sharding and is a no-op for leaves already carrying it.
        Mesh-less engines skip entirely."""
        if self._cache_shardings is not None:
            self.caches = jax.device_put(self.caches, self._cache_shardings)

    def _admit_one(self, slot: int, req: Request):
        """Prefill ``req`` into ``slot`` of the live batch (re-execute of the
        hot-loaded prefill_slot program — admission never recompiles)."""
        self._pin_caches()
        tokens = np.zeros((1, self.prefill_len), np.int32)
        tokens[0, :req.prompt_len] = req.prompt
        t1 = time.perf_counter()
        with _span("engine.dispatch.prefill_slot", rid=req.rid,
                   prompt_len=req.prompt_len):
            self.caches, last = self._prefill_slot(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(req.prompt_len, jnp.int32))
        with _span("engine.wait.prefill_slot", rid=req.rid,
                   prompt_len=req.prompt_len):
            last = np.asarray(last)        # blocks on the device result
        if self.trace is not None:
            self.trace.on_dispatch("prefill_slot",
                                   time.perf_counter() - t1, active=1,
                                   tokens=0, rid=req.rid)
        self._place(slot, req, last)

    def _admit_offset(self, slot: int, req: Request, offset: int):
        """Warm admission (prefix hit): the slot's leading ``offset`` prompt
        tokens are already resident in shared arena blocks mapped into its
        block-table row, so only the suffix runs — one execution of the
        hot-loaded ``prefill_offset`` program, positions seeded at the
        divergence offset.  The matched tokens cost zero prefill compute;
        that is the near-zero-TTFT path for warm-prefix traffic."""
        self._pin_caches()
        suffix = req.prompt[offset:]
        assert 1 <= len(suffix) <= self.prefix_suffix, \
            (req.rid, offset, req.prompt_len)
        tokens = np.zeros((1, self.prefix_suffix), np.int32)
        tokens[0, :len(suffix)] = suffix
        t1 = time.perf_counter()
        with _span("engine.dispatch.prefill_offset", rid=req.rid,
                   prompt_len=req.prompt_len):
            self.caches, last = self._prefill_offset(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(slot, jnp.int32), jnp.asarray(offset, jnp.int32),
                jnp.asarray(req.prompt_len, jnp.int32))
        with _span("engine.wait.prefill_offset", rid=req.rid,
                   prompt_len=req.prompt_len):
            last = np.asarray(last)        # blocks on the device result
        if self.trace is not None:
            self.trace.on_dispatch("prefill_offset",
                                   time.perf_counter() - t1, active=1,
                                   tokens=0, rid=req.rid)
        self._place(slot, req, last)

    def _admit_burst(self, reqs: List[Request]):
        """Cold-start burst: admit every request in ONE execution of the
        whole-batch ``prefill`` program (engine must be idle — the program
        rewrites all rows; unused rows get a dummy length-1 prompt)."""
        self._pin_caches()
        tokens = np.zeros((self.batch, self.prefill_len), np.int32)
        lengths = np.ones((self.batch,), np.int32)
        for i, req in enumerate(reqs):
            tokens[i, :req.prompt_len] = req.prompt
            lengths[i] = req.prompt_len
        t1 = time.perf_counter()
        with _span("engine.dispatch.prefill", active=len(reqs)):
            self.caches, last = self._prefill(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(lengths))
        with _span("engine.wait.prefill", active=len(reqs)):
            last = np.asarray(last)
        if self.trace is not None:
            self.trace.on_dispatch("prefill", time.perf_counter() - t1,
                                   active=len(reqs), tokens=0)
        for i, req in enumerate(reqs):
            self._place(i, req, last[i])

    def _admit(self):
        """Refill free slots from the queue, earliest arrival first."""
        with _span("engine.admit"):
            t = self.now()
            if self.paged:
                self._admit_paged(t)
                return
            eligible = sum(1 for r in self.queue if r.arrival_time <= t)
            if (self.group_prefill and eligible >= 2
                    and not any(s is not None for s in self.slots)):
                burst = [self.queue.pop(0)
                         for _ in range(min(eligible, self.batch))]
                self._admit_burst(burst)
                return
            for i, s in enumerate(self.slots):
                if s is not None:
                    continue
                if not self.queue or self.queue[0].arrival_time > t:
                    break
                self._admit_one(i, self.queue.pop(0))

    # -- paged admission / preemption -----------------------------------------
    def _blocks_needed(self, prompt_len: int, max_new: int) -> int:
        return -(-(prompt_len + max_new) // self.kv_block)

    def _admit_paged(self, t: float):
        """FIFO admission under memory pressure: the queue head admits only
        when its block reservation can be made resident without touching a
        pinned (actively decoding) page; otherwise it waits — optionally
        rotating out slots that have used up their timeslice first."""
        for i, s in enumerate(self.slots):
            if s is not None:
                continue
            if not self.queue or self.queue[0].arrival_time > t:
                break
            req = self.queue[0]
            n_blocks = self._blocks_needed(req.prompt_len, req.max_new)
            shared = []
            if self.prefix_cfg is not None and not req.needs_resume:
                with _span("engine.pager", rid=req.rid):
                    shared = self.pager.match_prefix(req.prompt)
            with _span("engine.pager", rid=req.rid):
                fits = self.pager.can_admit(req.rid, n_blocks, shared=shared)
            if not fits:
                if self.timeslice is not None:
                    self._preempt_expired()
                with _span("engine.pager", rid=req.rid):
                    fits = self.pager.can_admit(req.rid, n_blocks,
                                                shared=shared)
                if not fits:
                    break
            # remove by identity: _preempt_expired may have re-queued a
            # victim AHEAD of the peeked head (same arrival time, smaller
            # rid), so pop(0) could discard the victim and leave ``req``
            # queued for a second, state-corrupting admission
            for qi, r in enumerate(self.queue):
                if r is req:
                    del self.queue[qi]
                    break
            if req.needs_resume:
                self._resume_one(i, req)
            else:
                with _span("engine.pager", rid=req.rid):
                    self.caches = self.pager.admit(req.rid, n_blocks, i,
                                                   self.caches, shared=shared)
                matched = len(shared) * self.kv_block
                warm = (shared and self._prefix_tier1
                        and len(shared) >= self.prefix_cfg.min_blocks
                        and req.prompt_len - matched <= self.prefix_suffix)
                if warm:
                    self._admit_offset(i, req, matched)
                else:
                    self._admit_one(i, req)
                if shared:
                    self.prefix_admissions += 1
                    self.warm_admissions += bool(warm)
                    self.prefix_tokens_reused += matched
                    self.syscore.hostcalls.dispatch(
                        CALL_METRIC, METRIC_PREFIX_HIT, float(matched))
                # publish only FULL-prefill blocks into the trie: the cold
                # path's bytes are the canonical ones every consumer (warm
                # or tier-2) must reproduce, so warm admissions bump refs
                # but never contribute scan-computed bytes.  Skipped when
                # the request already finished inside _admit_one (its
                # blocks went back to the free list with it).
                if self.prefix_cfg is not None and not warm \
                        and req.rid in self.pager.pages:
                    with _span("engine.pager", rid=req.rid):
                        self.caches = self.pager.publish(req.rid, req.prompt,
                                                         i, self.caches)

    def _resume_one(self, slot: int, req: Request):
        """Swap a preempted request back into a slot: the pager restores
        its blocks (a hit if still resident, a page fault if they were
        written back to host) and its recurrent rows; decode then resumes
        from the exact position it left off, so the token stream is
        unchanged by the round trip."""
        with _span("engine.pager", rid=req.rid):
            self.caches = self.pager.resume(req.rid, slot, self.caches)
        self.caches["pos"] = self.caches["pos"].at[slot].set(
            req.prompt_len + len(req.generated) - 1)
        req.slot = slot
        req.needs_resume = False
        req.gen_at_admit = len(req.generated)
        self.slots[slot] = req
        self.swap_ins += 1

    def preempt(self, req: Request, requeue_at: Optional[float] = None):
        """Swap an active request out of its slot and back into the queue.
        Its recurrent rows copy to host eagerly (the slot is reused); its
        KV blocks stay arena-resident, unpinned, until LRU pressure writes
        them back — a prompt resume costs nothing.  ``requeue_at`` moves
        the request behind current waiters (round-robin rotation); the
        default keeps its original arrival time (resume ASAP)."""
        assert self.paged and req.slot >= 0 and not req.done
        with _span("engine.pager", rid=req.rid):
            self.caches = self.pager.preempt(req.rid, req.slot, self.caches)
        self.slots[req.slot] = None
        req.slot = -1
        req.needs_resume = True
        if requeue_at is not None:
            req.arrival_time = requeue_at
        bisect.insort(self.queue, req,
                      key=lambda r: (r.arrival_time, r.rid))
        self.preemptions += 1

    def _preempt_expired(self):
        for req in list(self.slots):
            if req is not None and \
                    len(req.generated) - req.gen_at_admit >= self.timeslice:
                self.preempt(req, requeue_at=self.now())

    def _maybe_finish(self, req: Request):
        hit_eos = self.eos_id is not None and req.generated and \
            req.generated[-1] == self.eos_id
        full = req.prompt_len + len(req.generated) >= self.max_len
        if len(req.generated) >= req.max_new or hit_eos or full:
            req.done = True
            req.t_done = time.perf_counter()
            self._proposers.pop(req.rid, None)
            self.completed.append(req)
            if self.trace is not None:
                self.trace.on_done(req)
            if self.paged and req.rid in self.pager.pages:
                # idle-slot swap-out's terminal case: the request is done,
                # so its blocks free instead of swapping.  This must run
                # even for a request finishing while PREEMPTED (slot == -1,
                # page unpinned, possibly already written back to host):
                # release() handles that case without touching any live
                # block-table row, freeing resident blocks exactly once and
                # dropping the host-tier kvpage: entries
                with _span("engine.pager", rid=req.rid):
                    self.caches = self.pager.release(req.rid, req.slot,
                                                     self.caches)
            if req.slot >= 0:
                self.slots[req.slot] = None

    def _step_metrics(self, dt: float, occupancy: float, extra=(),
                      program: str = "decode", active: int = 0,
                      tokens: int = 0, trace_extra=None):
        """ONE aggregated hostcall round trip per engine step (CALL_BATCH)
        carrying what used to be 4-5 separate dispatches: decode latency,
        occupancy, optional gauges and the step report — stamped with the
        monotonic host clock so a recorded trace replays with real
        inter-dispatch gaps."""
        with _span("engine.telemetry"):
            calls = [(CALL_METRIC, METRIC_DECODE_MS, 1e3 * dt),
                     (CALL_METRIC, METRIC_OCCUPANCY, occupancy)]
            calls.extend(extra)
            if self.paged:
                calls.append((CALL_METRIC, METRIC_ARENA_OCCUPANCY,
                              self.pager.arena_occupancy()))
            calls.append((CALL_STEP_REPORT, self.decode_steps, dt,
                          time.perf_counter()))
            self.syscore.hostcalls.dispatch(CALL_BATCH, calls)
            if self.trace is not None:
                self.trace.on_dispatch(program, dt, active=active,
                                       tokens=tokens, **(trace_extra or {}))

    def _decode_once(self):
        self._pin_caches()
        tokens = np.zeros((self.batch, 1), np.int32)
        for i, req in enumerate(self.slots):
            if req is not None:
                tokens[i, 0] = req.generated[-1]
        active = sum(s is not None for s in self.slots)
        t1 = time.perf_counter()
        with _span("engine.dispatch.decode", active=active):
            self.caches, next_tok, _ = self._decode(
                self.params, self.caches, jnp.asarray(tokens))
        with _span("engine.wait.decode", active=active):
            nt = np.asarray(next_tok)       # blocks on the device result
        dt = time.perf_counter() - t1
        self.decode_steps += 1
        self.decode_tokens += active
        self._step_metrics(dt, active / self.batch, program="decode",
                           active=active, tokens=active)
        with _span("engine.emit"):
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                req.generated.append(int(nt[i, 0]))
                if self.spec_k is not None and req.rid in self._proposers:
                    self._proposers[req.rid].observe(req.generated[-1:])
                self._maybe_finish(req)
        return dt

    def _verify_once(self):
        """One speculative iteration: propose up to ``spec_k`` drafts per
        active slot (prompt lookup over that request's own history), score
        them ALL in one execution of the hot-loaded ``verify`` program,
        and accept each row's longest greedy-matching prefix.  Rows whose
        proposer has nothing to offer are padded with their last token —
        the verify math keeps them exact either way (an accepted token is
        always the model's own greedy token).  Falls back to the plain
        ``decode`` program — or a fused decode horizon, when one is
        loaded — when no slot has a proposal at all."""
        k = self.spec_k
        tokens = np.zeros((self.batch, k + 1), np.int32)
        n_props = np.zeros((self.batch,), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tokens[i, :] = req.generated[-1]
            props = self._proposers[req.rid].propose(k)
            n_props[i] = len(props)
            tokens[i, 1:1 + len(props)] = props
        drafted = int(n_props.sum())
        if drafted == 0:
            self._advance_decode()
            return
        active = sum(s is not None for s in self.slots)
        if self.paged:
            # speculative block over-allocation: map enough blocks that
            # draft writes past the base reservation land somewhere real
            # (best-effort, from the free list; a failed grow just drops
            # the overshoot writes)
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                pos0 = req.prompt_len + len(req.generated) - 1
                need = min(-(-(pos0 + k + 1) // self.kv_block),
                           self.blocks_per_slot)
                with _span("engine.pager", rid=req.rid):
                    self.caches = self.pager.grow(req.rid, need, i,
                                                  self.caches)
        self._pin_caches()
        t1 = time.perf_counter()
        with _span("engine.dispatch.verify", active=active):
            self.caches, ys, n_new = self._verify(
                self.params, self.caches, jnp.asarray(tokens))
        with _span("engine.wait.verify", active=active):
            ys = np.asarray(ys)
            n_new = np.asarray(n_new)      # blocks on the device result
        dt = time.perf_counter() - t1
        self.decode_steps += 1
        self.spec_steps += 1
        accepted = 0
        toks0 = self.decode_tokens
        with _span("engine.emit"):
            for i, req in enumerate(list(self.slots)):
                if req is None:
                    continue
                used = 0
                for j in range(int(n_new[i])):
                    if req.done:
                        break              # EOS / budget hit mid-accept
                    req.generated.append(int(ys[i, j]))
                    used += 1
                    self._maybe_finish(req)
                self.decode_tokens += used
                accepted += min(used - 1, int(n_props[i]))
                if req.rid in self._proposers:
                    self._proposers[req.rid].observe(req.generated[-used:])
                if (self.paged and req.rid in self.pager.pages
                        and req.slot >= 0):
                    # reclaim on rejection: speculative tail blocks go back
                    # to the free list (verify restored their bytes
                    # in-program)
                    with _span("engine.pager", rid=req.rid):
                        self.caches = self.pager.trim_to_base(req.rid, i,
                                                              self.caches)
        self.draft_tokens += drafted
        self.accepted_drafts += accepted
        self._step_metrics(dt, active / self.batch,
                           extra=[(CALL_METRIC, METRIC_SPEC_ACCEPT,
                                   accepted / drafted)],
                           program="verify", active=active,
                           tokens=self.decode_tokens - toks0,
                           trace_extra={"drafted": drafted,
                                        "accepted": accepted})

    # -- fused decode horizons ------------------------------------------------
    def _budget_left(self, req: Request) -> int:
        """Tokens ``req`` may still emit (max_new and cache-length caps)."""
        return min(req.max_new,
                   self.max_len - req.prompt_len) - len(req.generated)

    def _use_horizon(self) -> bool:
        """Adaptive horizon policy: fuse only when it cannot hurt latency.

        With an eligible request waiting in the queue, a slot that frees
        mid-horizon would leave the waiter stuck behind the fused dispatch
        (TTFT regression), so the engine shrinks to single-step decode —
        UNLESS admission is provably impossible for the whole horizon:
        every slot holds a request that cannot finish inside it, which is
        predictable exactly when finishes come only from budget exhaustion
        (no EOS) and no timeslice preemption can rotate a slot out.  A
        saturated engine with a backed-up queue therefore still fuses —
        the regime fusion targets most.

        Fusing also needs some row able to amortize a meaningful part of
        the scan: a short tail (every remaining budget < H/2) is cheaper
        as single steps than as one dispatch whose scan runs mostly
        frozen."""
        if self._decode_horizon is None:
            return False
        if self.queue and self.queue[0].arrival_time <= self.now():
            if self.eos_id is not None or self.timeslice is not None:
                return False
            if not all(s is not None and self._budget_left(s) > self.horizon
                       for s in self.slots):
                return False
        return any(s is not None and
                   self._budget_left(s) >= max(2, self.horizon // 2)
                   for s in self.slots)

    def _advance_decode(self):
        """One decode-path advance: a fused horizon when the adaptive
        policy allows it, else the classic single-token dispatch."""
        if self._use_horizon():
            self._decode_horizon_once()
        else:
            self._decode_once()

    def _decode_horizon_once(self):
        """One fused horizon: up to ``self.horizon`` decode iterations in a
        single program dispatch.  The host crosses the boundary once — the
        event buffer (emitted tokens, per-slot finish steps, occupancy)
        comes back as arrays, and ALL bookkeeping (generated-token append,
        EOS/budget finishes, paged block release, proposer feed, metrics)
        happens here, at the horizon boundary."""
        self._pin_caches()
        tokens = np.zeros((self.batch, 1), np.int32)
        budget = np.zeros((self.batch,), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tokens[i, 0] = req.generated[-1]
            budget[i] = min(self._budget_left(req), self.horizon)
        active = sum(s is not None for s in self.slots)
        t1 = time.perf_counter()
        with _span("engine.dispatch.decode_horizon", active=active):
            self.caches, events = self._decode_horizon(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(budget))
        with _span("engine.wait.decode_horizon", active=active):
            toks = np.asarray(events["tokens"])  # blocks on the device result
            n_emit = np.asarray(events["n_emitted"])
            occ = np.asarray(events["occupancy"])
        dt = time.perf_counter() - t1
        emitted = int(n_emit.sum())
        self.decode_steps += 1
        self.horizon_steps += 1
        self.decode_tokens += emitted
        self.horizon_tokens += emitted
        with _span("engine.emit"):
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                new = [int(t) for t in toks[i, :n_emit[i]]]
                req.generated.extend(new)
                if new and self.spec_k is not None and \
                        req.rid in self._proposers:
                    self._proposers[req.rid].observe(new)
                self._maybe_finish(req)
        # one METRIC_OCCUPANCY entry per *executed* in-graph step (steps
        # after every row froze are skipped), so the channel keeps its
        # per-decode-step weighting: a horizon covering 15 tokens
        # contributes 15 entries, exactly like 15 single-step dispatches
        # would — run()'s occupancy mean stays token-step-weighted when
        # fused and single-step phases mix
        ran = [float(o) for o in occ if o > 0]
        extra = [(CALL_METRIC, METRIC_OCCUPANCY, o) for o in ran[1:]]
        extra.append((CALL_METRIC, METRIC_HORIZON_TOKENS, float(emitted)))
        self._step_metrics(dt, ran[0] if ran else 0.0, extra=extra,
                           program="decode_horizon", active=active,
                           tokens=emitted)
        return dt

    @property
    def has_work(self) -> bool:
        """True while any request is queued or occupies a slot."""
        return bool(self.queue) or any(s is not None for s in self.slots)

    def begin_drain(self):
        """Enter drain mode: every later :meth:`submit` is refused (the
        caller routes elsewhere) while already-accepted work — queued and
        in-flight — runs to completion.  The quiesce half of an elastic
        shrink: the supervisor stops routing here, waits for
        ``has_work`` to clear, then retires the replica."""
        self.draining = True

    def withdraw(self, rid: int) -> Optional[Request]:
        """Remove and return a QUEUED request by id, or ``None`` if ``rid``
        is not withdrawable: already in a slot, preempted (its KV lives in
        the pager — moving it would orphan the blocks), or unknown.  Used
        by elastic rebalancing to move never-started requests onto a
        freshly spawned replica; a withdrawn request holds no engine
        state, so resubmitting its prompt elsewhere is exact."""
        for qi, r in enumerate(self.queue):
            if r.rid == rid and not r.needs_resume:
                return self.queue.pop(qi)
        return None

    def tick(self) -> bool:
        """One SUPERVISED engine iteration — the step-level API a cluster
        supervisor drives instead of ``run()``'s closed loop.

        The injectable fault hook fires first (a
        ``repro.runtime.fault.FaultInjector.check`` raising
        SimulatedFailure models this replica crashing mid-serving; the
        supervisor catches it, discards the engine and warm-reboots a
        replacement), then one :meth:`step` runs.  Returns ``step()``'s
        value: False when no work remains."""
        if self.fault_hook is not None:
            self.fault_hook(self.steps)
        return self.step()

    def snapshot(self) -> Dict[str, object]:
        """Cheap point-in-time load view for a router/supervisor — host
        bookkeeping only, no device sync.

        ``inflight_rids`` is every request this engine currently owes an
        answer for (queued or in a slot); a supervisor diffs it against
        its journal to know what a crash would lose."""
        active = [s for s in self.slots if s is not None]
        snap: Dict[str, object] = {
            "steps": self.steps,
            "batch": self.batch,
            "active": len(active),
            "queue_depth": len(self.queue),
            "max_queue": self.max_queue,
            "inflight_rids": sorted([r.rid for r in active] +
                                    [r.rid for r in self.queue]),
            "completed": len(self.completed),
            "draining": self.draining,
            "arena_occupancy": (self.pager.arena_occupancy()
                                if self.paged else 0.0),
        }
        return snap

    def step(self) -> bool:
        """One engine iteration: admit into free slots, then one decode
        advance — a fused horizon, a speculative verify or a single decode
        step — for every active slot.  Returns False when no work
        remains."""
        if not self.has_work:
            return False
        with _span("engine.step"):
            self._admit()
            if any(s is not None for s in self.slots):
                if self.spec_k is not None:
                    self._verify_once()
                else:
                    self._advance_decode()
            elif self.clock == "wall" and self.queue:
                # idle: sleep toward the earliest future arrival (capped so
                # a far-future request costs O(wait/10ms) engine ticks, not
                # a 10 kHz busy-poll that drains run()'s step budget)
                wait = self.queue[0].arrival_time - self.now()
                time.sleep(min(max(wait, 1e-4), 1e-2))
            self.steps += 1
        return True

    def run(self, max_steps: int = 10_000) -> Dict[str, float]:
        """Serve until the queue and slots drain (or ``max_steps`` engine
        iterations pass).  The engine is reusable: all counters and metric
        windows are relative to this call, so a second run() (or the
        memoized reference engine) gets a fresh budget and fresh stats."""
        metrics = self.syscore.hostcalls.metrics
        start_steps, done0 = self.steps, len(self.completed)
        # window offsets are snapshotted PER CHANNEL: a fused horizon
        # appends to some channels once per dispatch and to others once per
        # engine step, so one shared offset would misalign the slices
        n_dec0 = len(metrics.get(METRIC_DECODE_MS, []))
        n_ttft0 = len(metrics.get(METRIC_TTFT_MS, []))
        n_occ0 = len(metrics.get(METRIC_OCCUPANCY, []))
        n_arena0 = len(metrics.get(METRIC_ARENA_OCCUPANCY, []))
        dec_steps0, dec_toks0 = self.decode_steps, self.decode_tokens
        hor0, hor_toks0 = self.horizon_steps, self.horizon_tokens
        adm0, ref0 = self.admitted, self.refill_admissions
        pre0, swi0 = self.preemptions, self.swap_ins
        spec0, drf0, acc0 = (self.spec_steps, self.draft_tokens,
                             self.accepted_drafts)
        pf0 = self.pager.page_faults if self.paged else 0
        swo0 = self.pager.swap_outs if self.paged else 0
        pa0, wa0 = self.prefix_admissions, self.warm_admissions
        ptr0 = self.prefix_tokens_reused
        t0 = time.perf_counter()
        while self.steps - start_steps < max_steps and self.step():
            pass
        wall = time.perf_counter() - t0
        completed = self.completed[done0:]
        toks = sum(len(r.generated) for r in completed)
        decode_ms = sorted(metrics.get(METRIC_DECODE_MS, [])[n_dec0:])
        ttft_ms = metrics.get(METRIC_TTFT_MS, [])[n_ttft0:]
        occ = metrics.get(METRIC_OCCUPANCY, [])[n_occ0:]
        dec_toks = self.decode_tokens - dec_toks0
        stats = {
            "requests": len(completed),
            "tokens": toks,
            "wall_s": wall,
            "tok_per_s": toks / wall if wall else 0.0,
            # latency stats are explicit None when this window placed or
            # decoded nothing (e.g. every submitted request was killed
            # before admission) — never a garbage mean over no samples
            "decode_p50_ms": (decode_ms[len(decode_ms) // 2]
                              if decode_ms else None),
            "ttft_ms": (sum(ttft_ms) / len(ttft_ms) if ttft_ms else None),
            "occupancy": sum(occ) / max(len(occ), 1),
            "decode_steps": self.decode_steps - dec_steps0,
            "decode_tokens": dec_toks,
            # host decode-path dispatches per generated token — the number
            # the fused horizon drives toward 1/H (paper Table 1 applied
            # to the generation loop)
            "dispatches_per_token": (self.decode_steps - dec_steps0)
                                    / max(dec_toks, 1),
            "admitted": self.admitted - adm0,
            # rejection happens at submit() time, outside any run() window,
            # so it stays an engine-lifetime count
            "rejected": self.rejected,
            "refill_admissions": self.refill_admissions - ref0,
        }
        if self._decode_horizon is not None:
            stats.update({
                "horizon_steps": self.horizon_steps - hor0,
                "horizon_tokens": self.horizon_tokens - hor_toks0,
            })
        if self.spec_k is not None:
            drafted = self.draft_tokens - drf0
            accepted = self.accepted_drafts - acc0
            stats.update({
                "spec_steps": self.spec_steps - spec0,
                "draft_tokens": drafted,
                "accepted_drafts": accepted,
                "accept_rate": accepted / max(drafted, 1),
            })
        if self.paged:
            arena = metrics.get(METRIC_ARENA_OCCUPANCY, [])[n_arena0:]
            stats.update({
                "preemptions": self.preemptions - pre0,
                "swap_ins": self.swap_ins - swi0,
                "page_faults": self.pager.page_faults - pf0,
                "swap_outs": self.pager.swap_outs - swo0,
                "arena_occupancy": sum(arena) / max(len(arena), 1),
            })
        if self.prefix_cfg is not None:
            stats.update({
                "prefix_admissions": self.prefix_admissions - pa0,
                "warm_admissions": self.warm_admissions - wa0,
                "prefix_tokens_reused": self.prefix_tokens_reused - ptr0,
            })
        return stats

    def drain_completed(self) -> List[Request]:
        """Hand finished requests to the caller and release engine-side
        history.  A long-lived resident engine otherwise grows
        ``completed`` and the hostcall metric channels linearly with served
        traffic; draining between run() calls bounds both.

        Channel trimming delegates to ``HostCallTable.drain_metrics``: one
        pass over the live channels, each list swapped for a fresh empty
        one — O(requests served since the last drain), never a rescan of
        total lifetime history, and with no hand-maintained code list to
        go stale as engine metric codes are added (the fused-horizon code
        9 is covered automatically).  Only the program-lifecycle channels
        (compile/load telemetry, codes 4/5) are kept: they describe the
        resident programs, not served traffic."""
        done, self.completed = self.completed, []
        hc = self.syscore.hostcalls
        hc.drain_metrics(keep=(METRIC_PROGRAM_COMPILE_MS,
                               METRIC_PROGRAM_LOAD_MS))
        hc.step_times.clear()
        hc.step_stamps.clear()
        return done

    # -- reference path -------------------------------------------------------
    def reference_generate(self, prompt: np.ndarray, max_new: int) -> List[int]:
        """Batch-of-1 greedy decode of ``prompt`` with this engine's params —
        the oracle each slot's output must match token for token.  The
        reference engine is built (compiled) once and re-used: admission
        rewrites its single slot's state completely, which is itself a v2
        invariant this oracle relies on."""
        ref = getattr(self, "_ref_engine", None)
        if ref is None:
            ref_config = self.config.replace(
                batch=1, prefill_len=self.prefill_len, clock="step",
                paging=None, prefix=None, spec=None, horizon=None,
                shard=ShardConfig(), group_prefill=False, store_dir=None)
            params = self.params
            if self.mesh is not None:
                # the oracle runs mesh-less single-device programs: gather
                # the sharded tree back to plain host-backed arrays first
                params = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)),
                                      self.params)
            ref = self._ref_engine = ServingEngine(
                self.arch, ref_config, params=params,
                store=self.syscore.store)
        req = ref.submit(prompt, max_new)
        ref.run()
        ref.drain_completed()   # keep the memoized oracle's history bounded
        return req.generated


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the architecture at its published widths "
                         "(default: the reduced preset)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=EngineConfig.max_len,
                    help="per-slot cache length (prompts keep their last "
                         "max_len // 2 tokens)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--store-dir", default=None,
                    help="persistent program store; a second run with the "
                         "same dir boots by deserialization, not compile")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache arena (repro.core.paging)")
    ap.add_argument("--kv-block", type=int, default=8)
    ap.add_argument("--arena-blocks", type=int, default=None,
                    help="device-resident KV blocks; below "
                         "batch*max_len/kv_block creates memory pressure")
    ap.add_argument("--prefix", action="store_true",
                    help="cross-request prefix sharing over the paged "
                         "arena (requires --paged)")
    ap.add_argument("--prefix-max-suffix", type=int, default=None,
                    help="warm-path suffix capacity; None = 2*kv_block")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative decoding: drafts per verify step "
                         "(n-gram prompt lookup); None = plain decode")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="suffix n-gram length the proposer matches on")
    ap.add_argument("--horizon", type=int, default=None,
                    help="fused decode horizon: run up to H decode "
                         "iterations per dispatch (None/1 = per-token)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel devices (ShardConfig.n_devices); "
                         "programs compile against a 1-D 'model' mesh")
    args = ap.parse_args()
    config = EngineConfig(
        reduced=args.reduced, batch=args.batch, max_len=args.max_len,
        store_dir=args.store_dir,
        paging=(PagingConfig(kv_block=args.kv_block,
                             arena_blocks=args.arena_blocks)
                if args.paged else None),
        prefix=(PrefixConfig(max_suffix=args.prefix_max_suffix)
                if args.prefix else None),
        spec=(SpecConfig(k=args.spec_k, ngram=args.spec_ngram)
              if args.spec_k is not None else None),
        horizon=(HorizonConfig(length=args.horizon)
                 if args.horizon is not None and args.horizon >= 2
                 else None),
        shard=ShardConfig(n_devices=args.tp))
    eng = ServingEngine(args.arch, config)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, size=8), args.max_new)
    print(eng.run())
    print(eng.syscore.report()["programs"])
    if args.paged:
        print(eng.pager.report())


if __name__ == "__main__":
    main()
