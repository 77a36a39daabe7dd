"""Paged KV-cache arena tests (ISSUE 3 tentpole).

Three layers of coverage:

  * ``PagedKVManager`` block accounting: admit/preempt/evict/resume move
    blocks between the device arena and the host tier without losing a
    byte, and the free list stays congruent with the DC table's byte
    capacity;
  * the paged ``ServingEngine``: admission defers under arena pressure,
    preemption (cooperative and timeslice round-robin) swaps requests out
    and back in, and every request's token stream is EXACTLY what the
    unpaged batch-of-1 reference produces — across attention, windowed
    and recurrent families;
  * the system path: a warm boot from the program store into a paged
    serving run whose total KV footprint exceeds the arena.
"""
import contextlib
import functools
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import PagedKVManager, ProgramStore
from repro.kernels import ops
from repro.launch.serve import (METRIC_ARENA_OCCUPANCY, METRIC_PAGE_FAULT,
                                ServingEngine)
from repro.models import attention, registry, transformer
from repro.sharding import make_rules

TESTS = Path(__file__).resolve().parent


def run_on_devices(n: int, code: str) -> dict:
    """Run ``code`` in a subprocess that sees ``n`` CPU devices (and this
    directory on its path); its last line of output is JSON."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=os.pathsep.join([str(TESTS.parent / "src"),
                                           str(TESTS)]))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# manager-level block accounting
# ---------------------------------------------------------------------------
def _toy_caches(batch=2, n_phys=4, n_blocks=4, bs=2):
    """Minimal cache pytree with the real layout: group-stacked arena
    leaves (layers axis first), a tail arena leaf, and per-slot recurrent
    state leaves."""
    return {
        "pos": jnp.zeros((batch,), jnp.int32),
        "block_table": jnp.full((batch, n_blocks), -1, jnp.int32),
        "groups": {"slot0": {"k": jnp.zeros((3, n_phys, bs, 1, 2)),
                             "v": jnp.zeros((3, n_phys, bs, 1, 2))},
                   "slot1": {"state": jnp.zeros((3, batch, 5))}},
        "tail": {"tail0": {"k": jnp.zeros((n_phys, bs, 1, 2)),
                           "v": jnp.zeros((n_phys, bs, 1, 2))},
                 "tail1": {"conv": jnp.zeros((batch, 3))}},
    }


def test_pager_swap_roundtrip_preserves_blocks_and_state():
    """admit -> write -> preempt -> evict (via a competing admit) ->
    resume must reproduce the request's KV blocks and recurrent rows
    bit-exactly, through the host tier."""
    block_bytes = 128          # 2 arena leaf-pairs: (3*2*1*2 + 2*1*2) * 2 * 4
    mgr = PagedKVManager(4, block_bytes)
    caches = _toy_caches()

    caches = mgr.admit(rid=0, n_blocks=2, slot=0, caches=caches)
    row0 = np.asarray(caches["block_table"][0])
    phys0 = [b for b in row0 if b >= 0]
    assert len(phys0) == 2 and row0[2] == -1

    # simulate decode/prefill writes into rid 0's blocks + slot 0's state
    rng = np.random.default_rng(0)
    gk = jnp.asarray(rng.standard_normal((3, 2, 2, 1, 2)), jnp.float32)
    tk = jnp.asarray(rng.standard_normal((2, 2, 1, 2)), jnp.float32)
    st = jnp.asarray(rng.standard_normal((3, 5)), jnp.float32)
    caches["groups"]["slot0"]["k"] = \
        caches["groups"]["slot0"]["k"].at[:, jnp.asarray(phys0)].set(gk)
    caches["tail"]["tail0"]["k"] = \
        caches["tail"]["tail0"]["k"].at[jnp.asarray(phys0)].set(tk)
    caches["groups"]["slot1"]["state"] = \
        caches["groups"]["slot1"]["state"].at[:, 0].set(st)

    caches = mgr.preempt(0, 0, caches)
    assert np.all(np.asarray(caches["block_table"][0]) == -1)
    assert mgr.table.is_resident("kv:0")       # lazy: not yet written back

    # a competing admission forces rid 0's eviction (4 blocks - 3 needed)
    caches = mgr.admit(rid=1, n_blocks=3, slot=1, caches=caches)
    assert not mgr.table.is_resident("kv:0")
    assert mgr.swap_outs == 1
    assert len(mgr.free) == 4 - 3

    assert not mgr.can_admit(0, 2)             # rid 1 is pinned: no room
    caches = mgr.release(1, 1, caches)
    assert mgr.can_admit(0, 2)

    caches = mgr.resume(0, slot=0, caches=caches)
    assert mgr.page_faults == 1
    phys1 = [b for b in np.asarray(caches["block_table"][0]) if b >= 0]
    np.testing.assert_array_equal(
        np.asarray(caches["groups"]["slot0"]["k"][:, jnp.asarray(phys1)]), gk)
    np.testing.assert_array_equal(
        np.asarray(caches["tail"]["tail0"]["k"][jnp.asarray(phys1)]), tk)
    np.testing.assert_array_equal(
        np.asarray(caches["groups"]["slot1"]["state"][:, 0]), st)
    assert mgr.table.resident_bytes <= mgr.table.capacity


def test_release_while_preempted_no_double_free_no_host_leak():
    """ISSUE 8 satellite: a request that finishes while PREEMPTED
    (slot == -1) — possibly already evicted to the host tier — must free
    its blocks exactly once, drop its ``kvpage:`` host entries, and touch
    no block-table row (the old code cleared row ``-1``, silently wiping
    the LAST slot's live mapping)."""
    from repro.core.uva import UVARegistry
    uva = UVARegistry()
    mgr = PagedKVManager(4, 128, uva=uva)
    caches = _toy_caches()

    # still-resident (lazily swapped) preempted release: freed exactly once
    caches = mgr.admit(rid=0, n_blocks=1, slot=0, caches=caches)
    caches = mgr.preempt(0, 0, caches)
    caches = mgr.release(0, -1, caches)
    assert sorted(mgr.free) == list(range(4))
    mgr.check_invariants()

    # evicted preempted release: nothing resident to double-free, the
    # kvpage: host entries drop, and no block-table row changes
    caches = mgr.admit(rid=1, n_blocks=2, slot=0, caches=caches)
    caches = mgr.preempt(1, 0, caches)
    caches = mgr.admit(rid=2, n_blocks=3, slot=1, caches=caches)  # evicts 1
    assert mgr.swap_outs == 1
    assert "kvpage:1/0" in uva
    before = np.asarray(caches["block_table"]).copy()
    caches = mgr.release(1, -1, caches)
    np.testing.assert_array_equal(np.asarray(caches["block_table"]), before)
    assert "kvpage:1/0" not in uva
    assert len(mgr.free) == 1
    mgr.check_invariants()
    caches = mgr.release(2, 1, caches)
    assert sorted(mgr.free) == list(range(4))
    mgr.check_invariants()


# ---------------------------------------------------------------------------
# paged serving engine
# ---------------------------------------------------------------------------
def test_paged_engine_under_pressure_is_token_exact_and_reports():
    """Arena holds half the batch's KV footprint; timeslice round-robin
    rotates requests through it.  Everything completes token-exactly and
    the fault/occupancy telemetry flows through the resident hostcall
    table (the ISSUE acceptance criterion)."""
    eng = ServingEngine("qwen3-0.6b", reduced=True, batch=4, max_len=32,
                        clock="step", paged=True, kv_block=8,
                        arena_blocks=8, timeslice=3)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, 500, size=int(rng.integers(4, 12))),
                       max_new=int(rng.integers(4, 9))) for _ in range(8)]
    stats = eng.run()
    assert stats["requests"] == 8
    assert stats["preemptions"] >= 1
    assert stats["swap_outs"] >= 1 and stats["page_faults"] >= 1
    assert 0 < stats["arena_occupancy"] <= 1.0
    for r in reqs:
        ref = eng.reference_generate(r.prompt, r.max_new)
        assert r.generated == ref, (r.rid, r.generated, ref)
    hc = eng.syscore.report()["hostcalls"]["metrics"]
    assert hc[METRIC_PAGE_FAULT]["count"] == stats["page_faults"]
    assert hc[METRIC_ARENA_OCCUPANCY]["count"] == stats["decode_steps"]
    rep = eng.pager.report()
    assert rep["evictions"] == rep["swap_outs"] >= 1
    assert rep["loads"] >= 8


@pytest.mark.parametrize("arch", ["gemma3-4b", "mamba2-130m"])
def test_paged_engine_exactness_other_families(arch):
    """Paged decode through the block-table gather must stay exact for a
    windowed family (full-length, ring-free arena layout) and a recurrent
    family (no KV at all — state rows still swap)."""
    eng = ServingEngine(arch, reduced=True, batch=2, max_len=32,
                        clock="step", paged=True, kv_block=8,
                        arena_blocks=4, timeslice=3)
    rng = np.random.default_rng(2)
    reqs = [eng.submit(rng.integers(1, eng.cfg.vocab_size, size=n), max_new=m)
            for n, m in ((4, 6), (9, 5), (6, 7))]
    eng.run()
    for r in reqs:
        ref = eng.reference_generate(r.prompt, r.max_new)
        assert r.generated == ref, (arch, r.rid, r.generated, ref)


def test_paged_arena_reset_is_lossless():
    """A DC-table reset over the KV arena (the paper's staged-application
    invalidation) must write preempted pages back to host, not discard
    them: the resumed request page-faults its blocks back and stays
    exact."""
    eng = ServingEngine("qwen3-0.6b", reduced=True, batch=2, max_len=32,
                        clock="step", paged=True, kv_block=8, arena_blocks=8)
    r1 = eng.submit(np.arange(1, 7), max_new=8)
    for _ in range(3):
        eng.step()
    eng.preempt(r1)
    eng.caches = eng.pager.reset(eng.caches)       # invalidate the arena
    assert eng.pager.swap_outs == 1                # written back, not lost
    assert len(eng.pager.free) == eng.pager.arena_blocks
    eng.run()
    assert eng.pager.page_faults == 1
    assert r1.generated == eng.reference_generate(r1.prompt, r1.max_new)


def test_paged_cooperative_preempt_resume():
    """An explicitly preempted request resumes exactly; a prompt resume is
    an arena hit (lazy swap-out cost nothing)."""
    eng = ServingEngine("qwen3-0.6b", reduced=True, batch=2, max_len=32,
                        clock="step", paged=True, kv_block=8, arena_blocks=8)
    r1 = eng.submit(np.arange(1, 7), max_new=8)
    r2 = eng.submit(np.arange(3, 8), max_new=6)
    for _ in range(3):
        eng.step()
    eng.preempt(r1)
    assert r1.slot == -1 and r1.needs_resume
    eng.run()
    assert eng.preemptions == 1 and eng.swap_ins == 1
    assert eng.pager.hits >= 1 and eng.pager.page_faults == 0
    for r in (r1, r2):
        assert r.generated == eng.reference_generate(r.prompt, r.max_new)


def test_paged_admission_defers_until_blocks_free():
    """Arena sized for ONE request: concurrency degrades to sequential
    service instead of failing — admission under memory pressure."""
    eng = ServingEngine("qwen3-0.6b", reduced=True, batch=2, max_len=32,
                        clock="step", paged=True, kv_block=8, arena_blocks=2)
    r1 = eng.submit(np.arange(1, 9), max_new=6)    # 14 tokens -> 2 blocks
    r2 = eng.submit(np.arange(2, 10), max_new=6)
    max_active = 0
    while eng.step():
        max_active = max(max_active,
                         sum(s is not None for s in eng.slots))
    assert max_active == 1                         # never co-resident
    for r in (r1, r2):
        assert r.done
        assert r.generated == eng.reference_generate(r.prompt, r.max_new)


def test_paged_victim_requeued_ahead_of_waiter_is_not_lost():
    """Regression: under the step clock a timeslice victim re-queues with
    (arrival_time == now, smaller rid) and sorts AHEAD of the waiting
    head; admission must still remove the waiter it peeked — not blindly
    pop the victim — or the victim is silently dropped and the waiter is
    admitted twice."""
    eng = ServingEngine("qwen3-0.6b", reduced=True, batch=2, max_len=32,
                        clock="step", paged=True, kv_block=8,
                        arena_blocks=2, timeslice=2)
    r1 = eng.submit(np.arange(1, 9), max_new=6, arrival_time=0.0)
    r2 = eng.submit(np.arange(2, 10), max_new=6, arrival_time=3.0)
    stats = eng.run()
    assert stats["requests"] == 2
    assert eng.preemptions >= 1           # the rotation actually happened
    for r in (r1, r2):
        assert r.generated == eng.reference_generate(r.prompt, r.max_new)


def test_paged_rejects_requests_larger_than_arena():
    eng = ServingEngine("qwen3-0.6b", reduced=True, batch=2, max_len=32,
                        clock="step", paged=True, kv_block=8, arena_blocks=1)
    assert eng.submit(np.arange(1, 12), max_new=8) is None   # needs 3 blocks
    assert eng.rejected == 1


# ---------------------------------------------------------------------------
# end-to-end: warm boot from the program store into a paged run
# ---------------------------------------------------------------------------
def test_paged_warm_boot_from_store_token_exact(tmp_path):
    """ISSUE 3 system test: boot the paged engine from a persistent
    ProgramStore (load path, no recompiles) and serve a workload whose
    total KV footprint exceeds the arena — outputs must match both the
    cold paged boot and the unpaged batch-of-1 reference."""
    kw = dict(reduced=True, batch=2, max_len=32, clock="step", paged=True,
              kv_block=8, arena_blocks=4, timeslice=3)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 500, size=n) for n in (5, 9, 6, 8)]

    cold = ServingEngine("qwen3-0.6b", store=ProgramStore(tmp_path), **kw)
    total_blocks = sum(cold._blocks_needed(len(p), 6) for p in prompts)
    assert total_blocks > cold.arena_blocks        # footprint > arena
    cold_reqs = [cold.submit(p, max_new=6) for p in prompts]
    cold.run()
    if cold.syscore.store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")

    warm = ServingEngine("qwen3-0.6b", store=ProgramStore(tmp_path), **kw)
    progs = warm.syscore.report()["programs"]
    for name in ("prefill_slot", "decode"):
        assert progs[name]["source"] == "store", (name, progs[name])
        assert progs[name]["compile_s"] == 0, (name, progs[name])
    warm_reqs = [warm.submit(p, max_new=6) for p in prompts]
    stats = warm.run()
    assert stats["requests"] == len(prompts)
    for c, w, p in zip(cold_reqs, warm_reqs, prompts):
        assert w.generated == c.generated
        assert w.generated == warm.reference_generate(p, 6)


# ---------------------------------------------------------------------------
# the paged decode read: Pallas kernel over live blocks vs the XLA gather
# ---------------------------------------------------------------------------
def _paged_decode_setup(cfg, batch=3, max_len=32, bs=4, arena_blocks=24):
    """A paged cache mid-generation: random arena contents stand for the
    prefilled context; row 0 maps every block through a shuffled table,
    row 1 starts on two read-only shared blocks, row 2 is unmapped."""
    rng = np.random.default_rng(0)
    caches = transformer.init_paged_cache(cfg, batch, max_len, kv_block=bs,
                                          arena_blocks=arena_blocks)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for group in (caches["groups"], caches["tail"]):
        for slot in group.values():
            for name in ("k", "v"):
                slot[name] = jax.random.normal(next(keys), slot[name].shape,
                                               slot[name].dtype)
    phys = rng.permutation(arena_blocks)
    table = np.full((batch, max_len // bs), -1, np.int32)
    table[0] = phys[:8]
    table[1, :2] = -(phys[8:10] + 2)
    table[1, 2:] = phys[10:16]
    caches["block_table"] = jnp.asarray(table)
    caches["pos"] = jnp.asarray([3, 9, 0], jnp.int32)
    tokens = jnp.asarray([[5], [17], [0]], jnp.int32)
    return caches, tokens


def _decode_streams(cfg, params, impl, monkeypatch, steps=16):
    """Greedy tokens and logits of ``steps`` paged ``decode_step`` calls,
    and the tokens of one ``decode_horizon`` over the same steps, with the
    paged read that ``impl`` selects."""
    monkeypatch.setattr(ops, "default_impl", lambda: impl)
    rules = make_rules()
    caches, tok = _paged_decode_setup(cfg)
    step = jax.jit(functools.partial(transformer.decode_step, cfg,
                                     rules=rules))
    horizon = jax.jit(functools.partial(transformer.decode_horizon, cfg,
                                        rules=rules, horizon=steps))
    _, events = horizon(params, caches, tok,
                        jnp.asarray([steps, steps, 0], jnp.int32))
    toks, logits = [], []
    for _ in range(steps):
        out, caches = step(params, caches, tok)
        tok = transformer.greedy_token(cfg, out)
        toks.append(np.asarray(tok[:2, 0]))
        logits.append(np.asarray(out[:2, 0]))
    return (np.stack(toks, 1), np.stack(logits, 1),
            np.asarray(events["tokens"][:2]))


def test_paged_kernel_decode_matches_xla_path(monkeypatch):
    """Paged ``decode_step`` and ``decode_horizon`` over 16 steps that
    cross block boundaries give the same greedy tokens with the Pallas
    kernel (interpreted) as with the XLA gather, and logits within
    tolerance; the horizon equals the single steps on both paths."""
    cfg = registry.get_config("qwen3-0.6b", reduced=True)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    k_toks, k_logits, k_horizon = _decode_streams(cfg, params, "interpret",
                                                  monkeypatch)
    x_toks, x_logits, x_horizon = _decode_streams(cfg, params, "xla",
                                                  monkeypatch)
    np.testing.assert_array_equal(k_toks, x_toks)
    np.testing.assert_allclose(k_logits, x_logits, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(k_horizon, k_toks)
    np.testing.assert_array_equal(x_horizon, x_toks)


def test_paged_kernel_horizon_with_tail_layer_matches_dense(monkeypatch):
    """A model of two scanned groups of two layers and one unscanned tail
    layer, whose arenas the layer scan carries as stacks (the tail's as a
    stack of one): ``decode_horizon`` and single paged steps give the same
    greedy tokens with the kernel (interpreted) as with the XLA read, and
    both give the tokens and logits of the dense (unpaged) decode over the
    same context, which has no layer index to get wrong."""
    cfg = registry.get_config("qwen3-0.6b", reduced=True).replace(
        n_layers=5, layer_pattern=("G", "G"))
    assert transformer.split_layers(cfg)[1:] == (2, ("G",))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    streams = {impl: _decode_streams(cfg, params, impl, monkeypatch,
                                     steps=8)
               for impl in ("interpret", "xla")}

    # the same context as dense per-slot caches: each layer's arena read
    # out through the block table
    caches, tok = _paged_decode_setup(cfg)
    table = caches.pop("block_table")

    def dense(stack):
        return jnp.stack([attention.gather_paged_kv(stack, layer, table)
                          for layer in range(len(stack))])

    caches["groups"] = jax.tree.map(dense, caches["groups"])
    caches["tail"] = jax.tree.map(lambda a: dense(a[None])[0],
                                  caches["tail"])
    step = jax.jit(functools.partial(transformer.decode_step, cfg,
                                     rules=make_rules()))
    toks, logits = [], []
    for _ in range(8):
        out, caches = step(params, caches, tok)
        tok = transformer.greedy_token(cfg, out)
        toks.append(np.asarray(tok[:2, 0]))
        logits.append(np.asarray(out[:2, 0]))
    toks, logits = np.stack(toks, 1), np.stack(logits, 1)

    for impl, (p_toks, p_logits, p_horizon) in streams.items():
        np.testing.assert_array_equal(p_toks, toks, err_msg=impl)
        np.testing.assert_array_equal(p_horizon, toks, err_msg=impl)
        np.testing.assert_allclose(p_logits, logits, rtol=1e-4, atol=1e-4,
                                   err_msg=impl)


@pytest.mark.parametrize("case", ["kernel", "window", "sharded_kv_heads",
                                  "head_dim_layout"])
def test_paged_kernel_selection(monkeypatch, case):
    """The paged decode program holds the kernel where the layer attends
    its whole context and its KV heads are unsharded, or split evenly over
    a mesh (then once per shard, under ``shard_map``); a local-window layer
    and KV heads that do not divide the mesh (the head_dim layout) keep
    the XLA gather.  Without a mesh no ``shard_map`` is traced."""
    monkeypatch.setattr(ops, "default_impl", lambda: "interpret")
    cfg = registry.get_config("qwen3-0.6b", reduced=True)   # 2 KV heads
    mesh = contextlib.nullcontext()
    if case == "window":
        cfg = cfg.replace(layer_pattern=("L",), local_window=8)
    elif case in ("sharded_kv_heads", "head_dim_layout"):
        n = 2 if case == "sharded_kv_heads" else 4
        mesh = jax.sharding.use_abstract_mesh(
            jax.sharding.AbstractMesh((n,), ("model",)))
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    caches, tok = _paged_decode_setup(cfg)
    step = functools.partial(transformer.decode_step, cfg, rules=make_rules())
    with mesh:
        text = str(jax.make_jaxpr(step)(params, caches, tok))
    assert ("paged_decode_attention" in text) == (case in ("kernel",
                                                           "sharded_kv_heads"))
    assert ("shard_map" in text) == (case == "sharded_kv_heads")
    if case == "kernel":
        # the mesh-less read: the kernel, called once in the layer scan
        assert text.count("pallas_call") == 1


def test_paged_kernel_per_kv_head_shard_matches_xla_path():
    """On a 4-device CPU mesh that splits 4 KV heads (8 query heads) one a
    device, the paged ``decode_step`` with the kernel run per shard
    (interpreted) gives the XLA gather path's greedy tokens and logits
    over 8 steps, with a shuffled table, read-only shared blocks and an
    unmapped row (``_paged_decode_setup``).  The tolerance is float32
    reduction order: the kernel's online softmax against one softmax."""
    res = run_on_devices(4, """
        import functools, json
        import jax, numpy as np
        from repro.kernels import ops
        from repro.launch.mesh import serving_mesh
        from repro.models import registry, transformer
        from repro.sharding import make_rules, tree_shardings
        from test_paging import _paged_decode_setup

        cfg = registry.get_config("qwen3-0.6b", reduced=True).replace(
            n_heads=8, n_kv_heads=4, d_model=128)
        rules, mesh = make_rules(), serving_mesh(4)
        params = jax.device_put(
            transformer.init_params(cfg, jax.random.PRNGKey(0)),
            tree_shardings(transformer.abstract_params(cfg), rules, mesh))
        out = {}
        for impl in ("interpret", "xla"):
            ops.default_impl = lambda: impl
            caches, tok = _paged_decode_setup(cfg)
            step = jax.jit(functools.partial(transformer.decode_step, cfg,
                                             rules=rules))
            toks, logits = [], []
            with jax.set_mesh(mesh):
                text = str(step.trace(params, caches, tok).jaxpr)
                for _ in range(8):
                    o, caches = step(params, caches, tok)
                    tok = transformer.greedy_token(cfg, o)
                    toks.append(np.asarray(tok[:2, 0]).tolist())
                    logits.append(np.asarray(o[:2, 0]))
            out[impl] = {"kernel": "paged_decode_attention" in text,
                         "shard_map": "shard_map" in text, "toks": toks,
                         "logits": np.stack(logits).tolist()}
        print(json.dumps(out))
    """)
    k, x = res["interpret"], res["xla"]
    assert k["kernel"] and k["shard_map"]
    assert not x["kernel"]
    assert k["toks"] == x["toks"]
    np.testing.assert_allclose(k["logits"], x["logits"], rtol=1e-4, atol=1e-4)
