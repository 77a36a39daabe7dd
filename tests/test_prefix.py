"""Cross-request prefix sharing over the paged KV arena (ISSUE 8).

Three layers of coverage:

  * ``PagedKVManager`` trie mechanics: publish turns prefilled blocks into
    refcounted trie nodes, matches map them read-only, eviction and the
    write-through :class:`PrefixStore` round-trip the bytes, and grow /
    trim / release never touch a shared block;
  * the shared-mapping device encoding: ``-(phys + 2)`` entries gather the
    right block and silently drop writes (the COW write protection);
  * the serving engine: byte-exact streams vs the non-sharing reference
    across all five model families x {plain, spec, horizon} under warm
    (skip-prefill), tier-2 (full prefill over shared mappings) and cold
    admissions — including a spec-decode request diverging inside a
    shared prefix block, and sharing under arena pressure.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.paging import (PagedKVManager, PrefixStore, decode_block_table,
                               encode_shared)
from repro.engine_config import (EngineConfig, HorizonConfig, PagingConfig,
                                 PrefixConfig, SpecConfig)
from repro.launch.serve import METRIC_PREFIX_HIT, ServingEngine
from repro.models import attention

FAMILY_ARCHS = [
    "qwen3-0.6b",         # dense attention
    "gemma3-4b",          # sliding-window attention
    "mamba2-130m",        # SSM (no KV: sharing is a structural no-op)
    "recurrentgemma-2b",  # hybrid (tier-2 only: state must replay)
    "olmoe-1b-7b",        # MoE (tier-2 only: routing numerics differ
                          # between batched prefill and one-token decode)
]


# ---------------------------------------------------------------------------
# manager-level trie mechanics (toy caches, no model)
# ---------------------------------------------------------------------------
def _toy_caches(batch=2, n_phys=6, n_blocks=6, bs=2):
    """Same leaf layout as the real paged cache: group-stacked arena leaves
    (layers first), tail arena leaves, per-slot recurrent rows absent so
    the toy family is 'pure attention'.  block_bytes = 128 for bs=2."""
    return {
        "pos": jnp.zeros((batch,), jnp.int32),
        "block_table": jnp.full((batch, n_blocks), -1, jnp.int32),
        "groups": {"slot0": {"k": jnp.zeros((3, n_phys, bs, 1, 2)),
                             "v": jnp.zeros((3, n_phys, bs, 1, 2))}},
        "tail": {"tail0": {"k": jnp.zeros((n_phys, bs, 1, 2)),
                           "v": jnp.zeros((n_phys, bs, 1, 2))}},
    }


def _mgr(arena=6, store=None, uva=None):
    # NB: an empty PrefixStore is falsy (len 0) — test with `is None`
    return PagedKVManager(
        arena, 128, kv_block=2,
        prefix_store=PrefixStore() if store is None else store, uva=uva)


def _fill_blocks(caches, phys, seed):
    """Write distinct random content into physical blocks ``phys`` of every
    KV leaf; returns the groups-k values for later byte comparison."""
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(phys)
    gk = jnp.asarray(rng.standard_normal((3, len(phys), 2, 1, 2)),
                     jnp.float32)
    caches["groups"]["slot0"]["k"] = \
        caches["groups"]["slot0"]["k"].at[:, idx].set(gk)
    caches["tail"]["tail0"]["k"] = caches["tail"]["tail0"]["k"].at[idx].set(
        jnp.asarray(rng.standard_normal((len(phys), 2, 1, 2)), jnp.float32))
    return np.asarray(gk)


def test_publish_match_refcount_evict_fault_roundtrip():
    """The full shared-block lifecycle: publish -> match -> refcounts ->
    zero-ref eviction under pressure -> store fault-in, byte-exact."""
    mgr = _mgr(arena=6)
    caches = _toy_caches()
    p0 = [1, 2, 3, 4, 5]

    caches = mgr.admit(rid=0, n_blocks=3, slot=0, caches=caches)
    gk = _fill_blocks(caches, mgr.pages[0].phys, seed=0)
    caches = mgr.publish(0, p0, 0, caches)
    # 5 tokens / kv_block=2 -> 2 full blocks published, 1 private left
    assert mgr.published_blocks == 2 and len(mgr.store) == 2
    page0 = mgr.pages[0]
    assert len(page0.shared) == 2 and page0.n_private == 1
    row0 = np.asarray(caches["block_table"][0])
    assert row0[0] < -1 and row0[1] < -1 and row0[2] >= 0
    assert all(sb.refs == 1 for sb in page0.shared)
    mgr.check_invariants()

    # the match is capped strictly below the final-position block: a
    # 4-token prompt whose 2 blocks are both in the trie matches only 1
    assert len(mgr.match_prefix([1, 2, 3, 4])) == 1
    assert mgr.match_prefix([]) == [] and mgr.match_prefix([1]) == []
    assert len(mgr.match_prefix([1, 2, 9, 9, 9])) == 1   # divergence
    shared = mgr.match_prefix([1, 2, 3, 4, 7, 8, 9])
    assert [sb.chunk for sb in shared] == [(1, 2), (3, 4)]

    # second request maps the SAME physical blocks read-only
    assert mgr.can_admit(1, 4, shared=shared)
    caches = mgr.admit(rid=1, n_blocks=4, slot=1, caches=caches,
                       shared=shared)
    assert mgr.prefix_hits == 2
    assert all(sb.refs == 2 for sb in shared)
    row1 = np.asarray(caches["block_table"][1])
    np.testing.assert_array_equal(decode_block_table(row1)[:2],
                                  decode_block_table(row0)[:2])
    mgr.check_invariants()

    # release decrements refs; zero-ref blocks stay resident (no pressure)
    caches = mgr.release(0, 0, caches)
    assert all(sb.refs == 1 for sb in shared)
    caches = mgr.release(1, 1, caches)
    assert all(sb.refs == 0 for sb in shared)
    assert all(sb.phys is not None for sb in shared)
    mgr.check_invariants()

    # arena-wide admission evicts the cold shared blocks (free, no
    # writeback: the store copy is the write-through original)
    caches = mgr.admit(rid=2, n_blocks=6, slot=0, caches=caches)
    assert mgr.shared_evictions == 2
    assert all(sb.phys is None for sb in shared)
    assert len(mgr.store) == 2                 # store survives eviction
    mgr.check_invariants()
    caches = mgr.release(2, 0, caches)

    # the trie still matches; admission faults the bytes back from host
    shared = mgr.match_prefix(p0)
    assert len(shared) == 2 and all(sb.phys is None for sb in shared)
    caches = mgr.admit(rid=3, n_blocks=3, slot=0, caches=caches,
                       shared=shared)
    assert mgr.shared_faults == 2
    phys = [sb.phys for sb in shared]
    np.testing.assert_array_equal(
        np.asarray(caches["groups"]["slot0"]["k"][:, jnp.asarray(phys)]),
        gk[:, :2])
    mgr.check_invariants()


def test_trie_rebuilds_from_store_across_engine_lifetimes():
    """Failover shape: a PrefixStore that outlives its manager re-seeds a
    fresh trie whose cold nodes fault in byte-exactly (satellite 4's
    manager half)."""
    store = PrefixStore()
    mgr1 = _mgr(arena=6, store=store)
    caches = _toy_caches()
    caches = mgr1.admit(rid=0, n_blocks=3, slot=0, caches=caches)
    gk = _fill_blocks(caches, mgr1.pages[0].phys, seed=1)
    mgr1.publish(0, [1, 2, 3, 4, 5], 0, caches)

    mgr2 = _mgr(arena=6, store=store)         # the rebooted replica
    assert len(mgr2._shared) == 2
    shared = mgr2.match_prefix([1, 2, 3, 4, 5])
    assert len(shared) == 2 and all(sb.phys is None for sb in shared)
    caches2 = _toy_caches()
    caches2 = mgr2.admit(rid=0, n_blocks=3, slot=0, caches=caches2,
                         shared=shared)
    assert mgr2.shared_faults == 2
    phys = [sb.phys for sb in shared]
    np.testing.assert_array_equal(
        np.asarray(caches2["groups"]["slot0"]["k"][:, jnp.asarray(phys)]),
        gk[:, :2])
    mgr2.check_invariants()


def test_grow_and_trim_never_touch_shared_blocks():
    """Satellite 3 (manager half): speculative grow extends only the
    private run and trim reclaims only the grown tail — the shared head
    keeps its physical blocks, refcounts and encoding throughout."""
    mgr = _mgr(arena=8)
    caches = _toy_caches(n_phys=8)
    caches = mgr.admit(rid=0, n_blocks=3, slot=0, caches=caches)
    _fill_blocks(caches, mgr.pages[0].phys, seed=2)
    caches = mgr.publish(0, [1, 2, 3, 4, 5], 0, caches)
    shared = mgr.match_prefix([1, 2, 3, 4, 6, 7])
    caches = mgr.admit(rid=1, n_blocks=3, slot=1, caches=caches,
                       shared=shared)
    shared_phys = [sb.phys for sb in shared]

    caches = mgr.grow(1, 5, 1, caches)
    page = mgr.pages[1]
    assert page.n_blocks == 5 and page.n_private == 3
    assert [sb.phys for sb in shared] == shared_phys
    assert not set(shared_phys) & set(page.phys)     # never grabbed
    row = np.asarray(caches["block_table"][1])
    assert list(row[:2]) == [encode_shared(p) for p in shared_phys]
    mgr.check_invariants()

    caches = mgr.trim_to_base(1, 1, caches)
    page = mgr.pages[1]
    assert page.n_blocks == 3 and page.n_private == 1
    assert [sb.phys for sb in shared] == shared_phys  # never trimmed
    assert not set(shared_phys) & set(mgr.free)       # never freed
    assert all(sb.refs == 2 for sb in shared)
    row = np.asarray(caches["block_table"][1])
    assert list(row[:2]) == [encode_shared(p) for p in shared_phys]
    assert row[3] == -1 and row[2] >= 0
    mgr.check_invariants()


def test_preempted_shared_head_unpins_evicts_and_faults_back():
    """Preemption drops a request's shared pins with its row (keeping the
    refcounts): under arena-wide pressure the shared head evicts for free
    and the resume faults it back from the store byte-exactly — pinning
    it across preemption would let enough preempted requests deadlock a
    small arena."""
    mgr = _mgr(arena=6)
    caches = _toy_caches()
    caches = mgr.admit(rid=0, n_blocks=3, slot=0, caches=caches)
    gk = _fill_blocks(caches, mgr.pages[0].phys, seed=3)
    caches = mgr.publish(0, [1, 2, 3, 4, 5], 0, caches)
    caches = mgr.release(0, 0, caches)
    shared = mgr.match_prefix([1, 2, 3, 4, 5])
    caches = mgr.admit(rid=1, n_blocks=3, slot=0, caches=caches,
                       shared=shared)
    caches = mgr.preempt(1, 0, caches)
    assert all(sb.refs == 1 for sb in shared)  # refs survive preemption
    # arena-wide admission: the preempted request's private block writes
    # back AND its unpinned shared head evicts (free — store copy exists)
    assert mgr.can_admit(2, 6)
    caches = mgr.admit(rid=2, n_blocks=6, slot=1, caches=caches)
    assert mgr.swap_outs == 1                  # rid 1's private block
    assert mgr.shared_evictions == 2           # its shared head too
    assert all(sb.phys is None and sb.refs == 1 for sb in shared)
    mgr.check_invariants()
    caches = mgr.release(2, 1, caches)
    caches = mgr.resume(1, slot=0, caches=caches)
    assert mgr.page_faults == 1 and mgr.shared_faults == 2
    phys = [sb.phys for sb in shared]
    row = np.asarray(caches["block_table"][0])
    assert list(row[:2]) == [encode_shared(p) for p in phys]
    np.testing.assert_array_equal(
        np.asarray(caches["groups"]["slot0"]["k"][:, jnp.asarray(phys)]),
        gk[:, :2])
    mgr.check_invariants()
    caches = mgr.release(1, 0, caches)
    assert all(sb.refs == 0 for sb in shared)
    mgr.check_invariants()

    # finishing while preempted with a shared head: refs drop, the pins
    # preemption already dropped are not dropped twice
    shared = mgr.match_prefix([1, 2, 3, 4, 5])
    caches = mgr.admit(rid=3, n_blocks=3, slot=0, caches=caches,
                       shared=shared)
    caches = mgr.preempt(3, 0, caches)
    caches = mgr.release(3, -1, caches)
    assert all(sb.refs == 0 for sb in shared)
    mgr.check_invariants()


# ---------------------------------------------------------------------------
# the device-side encoding (write guard / gather decode)
# ---------------------------------------------------------------------------
def test_shared_encoding_gathers_reads_and_drops_writes():
    """``-(phys + 2)`` is the whole write protection: the gather decodes
    it to the physical block while the write path's ``phys >= 0`` guard
    silently drops any write aimed at it (one layer's arena, as a stack of
    one)."""
    arena = jnp.arange(4 * 2, dtype=jnp.float32).reshape(1, 4, 2, 1, 1)
    bt = jnp.asarray([[encode_shared(1), 2], [-1, -1]], jnp.int32)
    out = attention.gather_paged_kv(arena, 0, bt)
    np.testing.assert_array_equal(np.asarray(out[0, :2]),
                                  np.asarray(arena[0, 1]))  # shared decodes
    np.testing.assert_array_equal(np.asarray(out[0, 2:]),
                                  np.asarray(arena[0, 2]))  # private reads

    val = jnp.full((2, 1, 1), 99.0)
    live = jnp.asarray([True, False])
    # pos 0 -> logical block 0 -> shared mapping: the write must drop
    a2 = attention.write_paged_kv(arena, 0, bt, jnp.asarray([0, 0]), val,
                                  live)
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(arena))
    # pos 2 -> logical block 1 -> private block 2: the write lands
    a3 = attention.write_paged_kv(arena, 0, bt, jnp.asarray([2, 0]), val,
                                  live)
    assert float(a3[0, 2, 0, 0, 0]) == 99.0


# ---------------------------------------------------------------------------
# serving engine: the family x mode exactness matrix
# ---------------------------------------------------------------------------
def _prefix_cfg(mode, kv_block=4, max_len=32, prefill_len=16, **kw):
    return EngineConfig(
        reduced=True, batch=2, max_len=max_len, prefill_len=prefill_len,
        clock="step",
        paging=PagingConfig(kv_block=kv_block,
                            arena_blocks=kw.pop("arena_blocks", None),
                            timeslice=kw.pop("timeslice", None)),
        prefix=PrefixConfig(),
        spec=SpecConfig(k=3) if mode == "spec" else None,
        horizon=HorizonConfig(length=4) if mode == "horizon" else None, **kw)


def _sharing_workload(seed=0):
    """Prompts engineered against kv_block=4: a cold base, an identical
    repeat (warm), two divergent continuations inside the warm suffix
    window, one long-suffix divergence (tier-2) and one fresh cold."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, 500, size=12).astype(np.int32)
    fresh = rng.integers(1, 500, size=10).astype(np.int32)
    alt = rng.integers(1, 500, size=16).astype(np.int32)
    return [
        base,                                            # cold, publishes
        base.copy(),                                     # warm, suffix 4
        np.concatenate([base[:9], alt[:3]]),             # warm, diverges @9
        np.concatenate([base[:8], alt[:7]]),             # warm, suffix 7
        np.concatenate([base[:4], alt[:10]]),            # tier-2: suffix 10
        fresh,                                           # cold, publishes
    ]


@pytest.mark.parametrize("mode", ["plain", "spec", "horizon"])
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_prefix_sharing_streams_exact_all_families(arch, mode):
    """The tentpole gate: with prefix sharing on, every request's stream
    is byte-exact vs the non-sharing batch-of-1 reference — across warm
    (skip-prefill), tier-2 (shared mappings under a full prefill) and
    cold admissions, for every model family, plain / speculative /
    multi-token-horizon decode."""
    eng = ServingEngine(arch, _prefix_cfg(mode))
    prompts = _sharing_workload()
    reqs = [eng.submit(p, max_new=6) for p in prompts]
    assert all(r is not None for r in reqs)
    stats = eng.run()
    assert stats["requests"] == len(prompts)
    for r in reqs:
        ref = eng.reference_generate(r.prompt, r.max_new)
        assert r.generated == ref, (arch, mode, r.rid, r.generated, ref)
    eng.pager.check_invariants()

    rep = eng.pager.report()["prefix"]
    if eng._prefix_tier1:
        # pure-attention family: repeats skip prefill outright
        assert stats["warm_admissions"] >= 3, stats
        assert stats["prefix_tokens_reused"] >= 3 * 8, stats
        assert rep["published_blocks"] >= 3
        hc = eng.syscore.report()["hostcalls"]["metrics"]
        assert hc[METRIC_PREFIX_HIT]["count"] == stats["prefix_admissions"]
    elif rep["published_blocks"] > 0:
        # recurrent-hybrid family: storage dedup without the warm path
        assert stats["prefix_admissions"] >= 3 and \
            stats["warm_admissions"] == 0, stats
    else:
        # attention-free family: sharing is a structural no-op
        assert stats["prefix_admissions"] == 0, stats


def test_spec_divergence_inside_shared_prefix_block_exact():
    """Satellite 3 (engine half): a speculative request whose prompt
    diverges INSIDE a published block maps only the fully-matched head;
    draft writes, verify rollback and grow/trim all happen against the
    shared mapping without perturbing its bytes — streams stay exact and
    the published copy still equals its store original."""
    eng = ServingEngine("qwen3-0.6b", _prefix_cfg(
        "spec", kv_block=8, prefill_len=24))
    rng = np.random.default_rng(7)
    base = rng.integers(1, 500, size=17).astype(np.int32)
    mid = np.concatenate([base[:12],
                          rng.integers(1, 500, size=5).astype(np.int32)])
    reqs = [eng.submit(p, max_new=6) for p in (base, mid, base.copy())]
    eng.run()
    for r in reqs:
        ref = eng.reference_generate(r.prompt, r.max_new)
        assert r.generated == ref, (r.rid, r.generated, ref)
    # mid matched exactly ONE block (divergence inside block 1), the
    # repeat matched two and took the warm path
    assert eng.prefix_admissions >= 2 and eng.warm_admissions >= 1
    eng.pager.check_invariants()
    # the shared bytes survived the speculative traffic: every resident
    # trie block still equals its write-through store copy
    flat = jax.tree_util.tree_flatten_with_path(eng.caches)[0]
    from repro.core.paging import leaf_axis, leaf_kind
    for sb in eng.pager._shared.values():
        if sb.phys is None:
            continue
        live = [np.asarray(jnp.take(leaf, jnp.asarray([sb.phys]),
                                    axis=leaf_axis(path)))
                for path, leaf in flat if leaf_kind(path) == "kv"]
        for got, want in zip(live, eng.prefix_store.get(sb.key)):
            np.testing.assert_array_equal(got, want)


def test_prefix_sharing_under_arena_pressure_exact():
    """Sharing composes with paging pressure: a half-size arena plus
    timeslice rotation forces preemption and eviction around pinned
    shared heads — streams stay exact and the ownership invariants hold
    after every request retires."""
    eng = ServingEngine("qwen3-0.6b", _prefix_cfg(
        "plain", arena_blocks=8, timeslice=3))
    prompts = _sharing_workload(seed=5)
    reqs = [eng.submit(p, max_new=6) for p in prompts]
    stats = eng.run()
    assert stats["requests"] == len(prompts)
    assert stats["preemptions"] >= 1
    for r in reqs:
        ref = eng.reference_generate(r.prompt, r.max_new)
        assert r.generated == ref, (r.rid, r.generated, ref)
    eng.pager.check_invariants()
    assert eng.prefix_admissions >= 1


def test_prefix_stats_and_report_shape():
    """The telemetry contract: run() exposes the sharing counters and the
    pager report carries the prefix sub-report (store included)."""
    eng = ServingEngine("qwen3-0.6b", _prefix_cfg("plain"))
    p = np.arange(1, 13, dtype=np.int32)
    eng.submit(p, max_new=4)
    eng.submit(p.copy(), max_new=4)
    stats = eng.run()
    for key in ("prefix_admissions", "warm_admissions",
                "prefix_tokens_reused"):
        assert key in stats, key
    assert stats["warm_admissions"] == 1
    assert stats["prefix_tokens_reused"] == 8    # 2 blocks of 4
    rep = eng.pager.report()["prefix"]
    assert rep["trie_blocks"] == len(eng.prefix_store)
    assert rep["store"]["entries"] >= 3
    assert rep["store"]["host_bytes"] > 0
