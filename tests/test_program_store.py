"""Executor API v2: typed ProgramSpec/Handle + the persistent ProgramStore.

The paper's global-memory program tier (§3.3, Table 1): a stored program
installs into a rebooted syscore by deserialization (load path) instead of
recompilation, falls back to compile-and-store on any miss — version skew,
corruption, unserializable executables — and stays output-exact.
"""
import pickle

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import (METRIC_PROGRAM_COMPILE_MS, METRIC_PROGRAM_LOAD_MS,
                        ProgramSpec, ProgramStore, Syscore,
                        UnknownProgramError)
from repro.sharding import LogicalArray


def _toy(w, x):
    return jnp.tanh(x @ w) @ w.T


def _args(n=32):
    w = jnp.ones((n, n), jnp.float32) * 0.01
    x = jnp.ones((4, n), jnp.float32)
    return w, x


def _spec(key="toy", n=32, context="ctx", fn=_toy):
    w, x = _args(n)
    abstract = (LogicalArray(w.shape, w.dtype, (None, None)),
                LogicalArray(x.shape, x.dtype, (None, None)))
    return ProgramSpec(key=key, fn=fn, abstract_args=abstract,
                       context=context)


# ---------------------------------------------------------------------------
# ProgramSpec fingerprints
# ---------------------------------------------------------------------------
def test_fingerprint_stable_across_instances():
    assert _spec().fingerprint == _spec().fingerprint


def test_fingerprint_sensitive_to_content():
    base = _spec()
    assert _spec(n=16).fingerprint != base.fingerprint          # shapes
    assert _spec(context="other").fingerprint != base.fingerprint
    assert _spec(fn=lambda w, x: x).fingerprint != base.fingerprint
    # the key is routing, not content: same program under two keys shares
    # one fingerprint (and therefore one store entry)
    assert _spec(key="other").fingerprint == base.fingerprint


def test_fingerprint_covers_donation():
    w, x = _args()
    abstract = (LogicalArray(w.shape, w.dtype, (None, None)),
                LogicalArray(x.shape, x.dtype, (None, None)))
    a = ProgramSpec(key="k", fn=_toy, abstract_args=abstract)
    b = ProgramSpec(key="k", fn=_toy, abstract_args=abstract,
                    donate_argnums=(1,))
    assert a.fingerprint != b.fingerprint


def test_paged_decode_fingerprints_name_the_paged_read(monkeypatch):
    """The paged programs that decode carry the paged read in their
    fingerprint: the Pallas kernel and the XLA gather never share a store
    entry, and two builds of the same read agree."""
    from repro import steps
    from repro.engine_config import (EngineConfig, HorizonConfig,
                                     PagingConfig, SpecConfig)
    from repro.kernels import ops
    from repro.models import registry
    from repro.sharding import make_rules
    cfg = registry.get_config("qwen3-0.6b", reduced=True)
    config = EngineConfig(batch=2, max_len=32,
                          paging=PagingConfig(kv_block=4),
                          spec=SpecConfig(k=2), horizon=HorizonConfig(4))
    decoding = ("decode", "decode_horizon", "verify")

    def fingerprints(impl):
        monkeypatch.setattr(ops, "default_impl", lambda: impl)
        specs = steps.serve_program_specs(cfg, make_rules(), config)
        return {k: specs[k].fingerprint for k in decoding + ("prefill_slot",)}

    kernel, xla = fingerprints("pallas"), fingerprints("xla")
    assert fingerprints("pallas") == kernel
    assert fingerprints("xla") == xla
    for key in decoding:
        assert kernel[key] != xla[key], key
    assert kernel["prefill_slot"] == xla["prefill_slot"]


# ---------------------------------------------------------------------------
# Store-backed warm boot
# ---------------------------------------------------------------------------
def test_warm_boot_loads_instead_of_compiling(tmp_path):
    w, x = _args()
    spec = _spec()

    cold = Syscore(store=ProgramStore(tmp_path))
    toy = cold.hot_load(spec)
    want = np.asarray(toy.block(w, x))
    rep = cold.report()["programs"]["toy"]
    assert rep["source"] == "compile" and rep["compile_s"] > 0
    if cold.store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    assert rep["store_s"] > 0

    # a rebooted process: fresh store object over the same directory
    warm = Syscore(store=ProgramStore(tmp_path))
    toy2 = warm.hot_load(spec)
    rep = warm.report()["programs"]["toy"]
    assert rep["source"] == "store"
    assert rep["load_s"] > 0 and rep["compile_s"] == 0
    assert rep["store_s"] == 0
    assert rep["serialized_bytes"] > 0
    np.testing.assert_array_equal(np.asarray(toy2.block(w, x)), want)
    assert warm.store.hits == 1
    # load-vs-compile times flow through the CALL_METRIC channel
    assert METRIC_PROGRAM_LOAD_MS in warm.hostcalls.metrics
    assert METRIC_PROGRAM_COMPILE_MS in cold.hostcalls.metrics


def test_store_miss_on_corrupt_payload_falls_back_to_compile(tmp_path):
    store = ProgramStore(tmp_path)
    spec = _spec()
    sc = Syscore(store=store)
    sc.hot_load(spec)
    if store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    for p in tmp_path.glob("*.pkl"):
        p.write_bytes(b"not a pickle")
    warm = Syscore(store=ProgramStore(tmp_path))
    toy = warm.hot_load(spec)
    rep = warm.report()["programs"]["toy"]
    assert rep["source"] == "compile" and rep["compile_s"] > 0
    w, x = _args()
    assert np.isfinite(np.asarray(toy.block(w, x))).all()
    assert warm.store.misses >= 1
    assert warm.store.errors == 1          # the fallback is visible


def test_store_keyed_on_environment_version(tmp_path, monkeypatch):
    """Version skew (different jax/jaxlib/backend) must MISS, not revive a
    stale executable."""
    store = ProgramStore(tmp_path)
    spec = _spec()
    sc = Syscore(store=store)
    sc.hot_load(spec)
    if store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")

    skewed = ProgramStore(tmp_path)
    monkeypatch.setattr(
        skewed, "_env_key", lambda: ("jax-999.0", "jaxlib-999.0", "cpu", "1"))
    assert skewed.get(spec) is None
    assert skewed.misses == 1
    assert skewed.errors == 0              # a skewed key is a plain miss
    warm = Syscore(store=skewed)
    warm.hot_load(spec)
    assert warm.report()["programs"]["toy"]["source"] == "compile"


def test_unserializable_program_is_skipped_not_fatal(tmp_path):
    """Executables that capture host callbacks cannot be pickled; the store
    counts the skip and the program still installs and runs."""
    from repro.core import HostCallTable
    hct = HostCallTable()

    def with_callback(w, x):
        y = _toy(w, x)
        hct.hostcall(513, jnp.asarray(0), jnp.sum(y))    # CALL_METRIC
        return y

    store = ProgramStore(tmp_path)
    sc = Syscore(store=store)
    prog = sc.hot_load(_spec(fn=with_callback, context="cb"))
    w, x = _args()
    out = np.asarray(prog.block(w, x))
    assert np.isfinite(out).all()
    assert store.skipped == 1 and store.puts == 0
    assert store.errors == 1
    assert hct.metrics[0]                       # the callback still fired


def test_store_report_and_entries(tmp_path):
    store = ProgramStore(tmp_path)
    sc = Syscore(store=store)
    sc.hot_load(_spec())
    if store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    rep = store.report()
    assert rep["entries"] == 1 and rep["bytes"] > 0 and rep["puts"] == 1
    (entry,) = store.entries().values()
    assert entry["key"] == "toy"
    assert entry["fingerprint"] == _spec().fingerprint
    store.clear()
    assert store.report()["entries"] == 0


# ---------------------------------------------------------------------------
# Handles and the registry
# ---------------------------------------------------------------------------
def test_handle_follows_hot_swap_atomically():
    """A live handle retargets when its key is hot-swapped — the registry
    swap is the atomic install step."""
    sc = Syscore()
    w, x = _args()
    h = sc.hot_load(_spec())
    np.asarray(h.block(w, x))
    sc.hot_load(_spec(fn=lambda w, x: x * 3.0, context="v2"))
    np.testing.assert_allclose(np.asarray(h.block(w, x)), np.asarray(x) * 3)


def test_handle_evict_and_lookup_errors():
    sc = Syscore()
    h = sc.hot_load(_spec())
    assert sc.handle("toy").key == "toy"
    h.evict()
    with pytest.raises(UnknownProgramError):
        h(*_args())
    with pytest.raises(UnknownProgramError):
        sc.handle("toy")


@pytest.mark.parametrize("op", ["execute", "serialize", "evict"])
def test_unknown_key_error_names_key_and_lists_programs(op):
    sc = Syscore()
    sc.hot_load(_spec(key="alpha"))
    sc.hot_load(_spec(key="beta", context="b"))
    with pytest.raises(UnknownProgramError) as ei:
        if op == "execute":
            with pytest.warns(DeprecationWarning):
                sc.execute("gamma")
        else:
            getattr(sc, op)("gamma")
    msg = str(ei.value)
    assert "'gamma'" in msg and "'alpha'" in msg and "'beta'" in msg
    # still a KeyError for any caller catching the old exception type
    assert isinstance(ei.value, KeyError)


# ---------------------------------------------------------------------------
# Checkpoint integration
# ---------------------------------------------------------------------------
def test_checkpoint_manager_persists_programs(tmp_path):
    manager = CheckpointManager(tmp_path, keep=1)
    sc = Syscore(store=None)
    h = sc.hot_load(_spec())
    w, x = _args()
    want = np.asarray(h.block(w, x))
    manager.save(0, {"w": np.ones(3)}, syscore=sc)
    if manager.program_store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    # checkpoint GC never rolls the program store
    manager.save(1, {"w": np.ones(3)}, syscore=sc)
    assert manager.program_store.report()["entries"] == 1

    # reboot path: a Syscore over the checkpoint's store loads, not compiles
    warm = Syscore(store=CheckpointManager(tmp_path).program_store)
    h2 = warm.hot_load(_spec())
    assert warm.report()["programs"]["toy"]["source"] == "store"
    np.testing.assert_array_equal(np.asarray(h2.block(w, x)), want)


def test_store_pickle_layout_is_atomic(tmp_path):
    """No .tmp_* residue after a put; payload file is a loadable pickle."""
    store = ProgramStore(tmp_path)
    sc = Syscore(store=store)
    sc.hot_load(_spec())
    if store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    assert not list(tmp_path.glob(".tmp_*"))
    (pkl,) = tmp_path.glob("*.pkl")
    payload, in_tree, out_tree = pickle.loads(pkl.read_bytes())
    assert isinstance(payload, bytes) and len(payload) > 0


# ---------------------------------------------------------------------------
# Concurrent sharing: one store directory, many executors (the cluster
# supervisor's warm-failover substrate — repro.cluster.supervisor)
# ---------------------------------------------------------------------------
def test_two_executors_share_one_store_dir(tmp_path):
    """Executor A compiles-and-stores; executor B (its OWN store object,
    same directory) installs every program by deserialization."""
    w, x = _args()
    a = Syscore(store=ProgramStore(tmp_path))
    ha = a.hot_load(_spec())
    want = np.asarray(ha.block(w, x))
    if a.store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    b = Syscore(store=ProgramStore(tmp_path))
    hb = b.hot_load(_spec())
    rep = b.report()["programs"]["toy"]
    assert rep["source"] == "store" and rep["compile_s"] == 0
    np.testing.assert_array_equal(np.asarray(hb.block(w, x)), want)
    # B's load did not perturb A's live handle
    np.testing.assert_array_equal(np.asarray(ha.block(w, x)), want)


def test_interleaved_warm_boots_compile_each_program_once(tmp_path):
    """Two executors alternate first-touch on different programs; each
    program is compiled exactly once fleet-wide, every other install is a
    store hit."""
    specs = [_spec(key=f"p{i}", context=f"v{i}") for i in range(4)]
    a = Syscore(store=ProgramStore(tmp_path))
    b = Syscore(store=ProgramStore(tmp_path))
    owners = [a, b, a, b]              # who compiles each program first
    for sc, spec in zip(owners, specs):
        sc.hot_load(spec)
    if a.store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    for sc, spec in zip(reversed(owners), specs):   # second-touch swapped
        sc.hot_load(spec)
    for sc in (a, b):
        progs = sc.report()["programs"]
        assert len(progs) == 4
        compiled = [k for k, v in progs.items() if v["source"] == "compile"]
        loaded = [k for k, v in progs.items() if v["source"] == "store"]
        assert len(compiled) == 2 and len(loaded) == 2, progs
    assert a.store.puts + b.store.puts == 4
    assert ProgramStore(tmp_path).report()["entries"] == 4


def test_corrupt_entry_while_shared_degrades_one_reader_and_heals(tmp_path):
    """Corrupting a shared entry on disk sends the NEXT reader down the
    compile path — which re-puts and heals the entry for everyone after —
    while executors already holding the program keep serving."""
    w, x = _args()
    a = Syscore(store=ProgramStore(tmp_path))
    ha = a.hot_load(_spec())
    want = np.asarray(ha.block(w, x))
    if a.store.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    for p in tmp_path.glob("*.pkl"):
        p.write_bytes(b"torn write garbage")
    # reader B: miss -> compile -> re-put (the heal)
    b_store = ProgramStore(tmp_path)
    b = Syscore(store=b_store)
    hb = b.hot_load(_spec())
    assert b.report()["programs"]["toy"]["source"] == "compile"
    assert b_store.misses >= 1 and b_store.puts == 1
    np.testing.assert_array_equal(np.asarray(hb.block(w, x)), want)
    # A's live handle never noticed
    np.testing.assert_array_equal(np.asarray(ha.block(w, x)), want)
    # reader C sees the healed entry: back on the load path
    c = Syscore(store=ProgramStore(tmp_path))
    c.hot_load(_spec())
    assert c.report()["programs"]["toy"]["source"] == "store"


def test_racing_puts_leave_no_tmp_residue_and_one_winner(tmp_path):
    """Two stores putting the same fingerprint: last os.replace wins
    whole-file; no .tmp_* residue, entry loads cleanly afterwards."""
    s1, s2 = ProgramStore(tmp_path), ProgramStore(tmp_path)
    a = Syscore(store=s1)
    a.hot_load(_spec())
    if s1.puts == 0:
        pytest.skip("executable serialization unavailable on this jax")
    b = Syscore(store=s2)
    handle = b.hot_load(_spec())
    # force a second put of the same entry through store 2
    payload, in_tree, out_tree = a.serialize("toy")
    s2.put(_spec(), payload, in_tree, out_tree)
    assert not list(tmp_path.glob(".tmp_*"))
    assert ProgramStore(tmp_path).get(_spec()) is not None
    w, x = _args()
    assert np.isfinite(np.asarray(handle.block(w, x))).all()
