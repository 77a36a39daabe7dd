"""Substrate tests: checkpoint atomicity/rotation, data determinism,
sharding-rule properties (hypothesis), optimizer behaviour, HLO analyzer."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is optional: the property-based case falls back to a fixed
# sweep so tier-1 collection never depends on it
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.checkpoint import CheckpointManager, save_checkpoint, load_checkpoint
from repro.checkpoint.checkpoint import latest_step
from repro.data import DataConfig, TokenPipeline
from repro.models import registry
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.sharding import LogicalArray, fit_spec, make_rules, spec_from_logical


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def _tree():
    return {"a": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.float32),
                       "step": jnp.asarray(3, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(tmp_path, 5, t)
    restored, step = load_checkpoint(tmp_path, t)
    assert step == 5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_rotation(tmp_path):
    m = CheckpointManager(tmp_path, keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        m.save(s, t)
    assert latest_step(tmp_path) == 4
    kept = sorted(p.name for p in tmp_path.glob("step_*"))
    assert kept == ["step_3", "step_4"]


def test_checkpoint_partial_write_is_invisible(tmp_path):
    """A crashed save (tmp dir left behind) must not be restorable."""
    t = _tree()
    save_checkpoint(tmp_path, 1, t)
    # simulate a crash mid-save of step 2: tmp dir exists, no rename
    (tmp_path / ".tmp_step_2").mkdir()
    (tmp_path / ".tmp_step_2" / "garbage.npy").write_bytes(b"xx")
    assert latest_step(tmp_path) == 1
    restored, step = load_checkpoint(tmp_path, t)
    assert step == 1


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_deterministic_replay():
    cfg = registry.get_config("qwen3-0.6b", reduced=True)
    d = DataConfig(global_batch=4, seq_len=32, seed=7)
    p1 = TokenPipeline(cfg, d)
    p2 = TokenPipeline(cfg, d)
    b1 = p1.host_batch(13)
    b2 = p2.host_batch(13)
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])
    b3 = p1.host_batch(14)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_data_prefetch_iterator_matches_direct():
    cfg = registry.get_config("qwen3-0.6b", reduced=True)
    d = DataConfig(global_batch=2, seq_len=16, seed=1)
    p = TokenPipeline(cfg, d, prefetch=2)
    seen = list(p.run(5, 3))
    assert [s for s, _ in seen] == [5, 6, 7]
    direct = p.host_batch(6)
    np.testing.assert_array_equal(np.asarray(seen[1][1]["tokens"]),
                                  direct["tokens"])


def test_data_labels_are_shifted_tokens():
    cfg = registry.get_config("qwen3-0.6b", reduced=True)
    d = DataConfig(global_batch=2, seq_len=16, seed=1)
    b = TokenPipeline(cfg, d).host_batch(0)
    # labels[t] is the next token after tokens[t]
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


def test_vlm_batch_masks_prefix():
    cfg = registry.get_config("internvl2-26b", reduced=True)
    d = DataConfig(global_batch=2, seq_len=16, seed=1)
    b = TokenPipeline(cfg, d).host_batch(0)
    p = cfg.frontend_tokens
    assert (b["labels"][:, :p] == -1).all()
    assert b["prefix_embeds"].shape == (2, p, cfg.d_model)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def _fit_spec_divisible_case(dims, axis_dim):
    """Property: fit_spec output always satisfies pjit divisibility."""
    from jax.sharding import PartitionSpec as P
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    axis_dim = axis_dim % len(dims)
    spec = [None] * len(dims)
    spec[axis_dim] = "model"
    fitted = fit_spec(tuple(dims), P(*spec), mesh)
    for size, ax in zip(dims, tuple(fitted) + (None,) * len(dims)):
        if ax is None:
            continue
        factor = 16 if ax == "model" else 1
        assert size % factor == 0


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(dims=st.lists(st.sampled_from([1, 3, 4, 8, 16, 24, 128, 256]),
                         min_size=1, max_size=4),
           axis_dim=st.integers(0, 3))
    def test_fit_spec_always_divisible(dims, axis_dim):
        _fit_spec_divisible_case(dims, axis_dim)
else:
    def test_fit_spec_always_divisible():
        rng = np.random.default_rng(0)
        choices = [1, 3, 4, 8, 16, 24, 128, 256]
        for _ in range(60):
            dims = list(rng.choice(choices, size=rng.integers(1, 5)))
            _fit_spec_divisible_case([int(d) for d in dims],
                                     int(rng.integers(0, 4)))


def test_fit_spec_moves_model_axis_to_head_dim():
    from jax.sharding import PartitionSpec as P
    mesh = _FakeMesh({"data": 16, "model": 16})
    # KV cache (B, C, kv_heads=8, head_dim=128): model moves to dim 3
    fitted = fit_spec((128, 2048, 8, 128),
                      P(("data",), None, "model", None), mesh)
    assert tuple(fitted)[2:] == (None, "model")   # moved to head_dim
    assert fitted[0] in ("data", ("data",))


def test_rules_resolve_against_mesh_subsets():
    rules = make_rules(fsdp=True)
    spec = spec_from_logical(("embed_fsdp", "ff"), rules,
                             _FakeMesh({"data": 16, "model": 16}))
    # PartitionSpec normalizes 1-tuples to bare names
    assert tuple(spec) in ((("data",), "model"), ("data", "model"))
    spec2 = spec_from_logical(("embed_fsdp", "ff"), rules,
                              _FakeMesh({"pod": 2, "data": 16, "model": 16}))
    assert tuple(spec2) == (("pod", "data"), "model")


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_reduces_quadratic_loss():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=400,
                      weight_decay=0.0, clip_norm=100.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(cfg, g, state, params)
    assert float(loss(params)) < 0.3


def test_adamw_clipping_and_schedule():
    cfg = AdamWConfig(lr=1e-2, clip_norm=1.0, warmup_steps=10,
                      total_steps=100)
    assert float(cosine_schedule(cfg, jnp.asarray(0))) == 0.0
    assert float(cosine_schedule(cfg, jnp.asarray(10))) == pytest.approx(1e-2)
    assert float(cosine_schedule(cfg, jnp.asarray(100))) == pytest.approx(
        1e-3, rel=1e-2)
    params = {"w": jnp.ones((3,))}
    state = adamw_init(params)
    huge = {"w": jnp.full((3,), 1e6)}
    _, _, m = adamw_update(cfg, huge, state, params)
    assert float(m["grad_norm"]) > 1e6  # reported pre-clip


# ---------------------------------------------------------------------------
# HLO analyzer (the roofline's foundation)
# ---------------------------------------------------------------------------
def test_hlo_analyzer_loop_awareness():
    from repro.launch import hlo_analysis as ha

    def body(x, w):
        return jnp.tanh(x @ w), None

    w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)

    def scanned(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y

    def unrolled(x, ws):
        for i in range(8):
            x, _ = body(x, ws[i])
        return x

    fs = ha.analyze(jax.jit(scanned).lower(x, w).compile().as_text(), 1)
    fu = ha.analyze(jax.jit(unrolled).lower(x, w).compile().as_text(), 1)
    true_flops = 8 * 2 * 32 * 64 * 64
    assert fs.flops == true_flops
    assert fu.flops == true_flops


def test_hlo_analyzer_collectives_scale_with_loop(tmp_path):
    """An all-reduce inside a scan body must be counted trip_count times."""
    from repro.launch import hlo_analysis as ha
    import subprocess, sys, textwrap, os
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P
        from repro.launch import hlo_analysis as ha
        mesh = jax.make_mesh((4,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        def step(ws, x):
            def body(x, w):
                y = x @ w
                y = jax.lax.with_sharding_constraint(y, P(None, None))
                return y, None
            out, _ = jax.lax.scan(body, x, ws)
            return out
        with jax.set_mesh(mesh):
            NS = lambda *spec: jax.sharding.NamedSharding(mesh, P(*spec))
            f = jax.jit(step, in_shardings=(NS(None, None, "model"),
                                            NS(None, "model")),
                        out_shardings=NS(None, None))
            txt = f.lower(jax.ShapeDtypeStruct((6, 32, 32), jnp.float32),
                          jax.ShapeDtypeStruct((16, 32), jnp.float32)
                          ).compile().as_text()
        c = ha.analyze(txt, 4)
        ar = [x for x in c.collectives
              if x.kind in ("all-reduce", "all-gather")]
        print(json.dumps({"count": sum(x.count for x in ar)}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["count"] >= 6  # one per scan iteration


def test_roofline_terms_and_dominance():
    from repro.launch.roofline import roofline_terms
    r = roofline_terms(197e12, 819e9 * 0.5, 0.0)
    assert r["dominant"] == "compute"
    assert r["roofline_fraction"] == pytest.approx(1.0)
    r2 = roofline_terms(197e11, 819e9, 0.0)
    assert r2["dominant"] == "memory"
    assert r2["roofline_fraction"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# fault runtime
# ---------------------------------------------------------------------------
def test_straggler_monitor_flags_outliers():
    from repro.runtime import StragglerMonitor
    m = StragglerMonitor(window=16, threshold=1.5, patience=2)
    escalated = False
    for i in range(20):
        escalated |= m.observe(1.0)
    assert not escalated
    for i in range(3):            # sustained straggling escalates
        escalated |= m.observe(5.0)
    assert m.summary()["median_s"] == 1.0
    assert escalated


def test_run_with_restarts_resumes_from_checkpoint():
    from repro.runtime import FaultInjector, run_with_restarts
    from repro.runtime.fault import SimulatedFailure
    inj = FaultInjector([3])
    durable = {"step": 0}
    log = []

    def loop(start):
        for s in range(start, 6):
            inj.check(s)
            log.append(s)
            durable["step"] = s
        return 5

    res = run_with_restarts(loop, resume_step_fn=lambda: durable["step"],
                            max_restarts=2)
    assert res["restarts"] == 1
    assert res["final_step"] == 5
    assert 3 in log  # the failed step was retried after restart
