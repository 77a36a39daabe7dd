"""OLMoE's published block against its plain float32 reference.

The serving engine's programs (paged ``prefill_slot``, then paged
``decode`` through the cache) run OLMoE's block at the reduced preset on
seeded random weights, and their logits are compared with
``chipbench/models/olmoe.py``'s full forward pass over the same tokens:
mesh-less, and on a 4-device CPU mesh that splits the heads, the experts
and the vocabulary as the four-chip engine does.  The two ablations of the
block (top-k gates renormalised, qk-norm per head) must fail the same
tolerance, and serving routing must be drop-free.
"""
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

from chipbench import bench
from chipbench.tests.olmoe_cells import reduced_config
from repro.configs.olmoe_1b_7b import CONFIG
from repro.engine_config import EngineConfig, PagingConfig, ShardConfig
from repro.launch.serve import ServingEngine
from repro.models import registry

ROOT = Path(__file__).resolve().parents[1]
OLMOE = bench.load_module(bench.HERE / "models" / "olmoe.py", "model")
SEED = 2**31 + 1515
# float32 program against the float32 reference: they differ only in the
# order of reductions (batched matmuls, the paged read, the psum of the
# experts' parts), about 1e-6 on logits of size 4; an ablated block moves
# them by 1e-2 or more
TOL = 1e-4


def served_logits(eng, prompt, n_new, other=None):
    """Logits of slot 0 over its prompt's last position and ``n_new``
    greedy decode steps, from the engine's compiled ``prefill_slot`` and
    ``decode`` programs over the paged arena; ``other`` is a prompt that
    fills slot 1 beside it (else slot 1 stays empty).  Returns (logits
    (n_new + 1, V), the served sequence)."""
    v = eng.cfg.vocab_size
    caches = eng.caches
    table = np.full((2, eng.max_len // eng.kv_block), -1, np.int32)
    table[0] = np.random.default_rng(1).permutation(table.shape[1])
    if other is not None:
        table[1] = table.shape[1] + np.arange(table.shape[1])
    caches["block_table"] = jnp.asarray(table)
    eng.caches = caches
    eng._pin_caches()
    caches = eng.caches

    def prefill(caches, slot, p):
        toks = np.zeros((1, eng.prefill_len), np.int32)
        toks[0, :len(p)] = p
        return eng.programs["prefill_slot"](
            eng.params, caches, jnp.asarray(toks), jnp.asarray(slot, jnp.int32),
            jnp.asarray(len(p), jnp.int32))

    caches, last = prefill(caches, 0, prompt)
    tok1 = 0
    if other is not None:
        caches, last1 = prefill(caches, 1, other)
        tok1 = int(np.argmax(np.asarray(last1)[:v]))
    out = [np.asarray(last)[:v]]
    seq = list(prompt)
    for _ in range(n_new):
        tok = int(np.argmax(out[-1]))
        seq.append(tok)
        caches, nxt, logits = eng.programs["decode"](
            eng.params, caches, jnp.asarray([[tok], [tok1]], jnp.int32))
        tok1 = int(np.asarray(nxt)[1, 0])
        out.append(np.asarray(logits)[0, 0, :v])
    eng.caches = caches
    return np.stack(out), np.asarray(seq, np.int32)


def engine(cfg_json, mcfg=None, n_devices=1, monkeypatch=None):
    """A paged engine of 2 slots over the weights of ``SEED``; ``mcfg``
    puts another block in the program's place."""
    m = registry.get_config("olmoe-1b-7b", reduced=True)
    if mcfg is not None:
        monkeypatch.setattr(registry, "get_config",
                            lambda arch, reduced=False: mcfg)
        m = mcfg
    params = OLMOE.program_params(cfg_json, SEED, m.padded_vocab)
    return ServingEngine("olmoe-1b-7b", EngineConfig(
        reduced=True, batch=2, max_len=32, prefill_len=16, clock="step",
        paging=PagingConfig(kv_block=4), shard=ShardConfig(n_devices)),
        params=params)


def reference(cfg_json, seq, prompt_len):
    w = OLMOE.reference_weights(cfg_json, SEED)
    return OLMOE.reference_logits(cfg_json, w, seq)[prompt_len - 1:]


PROMPT = np.random.default_rng(0).integers(0, 512, 11).astype(np.int32)


def test_engine_prefill_and_paged_decode_match_reference():
    cfg = reduced_config()
    got, seq = served_logits(engine(cfg), PROMPT, 6)
    want = reference(cfg, seq, len(PROMPT))
    np.testing.assert_allclose(got[:len(want)], want, atol=TOL, rtol=0)


@pytest.mark.parametrize("ablation", ["renormalised_top_k", "per_head_qk_norm"])
def test_an_ablated_block_fails_the_tolerance(ablation, monkeypatch):
    m = registry.get_config("olmoe-1b-7b", reduced=True)
    if ablation == "renormalised_top_k":
        m = m.replace(norm_topk_prob=True)
    else:
        m = m.replace(qk_norm=True)
    cfg = reduced_config()
    eng = engine(cfg, m, monkeypatch=monkeypatch)
    if ablation == "per_head_qk_norm":
        # the same norm weights, read as one (hd,) weight per layer
        hd = m.resolved_head_dim
        mix = eng.params["groups"]["slot0"]["mix"]
        for k in ("q_norm", "k_norm"):
            mix[k] = mix[k][:, :hd]
    got, seq = served_logits(eng, PROMPT, 6)
    want = reference(cfg, seq, len(PROMPT))
    assert np.abs(got[:len(want)] - want).max() > 100 * TOL


def test_dropfree_routing_a_request_alone_equals_it_among_full_slots():
    """Every token gets all of its experts, so slot 0's logits do not move
    when slot 1 holds another request."""
    cfg = reduced_config()
    eng = engine(cfg)
    alone, _ = served_logits(eng, PROMPT, 6)
    other = np.random.default_rng(2).integers(0, 512, 14).astype(np.int32)
    among, _ = served_logits(eng, PROMPT, 6, other=other)
    np.testing.assert_array_equal(alone, among)


@pytest.mark.parametrize("field,value", [("qk_norm", True),
                                         ("norm_topk_prob", True),
                                         ("norm_eps", 1e-6),
                                         ("n_experts", 32)])
def test_check_program_config_refuses_another_block(field, value):
    cfg = json.loads((ROOT / "chipbench/configs/olmoe-1b-7b.json")
                     .read_text())
    OLMOE.check_program_config(cfg, CONFIG)
    with pytest.raises(ValueError, match=field):
        OLMOE.check_program_config(cfg, CONFIG.replace(**{field: value}))


def test_the_registry_holds_the_published_model():
    assert (CONFIG.qk_norm, CONFIG.norm_topk_prob, CONFIG.norm_eps) == \
        ("full", False, 1e-5)
    assert (CONFIG.n_experts, CONFIG.experts_per_token, CONFIG.d_ff,
            CONFIG.d_model, CONFIG.n_layers, CONFIG.vocab_size) == \
        (64, 8, 1024, 2048, 16, 50304)
    # per layer: q, k, v, o (2048 x 2048 each), the full-width q and k norm
    # weights (2048 each), the router (2048 x 64) and 64 experts of 3 x
    # 2048 x 1024; the embedding and untied head over the padded 51,200 ids
    attn = 4 * 2048 * 2048 + 2 * 2048
    expert = 3 * 2048 * 1024
    head = 2 * 51200 * 2048
    pc = registry.param_counts(CONFIG)
    assert pc["total"] == head + 16 * (attn + 2048 * 64 + 64 * expert)
    assert pc["active"] == head + 16 * (attn + 2048 * 64 + 8 * expert)
    # 6.92 B published, over 50,304 ids
    assert pc["total"] - 2 * 896 * 2048 == pytest.approx(6.92e9, rel=2e-3)


def test_on_a_four_device_mesh():
    """On 4 CPU devices (one KV head, two experts and a quarter of the
    vocabulary a device), the engine's prefill and paged decode, with the
    paged read as the per-shard kernel (interpreted), match the reference
    and the mesh-less engine; the full-width q norm split over the devices
    equals it unsplit."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT),
                                           str(ROOT / "tests")]))
    code = textwrap.dedent("""
        import json, re
        import numpy as np, jax, jax.numpy as jnp
        from chipbench import spans
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import ops
        from repro.launch.mesh import serving_mesh
        from repro.models.layers import apply_rmsnorm
        from chipbench.tests.olmoe_cells import reduced_config
        import test_olmoe as t

        ops.default_impl = lambda: "interpret"
        cfg = reduced_config(4)
        eng = t.engine(cfg, n_devices=4)
        text = eng.syscore.lookup("decode").compiled.as_text()
        got, seq = t.served_logits(eng, t.PROMPT, 6)
        want = t.reference(cfg, seq, len(t.PROMPT))
        ops.default_impl = lambda: "xla"
        flat, _ = t.served_logits(t.engine(reduced_config()), t.PROMPT, 6)
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((2, 5, 64)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(64) * 0.1, jnp.float32)
        norm = jax.jit(lambda w, x: apply_rmsnorm(w, x, 1e-5))
        mesh = serving_mesh(4)
        split = norm(jax.device_put(w, NamedSharding(mesh, P("model"))),
                     jax.device_put(x, NamedSharding(mesh,
                                                     P(None, None, "model"))))
        print(json.dumps({
            "ref": float(np.abs(got[:len(want)] - want).max()),
            "flat": float(np.abs(got - flat).max()),
            "norm": float(np.abs(np.asarray(split)
                                 - np.asarray(norm(w, x))).max()),
            "devices": len(eng.params["embed"].sharding.device_set),
            "kernel": "paged_decode_attention" in text,
            # full paths; the reducers inside a reduction or a psum carry
            # a short one, and never run as operations of their own
            "moe_scopes": sorted({spans.scope_of(o) for o in
                                  re.findall(r'op_name="(jit[^"]*moe/[^"]*)"',
                                             text)}, key=str),
            "psum": bool(re.search(r'all-reduce[^\\n]*op_name="jit[^"]*mlp/'
                                   r'[^"]*moe/combine', text))}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["kernel"]
    # the MoE layer's operations, psum included, are charged to ``mlp``
    assert res["moe_scopes"] == ["mlp"] and res["psum"], res
    assert res["ref"] < TOL and res["flat"] < TOL, res
    assert res["norm"] < 1e-6, res
