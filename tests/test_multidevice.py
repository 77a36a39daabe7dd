"""Multi-device correctness: tree loader, sharded MoE parity, elastic reshard.

These need >1 device, so each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps the real single device per the dry-run isolation rule).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    last = out.stdout.strip().splitlines()[-1]
    return json.loads(last)


def test_tree_broadcast_equals_serial():
    res = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import treeload
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        x = rng.standard_normal((32, 16)).astype(np.float32)
        tree = treeload.tree_broadcast_replicate(x, mesh, "data")
        serial = treeload.serial_load(x, mesh, "data")
        ok_tree = all(np.allclose(np.asarray(tree[i]), x) for i in range(8))
        ok_match = np.allclose(np.asarray(tree), np.asarray(serial))
        print(json.dumps({"ok_tree": bool(ok_tree), "ok_match": bool(ok_match)}))
    """)
    assert res["ok_tree"] and res["ok_match"]


def test_tree_broadcast_round_structure():
    """log2(N) rounds: with 8 replicas the payload reaches everyone in 3
    ppermute rounds; check the compiled HLO contains exactly 3."""
    res = _run("""
        import json, re
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import treeload
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = jnp.zeros((8, 4, 4))
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        f = jax.jit(lambda a: treeload.tree_broadcast_stacked(a, mesh, "data"))
        txt = f.lower(xs).compile().as_text()
        n = len(re.findall(r" collective-permute\\(", txt))
        print(json.dumps({"permutes": n}))
    """)
    assert res["permutes"] == 3


def test_checkpoint_restore_with_tree_broadcast(tmp_path):
    res = _run(f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import save_checkpoint, load_checkpoint
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        tree = {{"a": jnp.arange(12.0).reshape(3, 4), "b": {{"c": jnp.ones(5)}}}}
        save_checkpoint("{tmp_path}", 7, tree)
        like = jax.tree.map(lambda x: x, tree)
        restored, step = load_checkpoint("{tmp_path}", like, mesh=mesh,
                                         broadcast_axis="data")
        ok = all(np.allclose(np.asarray(a), np.asarray(b))
                 for a, b in zip(jax.tree.leaves(tree),
                                 jax.tree.leaves(restored)))
        print(json.dumps({{"ok": bool(ok), "step": step}}))
    """)
    assert res["ok"] and res["step"] == 7


def test_moe_sharded_matches_single_device():
    """apply_moe under a (data=2, model=4) mesh == single-device body."""
    res = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import registry, moe
        from repro.sharding import make_rules, tree_shardings
        cfg = registry.get_config("olmoe-1b-7b", reduced=True)
        rules = make_rules()
        rng = np.random.default_rng(0)
        b, s, d = 4, 8, cfg.d_model
        e, f = cfg.n_experts, cfg.d_ff
        x = jnp.asarray(rng.standard_normal((b, s, d)) * 0.1, jnp.float32)
        p = {"router": jnp.asarray(rng.standard_normal((d, e)) * 0.1, jnp.float32),
             "w_gate": jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32),
             "w_up": jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32),
             "w_down": jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32)}
        # single-device reference
        ref, aux_ref = moe.apply_moe(cfg, p, x, rules)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            got, aux = jax.jit(lambda p, x: moe.apply_moe(cfg, p, x, rules))(p, x)
        # capacities differ (local T), so compare with loose tolerance on the
        # overlap: routing is identical, drops may differ near capacity
        close = np.mean(np.isclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-3, atol=2e-3))
        # aux: reduction order differs (pmean of local means) -> f32 noise
        print(json.dumps({"frac_close": float(close),
                          "aux_close": bool(abs(float(aux) - float(aux_ref))
                                            < 2e-2 * max(1.0, float(aux_ref)))}))
    """)
    assert res["frac_close"] > 0.95, res
    assert res["aux_close"]


def test_elastic_reshard_preserves_values():
    res = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime import ElasticPlan, reshard_tree
        from repro.sharding import LogicalArray, make_rules
        mesh_big = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
        mesh_small = jax.make_mesh((1, 4), ("data", "model"),
                                   axis_types=(jax.sharding.AxisType.Auto,) * 2)
        abstract = {"w": LogicalArray((8, 16), jnp.float32, ("embed_fsdp", "ff"))}
        rules = make_rules(fsdp=True)
        from repro.sharding import tree_shardings
        w = jnp.arange(128.0).reshape(8, 16)
        big = jax.device_put(w, jax.tree.leaves(
            tree_shardings(abstract, rules, mesh_big))[0])
        plan = ElasticPlan({"data": 2, "model": 4}, {"data": 1, "model": 4})
        plan.validate()
        small = reshard_tree(abstract, {"w": big}, rules, mesh_small)
        ok = np.allclose(np.asarray(small["w"]), np.asarray(w))
        print(json.dumps({"ok": bool(ok),
                          "batch_advice": plan.batch_advice(256)}))
    """)
    assert res["ok"] and res["batch_advice"] == 128


def test_elastic_plan_rejects_model_axis_change():
    from repro.runtime import ElasticPlan
    plan = ElasticPlan({"data": 2, "model": 4}, {"data": 2, "model": 8})
    with pytest.raises(ValueError):
        plan.validate()
