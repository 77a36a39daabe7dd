"""Tensor-parallel serving: token-exactness and per-mesh-shape warm boot.

The sharded engine must be a pure implementation detail: for every model
family and every engine mode (paged/unpaged x plain/speculative/fused
horizons) the token streams of an 8-way tensor-parallel engine must match
the 1-device engine exactly.  These need >1 device, so each check runs in
a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
main test process keeps the real single device per the dry-run isolation
rule).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")

FAMILY_ARCHS = ["qwen3-0.6b", "gemma3-4b", "mamba2-130m",
                "recurrentgemma-2b", "olmoe-1b-7b"]


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


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_tp_engine_token_exact(arch):
    """One family, full mode matrix: the 8-device engine's streams equal
    the 1-device engine's, token for token, for the plain dense engine,
    the dense speculative+fused-horizon engine and the paged
    speculative+fused-horizon engine (speculation and horizons are
    already exactness-preserving vs plain decode, so one plain 1-device
    oracle covers all three)."""
    res = _run(f"""
        import json
        import numpy as np, jax
        from repro.launch.serve import (ServingEngine, EngineConfig,
                                        PagingConfig, SpecConfig,
                                        HorizonConfig, ShardConfig)

        assert jax.device_count() == 8
        base_cfg = EngineConfig(batch=2, max_len=32, prefill_len=8,
                                clock="step")
        base = ServingEngine({arch!r}, base_cfg)
        params = jax.tree.map(np.asarray, base.params)

        def streams(eng):
            rng = np.random.default_rng(0)
            for _ in range(4):
                eng.submit(rng.integers(0, eng.cfg.vocab_size, size=6), 8)
            eng.run()
            return [r.generated for r in sorted(eng.drain_completed(),
                                                key=lambda r: r.rid)]

        want = streams(base)
        tp8 = ShardConfig(n_devices=8)
        modes = {{
            "plain": base_cfg.replace(shard=tp8),
            "spec_horizon": base_cfg.replace(
                shard=tp8, spec=SpecConfig(k=2, ngram=2),
                horizon=HorizonConfig(length=3)),
            "paged_spec_horizon": base_cfg.replace(
                shard=tp8, paging=PagingConfig(kv_block=8),
                spec=SpecConfig(k=2, ngram=2),
                horizon=HorizonConfig(length=3)),
        }}
        got = {{name: streams(ServingEngine({arch!r}, cfg, params=params))
               for name, cfg in modes.items()}}
        print(json.dumps({{"want": want, "got": got}}))
    """)
    for mode, got in res["got"].items():
        assert got == res["want"], (arch, mode)


def test_tp_warm_boot_per_mesh_shape(tmp_path):
    """ProgramStore entries are keyed per mesh shape: a second 8-device
    engine over the same store deserializes every program (compile_s == 0,
    source == "store"), while a 4-device engine over the same store is a
    cold compile — and then warm for ITS shape on the next boot."""
    res = _run(f"""
        import json
        import numpy as np, jax
        from repro.core import ProgramStore
        from repro.launch.serve import (ServingEngine, EngineConfig,
                                        ShardConfig)

        store_dir = {str(tmp_path / "store")!r}
        def boot(n):
            cfg = EngineConfig(batch=2, max_len=32, prefill_len=8,
                               clock="step", store_dir=store_dir,
                               shard=ShardConfig(n_devices=n))
            eng = ServingEngine("qwen3-0.6b", cfg)
            rep = eng.syscore.report()["programs"]
            return {{k: {{"source": v["source"],
                          "compile_s": v["compile_s"],
                          "load_s": v["load_s"]}} for k, v in rep.items()}},\
                   eng.syscore.store.puts

        cold8, puts8 = boot(8)
        warm8, _ = boot(8)
        cold4, puts4 = boot(4)
        warm4, _ = boot(4)
        print(json.dumps({{"cold8": cold8, "warm8": warm8, "puts8": puts8,
                           "cold4": cold4, "warm4": warm4,
                           "puts4": puts4}}))
    """)
    if res["puts8"] == 0:
        pytest.skip("sharded executables not serializable on this backend")
    for name, prog in res["cold8"].items():
        assert prog["source"] == "compile", (name, prog)
    for name, prog in res["warm8"].items():
        assert prog["source"] == "store", (name, prog)
        assert prog["compile_s"] == 0.0 and prog["load_s"] > 0, (name, prog)
    # a DIFFERENT mesh shape over the same store must not revive 8-way
    # executables...
    for name, prog in res["cold4"].items():
        assert prog["source"] == "compile", (name, prog)
    assert res["puts4"] > 0        # the 4-way shape wrote its own entries
    # ...but becomes warm for its own shape
    for name, prog in res["warm4"].items():
        assert prog["source"] == "store", (name, prog)
        assert prog["compile_s"] == 0.0, (name, prog)


def test_tp_mesh_goes_through_serving_mesh():
    """The engine's mesh is THE canonical serving mesh (one constructor,
    repro.launch.mesh.serving_mesh), so the ProgramStore's mesh-shape key
    can never drift between the engine, tests and benchmarks."""
    res = _run("""
        import json
        import jax
        from repro.launch.mesh import serving_mesh
        from repro.launch.serve import ServingEngine, EngineConfig, \
            ShardConfig

        eng = ServingEngine("qwen3-0.6b", EngineConfig(
            batch=2, max_len=32, prefill_len=8, clock="step",
            shard=ShardConfig(n_devices=8)))
        mesh = serving_mesh(8)
        same = (eng.mesh.axis_names == mesh.axis_names
                and eng.mesh.devices.shape == mesh.devices.shape
                and eng.syscore.mesh is eng.mesh)
        print(json.dumps({"same": bool(same),
                          "axis_names": list(mesh.axis_names)}))
    """)
    assert res["same"] and res["axis_names"] == ["model"]


def test_tp_engine_makes_its_cache_on_the_mesh():
    """A sharded engine makes its cache tree inside a program whose outputs
    are the tree's shardings, so no device ever holds a whole leaf (a
    model's arena spread over several chips need not fit one); the
    mesh-less engine makes it as plain arrays."""
    res = _run("""
        import json
        import jax
        from repro.launch.serve import (ServingEngine, EngineConfig,
                                        PagingConfig, ShardConfig)
        from repro.models import transformer

        traced = []
        for name in ("init_paged_cache", "init_cache"):
            make = getattr(transformer, name)
            def spy(*a, make=make, **k):
                tree = make(*a, **k)
                traced.append(all(isinstance(x, jax.core.Tracer)
                                  for x in jax.tree.leaves(tree)))
                # the prefill programs make a fresh cache too, while they
                # compile: the engine's own tree is the last one made
                return tree
            setattr(transformer, name, spy)
        base = EngineConfig(batch=2, max_len=32, prefill_len=8, clock="step")
        out = {}
        for name, cfg in {"paged": base.replace(
                              paging=PagingConfig(kv_block=8)),
                          "dense": base}.items():
            for n in (1, 8):
                traced.clear()
                eng = ServingEngine("qwen3-0.6b", cfg.replace(
                    shard=ShardConfig(n_devices=n)))
                arena = jax.tree.leaves(eng.caches["groups"])[0]
                out[f"{name}{n}"] = {"traced": traced[-1],
                                     "devices": len(arena.sharding.device_set)}
        print(json.dumps(out))
    """)
    for name in ("paged", "dense"):
        assert res[f"{name}1"] == {"traced": False, "devices": 1}, res
        assert res[f"{name}8"] == {"traced": True, "devices": 8}, res
