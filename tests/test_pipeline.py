"""Pipeline-parallel forward: correctness vs sequential stage application."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.runtime.pipeline import bubble_fraction

SRC = str(Path(__file__).resolve().parent.parent / "src")


def test_bubble_fraction():
    assert bubble_fraction(2, 14) == 2 / 16 * 1 / 1 or True
    assert abs(bubble_fraction(2, 14) - 1 / 15) < 1e-9
    assert bubble_fraction(1, 8) == 0.0


def test_pipeline_forward_matches_sequential():
    code = """
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime.pipeline import pipeline_forward
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((4,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        S, M, B, D = 4, 6, 2, 16
        # each stage: x -> tanh(x @ w + b)
        ws = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)
        bs = jnp.asarray(rng.standard_normal((S, D)) * 0.1, jnp.float32)
        params = {"w": jax.device_put(ws, NamedSharding(mesh, P("pod"))),
                  "b": jax.device_put(bs, NamedSharding(mesh, P("pod")))}
        x = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)

        def stage(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        got = pipeline_forward(stage, params, x, mesh, axis="pod")
        # sequential reference
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s] + bs[s])
        ok = bool(np.allclose(np.asarray(got), np.asarray(ref),
                              rtol=1e-5, atol=1e-5))
        print(json.dumps({"ok": ok}))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
