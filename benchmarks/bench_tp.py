"""Tensor-parallel serving benchmark: decode throughput vs device count.

The sharded engine compiles its five hot-loaded programs against a 1-D
``serving_mesh`` (``ShardConfig.n_devices``), sharding weights and KV over
heads / head_dim while the host-side scheduler stays mesh-agnostic.  This
bench serves the same deterministic workload at n_devices ∈ {1, 2, 4, 8}
and records the decode-throughput trajectory into ``BENCH_tp.json``.

Each cell runs in a subprocess on the CPU backend (``JAX_PLATFORMS=cpu``):
device count on the host platform is fixed at process start
(``--xla_force_host_platform_device_count``), so a single process cannot
sweep it — and a child must never claim an accelerator the parent may
already hold.  Every number here is therefore a CPU run;
``chip_smoke.py --chips 4`` is the tensor-parallel path on real chips.

Every cell boots TWICE against one shared ProgramStore — the second boot
must deserialize every program
(``compile_s == 0``), demonstrating per-mesh-shape warm boot — and every
cell's token streams are asserted identical to the 1-device engine's.

Honesty note: forced host-platform devices are threads over the same CPU,
so real speedup needs real cores.  The monotonic-throughput gate is only
asserted when the host has at least as many cores as the largest device
count; below that the trajectory is recorded with ``scaling_gated:
false`` (the token-exactness and warm-boot asserts always run).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
TP_JSON = REPO / "BENCH_tp.json"

DEVICE_COUNTS = (1, 2, 4, 8)

_CELL = """
    import json
    import numpy as np, jax
    from repro.launch.serve import (ServingEngine, EngineConfig,
                                    ShardConfig, METRIC_DECODE_MS)

    n = {n}
    assert jax.device_count() == n, (jax.device_count(), n)
    config = EngineConfig(batch={batch}, max_len={max_len},
                          prefill_len={prefill_len}, clock="step", seed=0,
                          store_dir={store_dir!r},
                          shard=ShardConfig(n_devices=n))
    eng = ServingEngine({arch!r}, config)
    boot = {{k: {{"source": v["source"], "compile_s": v["compile_s"],
                  "load_s": v["load_s"]}}
             for k, v in eng.syscore.report()["programs"].items()}}

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, eng.cfg.vocab_size, size=8)
               for _ in range({batch})]
    # warm the decode path (first executions pay one-off lazy costs)
    eng.submit(prompts[0][:4], max_new=4)
    eng.run()
    eng.drain_completed()

    best_tps, streams = 0.0, None
    for _ in range({repeats}):
        reqs = [eng.submit(p, max_new={max_new}) for p in prompts]
        stats = eng.run()
        assert stats["requests"] == {batch}, stats
        rep = [r.generated for r in reqs]
        assert streams is None or streams == rep
        streams = rep
        dec_s = sum(eng.syscore.hostcalls.metrics[METRIC_DECODE_MS]) / 1e3
        eng.drain_completed()
        best_tps = max(best_tps, stats["decode_tokens"] / max(dec_s, 1e-9))
    print(json.dumps({{"n": n, "decode_tok_per_s": best_tps,
                       "streams": streams, "boot": boot,
                       "backend": jax.default_backend()}}))
"""


def _run_cell(n: int, *, arch, store_dir, batch, max_len, prefill_len,
              max_new, repeats) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    code = textwrap.dedent(_CELL.format(
        n=n, arch=arch, store_dir=store_dir, batch=batch, max_len=max_len,
        prefill_len=prefill_len, max_new=max_new, repeats=repeats))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(smoke: bool = False, arch: str = "qwen3-0.6b"):
    batch, max_len, prefill_len = 2, 128, 16
    max_new = 32 if smoke else 64
    repeats = 2 if smoke else 4
    counts = DEVICE_COUNTS[:3] if smoke else DEVICE_COUNTS

    results = {}
    with tempfile.TemporaryDirectory() as store_dir:
        kw = dict(arch=arch, store_dir=store_dir, batch=batch,
                  max_len=max_len, prefill_len=prefill_len,
                  max_new=max_new, repeats=repeats)
        for n in counts:
            cold = _run_cell(n, **kw)
            warm = _run_cell(n, **kw)
            # warm boot per mesh shape: the SECOND process over the same
            # store deserializes every program for THIS device count
            warm_ok = all(p["source"] == "store" and p["compile_s"] == 0.0
                          for p in warm["boot"].values())
            assert warm["streams"] == cold["streams"], n
            results[n] = {
                "decode_tok_per_s": max(cold["decode_tok_per_s"],
                                        warm["decode_tok_per_s"]),
                "warm_boot_from_store": warm_ok,
                "cold_sources": sorted({p["source"]
                                        for p in cold["boot"].values()}),
                "streams": cold["streams"],
                "backend": cold["backend"],
            }

    # token-exactness across every device count — TP is an implementation
    # detail, never a numerics change the argmax can see
    token_exact = all(results[n]["streams"] == results[counts[0]]["streams"]
                      for n in counts)
    assert token_exact, "sharded engine diverged from the 1-device engine"
    warm_boot_ok = all(results[n]["warm_boot_from_store"] for n in counts)
    assert warm_boot_ok, {n: results[n]["warm_boot_from_store"]
                          for n in counts}
    for n in counts:
        results[n].pop("streams")

    host_cores = os.cpu_count() or 1
    scaling_gated = host_cores >= counts[-1]
    speedup = (results[counts[-1]]["decode_tok_per_s"]
               / results[counts[0]]["decode_tok_per_s"])

    record = {
        "bench": "tp",
        "arch": f"{arch}(reduced)",
        "batch": batch,
        "max_len": max_len,
        "prefill_len": prefill_len,
        "workload": {"requests": batch, "max_new": max_new,
                     "repeats": repeats},
        "host_cores": host_cores,
        "scaling_gated": scaling_gated,
        "device_counts": {str(n): results[n] for n in counts},
        "speedup_max_devices": speedup,
        "token_exact": token_exact,
        "warm_boot_per_mesh_shape": warm_boot_ok,
        "env": {"jax": __import__("jax").__version__,
                "backend": results[counts[0]]["backend"]},
    }
    TP_JSON.write_text(json.dumps(record, indent=2) + "\n")
    if scaling_gated:
        assert speedup > 1.0, (speedup, record)
    return [
        ("tp_decode_speedup", speedup,
         f"{results[counts[-1]]['decode_tok_per_s']:.0f} tok/s at "
         f"{counts[-1]} dev vs {results[counts[0]]['decode_tok_per_s']:.0f}"
         f" at 1 (CPU run, forced host devices; host_cores={host_cores}, "
         f"gated={scaling_gated}) -> {TP_JSON.name}"),
        ("tp_token_exact", float(token_exact),
         f"streams identical across n_devices={list(counts)}"),
        ("tp_warm_boot_per_mesh_shape", float(warm_boot_ok),
         "second boot per device count deserializes every program "
         "(compile_s == 0)"),
    ]


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--arch", default="qwen3-0.6b")
    args = ap.parse_args()
    for name, value, derived in run(smoke=args.smoke, arch=args.arch):
        print(f"{name},{value:.3f},{derived}")


if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    main()
