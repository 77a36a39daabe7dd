"""Figure 2 measured: serial loader vs distributed tree loader.

Runs both loaders on an 8-device host mesh in a CPU subprocess
(``JAX_PLATFORMS=cpu``: the benchmark process may hold the accelerator,
which a child can never share) and reports CPU wall times plus the
host-link byte counts — the quantity the tree design is
about: serial moves N x payload over the host link, tree moves 1 x.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")

_CODE = """
import json, time
import jax, numpy as np
from repro.core import treeload
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
x = rng.standard_normal((512, 512)).astype(np.float32)   # 1 MB payload

def med(fn, n=5):
    fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[n // 2]

t_serial = med(lambda: treeload.serial_load(x, mesh, "data"))
t_tree = med(lambda: treeload.tree_broadcast_replicate(x, mesh, "data"))
ok = bool(np.allclose(
    np.asarray(treeload.tree_broadcast_replicate(x, mesh, "data")[3]), x))
print(json.dumps({"serial_us": t_serial * 1e6, "tree_us": t_tree * 1e6,
                  "payload_mb": x.nbytes / 1e6, "correct": ok}))
"""


def run() -> list:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_CODE)],
                         capture_output=True, text=True, env=env, timeout=600)
    if out.returncode != 0:
        return [("treeload_measured", -1.0, f"ERROR {out.stderr[-200:]}")]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    rows = [
        ("treeload_serial_8dev", r["serial_us"],
         f"us (CPU run); host moves 8x{r['payload_mb']:.0f}MB"),
        ("treeload_tree_8dev", r["tree_us"],
         f"us (CPU run); host moves 1x{r['payload_mb']:.0f}MB + 3 ICI "
         f"rounds; correct={r['correct']}"),
    ]
    return rows
