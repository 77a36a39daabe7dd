#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload qwen3-0.6b.chat --seed 7 \\
        --seconds 51 --trace 0

Prints the run's checks on standard error and, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, last, ``checks``.  Without an
accelerator, or with fewer chips than the cell needs, it exits 1 and
prints no result.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path


def _process_start() -> float:
    """This process's start on the ``perf_counter`` clock."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def find_chips(n: int):
    """None when JAX sees at least ``n`` accelerator chips of a kind the
    peaks table knows, else why not."""
    import json
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return f"no accelerator: {e}"
    if devices[0].platform == "cpu":
        return "no accelerator: JAX runs on the cpu"
    if len(devices) < n:
        return f"the cell needs {n} chips, JAX sees {len(devices)}"
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    if devices[0].device_kind not in peaks["devices"]:
        return (f"no peaks for device kind {devices[0].device_kind!r} in "
                f"chipbench/peaks.json")
    return None


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import bench
    cell = bench.load_cell(args.workload)
    why = find_chips(cell.chips)
    if why:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    bench.use_caches()
    result = bench.run(cell, args.seed, args.seconds, bool(args.trace),
                       T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
