#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from.

    python3 chipbench/control.py --workload qwen3-0.6b.chat \\
        --seconds 12 --seeds 101 102 103 [--fault state_unchanged]

For each seed, in one process, one run of the cell (``bench.run``) with a
window of ``--seconds`` at the cell's own load and the control beside the
check.  Prints one JSON line per seed: the program's verdict and readings
(the widest gap by which a served token's reference logit lies below the
reference's best) and the control's, put through the same comparison:
the token that the reference computed in float8 (e4m3) puts first, at
every position of the same requests, in the program's place.

``--fault`` plants one of :data:`FAULTS` in the timed path first; its
programs are compiled into a store of their own.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def state_unchanged(patch):
    """A decode step that returns its key/value state unchanged: the paged
    arena is never written after admission."""
    from repro.models import attention
    patch(attention, "write_paged_kv", lambda arena, *a, **k: arena)


def altered_token(patch):
    """A token altered where it is produced: every decode step's greedy
    pick moves one id up."""
    from repro.models import transformer
    greedy = transformer.greedy_token
    patch(transformer, "greedy_token",
          lambda cfg, logits: (greedy(cfg, logits) + 1) % cfg.vocab_size)


FAULTS = {"state_unchanged": state_unchanged, "altered_token": altered_token}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    from chipbench import bench, run
    cell = bench.load_cell(args.workload)
    why = run.find_chips(cell.chips)
    if why:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    bench.use_caches()
    store = bench.STORE_DIR
    if args.fault:
        FAULTS[args.fault](setattr)
        store = bench.HERE / ".store-faults" / args.fault
    for seed in args.seeds:
        out = bench.run(cell, seed, args.seconds, False, time.perf_counter(),
                        store_dir=store, control=True)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": out["correct"], "checks": out["checks"],
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
