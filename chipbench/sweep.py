#!/usr/bin/env python3
"""Find the knee of an open-loop cell: run its mix at several rates.

    python3 chipbench/sweep.py --workload qwen3-0.6b.chat --seed 11 \\
        --seconds 30 --rates 2 3 4 5

One process, one engine; each rate gets a window of ``--seconds`` and a
drain.  Prints one JSON line per rate: requests offered and finished in
the window, requests still waiting at its close, TTFT p50 and p95 (and
p95 over the last third of the window's arrivals, which grows when the
backlog does), ITL p95 and output tokens per second.  The knee is the
highest rate whose backlog does not grow; a cell's rate is fixed at four
fifths of it, in its mix file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import bench, run
    cell = bench.load_cell(args.workload)
    why = run.find_chips(cell.chips)
    if why:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    import numpy as np
    bench.use_caches()
    eng, recorder = bench.build_engine(cell, args.seed)
    bench.warm_up(eng, eng.cfg.vocab_size)
    for rate in args.rates:
        mix = dict(cell.mix, rate_per_s=rate)
        t = time.perf_counter()
        win = bench.run_window(eng, recorder, mix, args.seed, args.seconds,
                               eng.cfg.vocab_size)
        reqs = sorted(win.requests.values(), key=lambda r: r.due)
        waiting = sum(1 for r in reqs
                      if not r.token_times or r.token_times[0] > win.t_end)
        last = [r.token_times[0] - r.due for r in reqs[2 * len(reqs) // 3:]
                if r.token_times]
        ttft = bench.ttft_s(win)
        print(json.dumps({
            "rate_per_s": rate, "offered": len(reqs),
            "finished_in_window": sum(1 for r in reqs if r.req.done and
                                      r.token_times[-1] <= win.t_end),
            "waiting_at_close": waiting,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p95_ms": 1e3 * bench.pct(ttft, 95),
            "ttft_p95_last_third_ms": 1e3 * bench.pct(last, 95),
            "itl_p95_ms": 1e3 * bench.pct(bench.itl_s(win), 95),
            "output_tok_s": bench.output_tokens_in_window(win) / win.seconds,
            "unserved_at_cap": win.unserved,
            "memory_peak_bytes": bench.peak_bytes(),
            "wall_s": time.perf_counter() - t}), flush=True)
        # what is still decoding ends before the next rate starts
        while eng.step():
            pass
        eng.drain_completed()
    return 0


if __name__ == "__main__":
    sys.exit(main())
