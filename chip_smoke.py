#!/usr/bin/env python3
"""Smoke run of the serving engine on a TPU, through its normal entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the tensor-parallel path, four chips

One chip: ``qwen3-0.6b`` at its published widths with random weights from
``--seed``, ``EngineConfig(batch=8, max_len=2048)``, in seven phases:

  1. find the chip (a CPU is refused: exit 1, no result line);
  2. cold boot over a cleared program store (``.smoke_store/``);
  3. serve 16 seeded requests, prompts of 16..1024 tokens, 32 new each;
  4. compare streams with the batch-of-1 oracle (``reference_generate``);
  5. warm boot from the store: every program deserialized, none compiled,
     streams bit-equal to the cold engine's;
  6. the paged KV arena with fused decode horizons, same requests;
  7. report.

``--chips 4`` runs only the tensor-parallel comparison: the 1-device
engine and a ``ShardConfig(n_devices=4)`` engine, each booted cold and then
warm, with every parameter and cache leaf of the sharded engine checked to
span all four devices.

Every time and byte count printed is a smoke-run reading, not a benchmark
number.  On success the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failed check exits non-zero without it.  One engine, and so one KV
cache, is alive at a time; all engines share one parameter tree.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

READING = "(smoke-run reading, not a benchmark number)"
# A batch-8 and a batch-1 program tile their matmuls differently on a TPU,
# so a greedy stream may part from the oracle where its top two logits are
# within rounding of each other; this is how close "within rounding" is.
NEAR_TIE_STEPS = 2


@dataclass(frozen=True)
class Smoke:
    """What the smoke run boots and serves.  The defaults are the chip run;
    tests shrink them to drive the same phases on the CPU."""
    arch: str = "qwen3-0.6b"
    reduced: bool = False
    batch: int = 8
    max_len: int = 2048
    requests: int = 16
    min_prompt: int = 16
    max_prompt: int = 1024
    max_new: int = 32
    oracle_streams: int = 2
    kv_block: int = 16
    horizon: int = 8
    tp_devices: int = 4
    seed: int = 0
    store_dir: str = str(REPO / ".smoke_store")

    def config(self, **kw):
        from repro.engine_config import EngineConfig
        return EngineConfig(reduced=self.reduced, batch=self.batch,
                            max_len=self.max_len, clock="step",
                            seed=self.seed, store_dir=self.store_dir, **kw)

    def prompts(self, vocab: int) -> List[np.ndarray]:
        rng = np.random.default_rng(self.seed)
        lens = np.linspace(self.min_prompt, self.max_prompt,
                           self.requests).round().astype(int)
        rng.shuffle(lens)           # long and short prompts share each wave
        return [rng.integers(1, vocab, size=int(n)).astype(np.int32)
                for n in lens]


class Checks:
    """Collects failed checks; a phase goes on after one so that a single
    chip run reports every fault it can."""

    def __init__(self):
        self.failures: List[str] = []

    def __call__(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            print(f"  FAIL: {what}", flush=True)
        return ok


def _boot(smoke: Smoke, label: str, params=None, **config):
    from repro.launch.serve import ServingEngine
    t0 = time.perf_counter()
    eng = ServingEngine(smoke.arch, smoke.config(**config), params=params)
    boot_s = time.perf_counter() - t0
    programs = eng.syscore.report()["programs"]
    for name, p in programs.items():
        print(f"  {label} program {name}: source={p['source']} "
              f"lower_s={p['lower_s']:.3f} compile_s={p['compile_s']:.3f} "
              f"load_s={p['load_s']:.3f} store_s={p['store_s']:.3f} "
              f"bytes={p['serialized_bytes']}", flush=True)
    in_programs = sum(p["lower_s"] + p["compile_s"] + p["load_s"]
                      + p["store_s"] for p in programs.values())
    print(f"  {label} boot_s={boot_s:.2f}, of which outside the programs' "
          f"lower/compile/load/store (weights, caches) "
          f"{boot_s - in_programs:.2f} {READING}", flush=True)
    return eng, boot_s


def _serve(eng, prompts, max_new: int, label: str, check: Checks):
    """Serve every prompt to completion; every stream must be full length
    and every id a real vocabulary id."""
    reqs = [eng.submit(p, max_new) for p in prompts]
    check(all(r is not None for r in reqs), f"{label}: a request was refused")
    reqs = [r for r in reqs if r is not None]
    stats = eng.run()
    vocab = eng.cfg.vocab_size
    for r in reqs:
        check(r.done and len(r.generated) == max_new,
              f"{label}: request {r.rid} finished={r.done} with "
              f"{len(r.generated)} of {max_new} tokens")
        check(all(0 <= t < vocab for t in r.generated),
              f"{label}: request {r.rid} emitted an id outside [0, {vocab})")
    print(f"  {label}: requests={stats['requests']} "
          f"decode_tokens={stats['decode_tokens']} "
          f"decode_steps={stats['decode_steps']} "
          f"wall_s={stats['wall_s']:.3f} {READING}", flush=True)
    return [list(r.generated) for r in reqs], stats


def _check_warm(eng, label: str, check: Checks):
    """Every program of a warm boot came from the store, and the store
    neither missed nor failed to read, deserialize or serialize anything."""
    for name, p in eng.syscore.report()["programs"].items():
        check(p["source"] == "store" and p["compile_s"] == 0,
              f"{label}: program {name} source={p['source']} "
              f"compile_s={p['compile_s']}")
    st = eng.syscore.store
    check(st.misses == 0 and st.errors == 0 and st.skipped == 0,
          f"{label}: store misses={st.misses} errors={st.errors} "
          f"skipped={st.skipped}")


def _first_divergence(a, b) -> int:
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def _reference_logits(eng, prompt, stream, at: int) -> np.ndarray:
    """The batch-of-1 oracle's logits where it emits ``stream[at]``,
    replayed through the oracle engine's own programs."""
    import jax.numpy as jnp
    ref = eng._ref_engine
    tokens = np.zeros((1, ref.prefill_len), np.int32)
    tokens[0, :len(prompt)] = prompt
    ref.caches, logits = ref._prefill_slot(
        ref.params, ref.caches, jnp.asarray(tokens),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(prompt), jnp.int32))
    for tok in stream[:at]:
        ref.caches, _, lg = ref._decode(ref.params, ref.caches,
                                        jnp.asarray([[tok]], jnp.int32))
        logits = lg[0, 0]
    return np.asarray(logits, np.float32)[:eng.cfg.vocab_size]


def _near_tie(logits: np.ndarray, token: int, dtype) -> tuple:
    """Whether ``token`` is the runner-up of ``logits`` and trails the top
    by at most ``NEAR_TIE_STEPS`` rounding steps of the model dtype at the
    top logit's magnitude; returns (near_tie, margin)."""
    import jax.numpy as jnp
    second, first = np.argsort(logits)[-2:]
    margin = float(logits[first] - logits[second])
    step = float(jnp.finfo(dtype).eps) * abs(float(logits[first]))
    return int(second) == token and margin <= NEAR_TIE_STEPS * step, margin


def _oracle(eng, prompts, streams, n: int, label: str, check: Checks):
    """Compare ``n`` streams, shortest and longest prompt first, with the
    batch-of-1 reference decode.  A stream must equal the reference up to
    its first near-tie: where they part, the engine's token must be the
    reference's runner-up within rounding of the top logit."""
    order = np.argsort([len(p) for p in prompts])
    picks = [int(order[0]), int(order[-1])]
    picks += [int(i) for i in order[1:-1][:max(n - 2, 0)]]
    for i in picks[:n]:
        what = f"{label} oracle: request {i} (prompt {len(prompts[i])})"
        ref = eng.reference_generate(prompts[i], len(streams[i]))
        if ref == streams[i]:
            print(f"  {what} matches the batch-of-1 reference, "
                  f"{len(ref)} tokens", flush=True)
            continue
        at = _first_divergence(ref, streams[i])
        logits = _reference_logits(eng, prompts[i], ref, at)
        tie, margin = _near_tie(logits, streams[i][at], eng.cfg.dtype)
        msg = (f"{what} equals the batch-of-1 reference for {at} tokens, "
               f"then engine {streams[i][at]} vs reference {ref[at]}, "
               f"reference top-2 logit margin {margin!r}")
        if check(tie, msg + " — not a near-tie"):
            print(f"  {msg} (a near-tie)", flush=True)


def _compare(label, streams, other, other_streams, prompts):
    """Print how many streams equal another engine's, and where the
    others first differ."""
    same = sum(a == b for a, b in zip(streams, other_streams))
    print(f"  {label} vs {other}: {same} of {len(streams)} streams equal",
          flush=True)
    for i, (a, b) in enumerate(zip(streams, other_streams)):
        if a != b:
            print(f"  {label} vs {other}: request {i} (prompt "
                  f"{len(prompts[i])}) first differs at generated position "
                  f"{_first_divergence(a, b)}", flush=True)


def _peak_bytes() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(smoke: Smoke) -> List[str]:
    """Phases 2-7 on one device; returns the failed checks."""
    from repro.engine_config import HorizonConfig, PagingConfig
    check = Checks()
    shutil.rmtree(smoke.store_dir, ignore_errors=True)

    print("[2/7] cold boot", flush=True)
    eng, cold_s = _boot(smoke, "cold")
    st = eng.syscore.store
    check(st.errors == 0 and st.skipped == 0 and st.puts == len(eng.programs),
          f"cold: store puts={st.puts} of {len(eng.programs)} programs, "
          f"errors={st.errors} skipped={st.skipped}")
    prompts = smoke.prompts(eng.cfg.vocab_size)
    params = eng.params

    print(f"[3/7] serve {len(prompts)} requests, prompts "
          f"{min(map(len, prompts))}..{max(map(len, prompts))} tokens, "
          f"max_new={smoke.max_new}", flush=True)
    streams, stats = _serve(eng, prompts, smoke.max_new, "dense", check)

    print("[4/7] batch-of-1 oracle", flush=True)
    _oracle(eng, prompts, streams, smoke.oracle_streams, "dense", check)
    del eng       # its KV cache and memoized oracle go with it
    gc.collect()

    print("[5/7] warm boot from the program store", flush=True)
    warm, warm_s = _boot(smoke, "warm", params=params)
    _check_warm(warm, "warm", check)
    warm_streams, _ = _serve(warm, prompts, smoke.max_new, "warm", check)
    check(warm_streams == streams,
          f"warm: {sum(a == b for a, b in zip(warm_streams, streams))} of "
          f"{len(streams)} streams equal the cold engine's")
    del warm
    gc.collect()

    print(f"[6/7] paged arena (kv_block={smoke.kv_block}) with fused "
          f"horizons (length={smoke.horizon})", flush=True)
    paged, paged_s = _boot(smoke, "paged", params=params,
                           paging=PagingConfig(kv_block=smoke.kv_block),
                           horizon=HorizonConfig(length=smoke.horizon))
    paged_streams, _ = _serve(paged, prompts, smoke.max_new, "paged", check)
    _compare("paged", paged_streams, "dense", streams, prompts)
    _oracle(paged, prompts, paged_streams, smoke.oracle_streams, "paged",
            check)
    del paged
    gc.collect()

    print("[7/7] report", flush=True)
    print(f"  boot_s cold={cold_s:.2f} warm={warm_s:.2f} "
          f"paged={paged_s:.2f} {READING}")
    print(f"  decode_tokens={stats['decode_tokens']} (dense run) {READING}")
    print(f"  peak_bytes_in_use={_peak_bytes()} {READING}", flush=True)
    return check.failures


def _spans(tree, n: int) -> List[str]:
    """Paths of the leaves that do not span ``n`` devices."""
    import jax
    return [jax.tree_util.keystr(path) for path, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]
            if len(x.sharding.device_set) != n]


def run_four_chips(smoke: Smoke) -> List[str]:
    """The 1-device engine and the tensor-parallel engine over
    ``smoke.tp_devices`` devices, each booted cold then warm; returns the
    failed checks."""
    import jax
    from repro.engine_config import ShardConfig
    n = smoke.tp_devices
    check = Checks()
    shutil.rmtree(smoke.store_dir, ignore_errors=True)

    print("[2/3] 1-device engine", flush=True)
    one, _ = _boot(smoke, "1dev cold")
    prompts = smoke.prompts(one.cfg.vocab_size)
    params = one.params
    ref_streams, _ = _serve(one, prompts, smoke.max_new, "1dev cold", check)
    del one
    gc.collect()
    one, _ = _boot(smoke, "1dev warm", params=params)
    _check_warm(one, "1dev warm", check)
    streams, _ = _serve(one, prompts, smoke.max_new, "1dev warm", check)
    check(streams == ref_streams, "1dev warm: streams differ from cold")
    del one
    gc.collect()

    print(f"[3/3] tensor-parallel engine, ShardConfig(n_devices={n})",
          flush=True)
    shard = ShardConfig(n_devices=n)
    tp, _ = _boot(smoke, f"tp{n} cold", params=params, shard=shard)
    tp_streams, _ = _serve(tp, prompts, smoke.max_new, f"tp{n} cold", check)
    for what, tree in (("parameter", tp.params), ("cache", tp.caches)):
        off = _spans(tree, n)
        check(not off, f"tp{n}: {len(off)} {what} leaves do not span {n} "
                       f"devices: {off[:4]}")
        if not off:
            print(f"  tp{n}: every {what} leaf "
                  f"({len(jax.tree.leaves(tree))}) spans {n} devices",
                  flush=True)
    del tp
    gc.collect()
    tp, _ = _boot(smoke, f"tp{n} warm", params=params, shard=shard)
    _check_warm(tp, f"tp{n} warm", check)
    warm_streams, _ = _serve(tp, prompts, smoke.max_new, f"tp{n} warm",
                             check)
    check(warm_streams == tp_streams, f"tp{n} warm: streams differ from cold")
    del tp
    gc.collect()

    _compare(f"tp{n}", tp_streams, "1dev", ref_streams, prompts)
    print(f"  peak_bytes_in_use(device 0)={_peak_bytes()} {READING}",
          flush=True)
    return check.failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the "
                         "tensor-parallel engine against the 1-device one")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not under {REPO / 'src'}: "
              f"{e}", file=sys.stderr)
        return 2
    use_compile_cache()

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r});"
              f" refusing to run on it", file=sys.stderr)
        return 1
    print(f"[1/{3 if args.chips == 4 else 7}] chip: platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devices)}", flush=True)
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    smoke = Smoke(seed=args.seed)
    failures = (run_four_chips(smoke) if args.chips == 4
                else run_one_chip(smoke))
    if failures:
        print(f"chip_smoke: {len(failures)} check(s) failed:",
              file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
