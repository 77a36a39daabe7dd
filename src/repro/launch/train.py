"""End-to-end training driver on the persistent executor.

Wires every layer of the system together (this is example (b)'s engine):

  syscore (C2)    — the train program is hot-loaded once, then re-executed
  hostcall (C5)   — per-step loss/step-time telemetry from inside jit
  checkpoint + treeload (C3) — durable saves; restore disseminates over ICI
  runtime         — restart-on-failure supervision, straggler monitor
  data            — deterministic restartable pipeline

CPU-scale by default (reduced configs); the same driver drives the production
mesh when devices exist.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import steps as steps_lib
from repro.checkpoint import CheckpointManager
from repro.core import (CALL_STEP_REPORT, Syscore)
from repro.data import DataConfig, TokenPipeline
from repro.models import registry
from repro.optim import AdamWConfig
from repro.runtime import FaultInjector, StragglerMonitor, run_with_restarts
from repro.sharding import make_rules, LogicalArray
from repro.models.registry import _batch_abstract


def build_abstract_state(cfg):
    from repro.optim import adamw_abstract_state
    mod = steps_lib.model_module(cfg)
    params = mod.abstract_params(cfg)
    return {"params": params, "opt": adamw_abstract_state(params)}


def train(arch: str, *, reduced: bool = True, steps: int = 100,
          global_batch: int = 8, seq_len: int = 128, ckpt_dir="/tmp/repro_ckpt",
          ckpt_every: int = 25, fail_at=(), lr: float = 1e-3,
          accum: int = 1, mesh=None, log_every: int = 10,
          seed: int = 0, max_restarts: int = 4,
          in_graph_telemetry: bool = True):
    cfg = registry.get_config(arch, reduced=reduced)
    rules = make_rules()
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=20, total_steps=steps)

    monitor = StragglerMonitor()
    injector = FaultInjector(list(fail_at))
    manager = CheckpointManager(ckpt_dir, keep=2)
    # the checkpoint dir's program store is the job's global-memory tier: a
    # restarted run hot-loads its train program by deserialization exactly
    # as it restores weights (programs with in-graph hostcalls cannot be
    # serialized — the store skips them; pass in_graph_telemetry=False for
    # a warm-bootable train program with host-side step reports instead)
    sys_core = Syscore(mesh=mesh, rules=rules, store=manager.program_store)

    # telemetry flows through the numbered hostcall ABI
    hct = sys_core.hostcalls

    data = DataConfig(global_batch=global_batch, seq_len=seq_len, seed=seed)
    pipeline = TokenPipeline(cfg, data)

    # ---- hot-load the train program once (C2) -----------------------------
    abstract_state = build_abstract_state(cfg)
    abstract_batch = _batch_abstract(cfg, seq_len, global_batch,
                                     with_labels=True)

    base_step = steps_lib.make_train_step(cfg, rules, opt_cfg, accum=accum)

    def train_step(state, batch):
        new_state, metrics = base_step(state, batch)
        # in-graph telemetry through the numbered hostcall ABI (C5):
        # the device blocks until the host daemon records the report.
        hct.hostcall(CALL_STEP_REPORT, new_state["opt"]["step"],
                     metrics["loss"])
        return new_state, metrics

    spec = steps_lib.train_program_spec(
        cfg, rules, opt_cfg, abstract_state, abstract_batch, accum=accum,
        fn=train_step if in_graph_telemetry else None)
    train_prog = sys_core.hot_load(spec)

    losses = []

    def loop(start_step: int) -> int:
        if manager.has_checkpoint():
            state, at = manager.restore(build_abstract_state(cfg),
                                        mesh=mesh, broadcast_axis="data")
            start_step = at + 1
        else:
            state = steps_lib.init_train_state(cfg, jax.random.PRNGKey(seed))
        for step, batch in pipeline.run(start_step, steps - start_step):
            injector.check(step)
            t0 = time.perf_counter()
            state, metrics = train_prog(state, batch)
            loss = float(metrics["loss"])
            wall = time.perf_counter() - t0
            if not in_graph_telemetry:
                # same (step, loss) payload as the in-graph hostcall so the
                # CALL_STEP_REPORT channel is mode-independent
                hct.dispatch(CALL_STEP_REPORT, step, loss)
            monitor.observe(wall)
            losses.append(loss)
            if step % log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"wall {wall*1e3:.1f}ms", flush=True)
            if step and step % ckpt_every == 0:
                manager.save(step, state, syscore=sys_core)
        manager.save(steps - 1, state, syscore=sys_core)
        return steps - 1

    def resume_step() -> int:
        from repro.checkpoint.checkpoint import latest_step
        s = latest_step(ckpt_dir)
        return 0 if s is None else s + 1

    result = run_with_restarts(
        loop, resume_step_fn=resume_step, max_restarts=max_restarts,
        on_restart=lambda n, e: print(f"[restart {n}] {e} — restoring from "
                                      f"checkpoint via tree loader", flush=True))
    result.update({
        "final_loss": losses[-1] if losses else float("nan"),
        "first_loss": losses[0] if losses else float("nan"),
        "straggler": monitor.summary(),
        "programs": sys_core.report()["programs"],
        "program_store": sys_core.store.report(),
        "telemetry_points": len(hct.step_times),
    })
    return result


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--host-telemetry", action="store_true",
                    help="report step telemetry host-side instead of via "
                         "in-graph hostcall, which makes the train program "
                         "serializable into the checkpoint's program store")
    args = ap.parse_args()
    res = train(args.arch, reduced=args.reduced, steps=args.steps,
                global_batch=args.batch, seq_len=args.seq, accum=args.accum,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                fail_at=args.fail_at, lr=args.lr,
                in_graph_telemetry=not args.host_telemetry)
    print({k: v for k, v in res.items() if k != "programs"})


if __name__ == "__main__":
    main()
