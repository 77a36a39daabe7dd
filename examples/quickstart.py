"""Quickstart: the persistent executor in 60 lines.

Demonstrates the paper's runtime model end to end on one CPU device:
  1. boot syscore once (C2),
  2. hot-load a train program AOT,
  3. re-execute it many times (the 40 us path of Table 1),
  4. in-graph hostcall telemetry (C5),
  5. placement report for the model (C1).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import steps
from repro.core import (CALL_STEP_REPORT, PlacementPlan, Syscore, apply_plan,
                        cold_execute, USRMEM)
from repro.models import registry
from repro.optim import AdamWConfig, adamw_init
from repro.sharding import LogicalArray, make_rules


def main():
    cfg = registry.get_config("qwen3-0.6b", reduced=True)
    rules = make_rules()
    sc = Syscore()

    params = steps.model_module(cfg).init_params(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params)}
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)),
                              jnp.int32),
    }

    base = steps.make_train_step(cfg, rules, AdamWConfig())

    def train_step(state, batch):
        new_state, metrics = base(state, batch)
        sc.hostcalls.hostcall(CALL_STEP_REPORT, new_state["opt"]["step"],
                              metrics["loss"])
        return new_state, metrics

    abstract = jax.tree.map(
        lambda a: LogicalArray(a.shape, a.dtype, (None,) * a.ndim),
        (state, batch))
    t0 = time.perf_counter()
    # hot_load returns a typed, callable ProgramHandle (Executor API v2)
    train_prog = sc.hot_load("train", train_step, abstract)
    print(f"hot_load (lower+compile once): {time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    for _ in range(10):
        state, metrics = train_prog(state, batch)
    jax.block_until_ready(metrics["loss"])
    step_ms = (time.perf_counter() - t0) / 10 * 1e3
    print(f"re-execute x10: {step_ms:.1f} ms/step, "
          f"loss={float(metrics['loss']):.3f}")
    print(f"handle stats: {train_prog.stats.executions} executions, "
          f"{step_ms:.1f} ms/step blocked wall")

    t0 = time.perf_counter()
    cold_execute(train_step, state, batch)
    print(f"cold compile+exec (eSDK analogue): {time.perf_counter() - t0:.2f}s")
    print("telemetry points via hostcall:", len(sc.hostcalls.step_times))

    plan = PlacementPlan().add(r"embed", USRMEM)     # embeddings host-resident
    placed = apply_plan(params, plan)
    print("placement report:", placed.report()["fraction"])
    print("programs:", sc.report()["programs"])


if __name__ == "__main__":
    main()
