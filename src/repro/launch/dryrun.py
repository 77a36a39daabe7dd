import os
if __name__ == "__main__":
    # CLI mode only: force 512 placeholder CPU devices so the production
    # meshes exist on any host — and the CPU platform, so on a machine with
    # a TPU the dry run never claims the chip.  MUST run before any jax
    # import (jax locks the platform and device count at first init) —
    # which is why it is gated: library importers (the autotuner's cost
    # model, tests) must see the process's real device topology, not have
    # it hijacked by a transitive import.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs ShapeDtypeStruct stand-ins for every input (``input_specs``),
  3. ``jax.jit(step).lower(...).compile()`` with explicit in/out shardings,
  4. records ``memory_analysis()`` (proves the cell fits per-chip HBM),
     ``cost_analysis()`` (FLOPs/bytes for the roofline) and the collective
     schedule parsed from the compiled HLO,
  5. writes one JSON per cell into results/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all --mesh both          # full sweep
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.launch import hlo_analysis as ha
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rl
from repro.models import registry
from repro.sharding import make_rules, tree_shardings, tree_structs
from repro.sharding import LogicalArray


def input_specs(arch: str, shape: str, **kw):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    spec = registry.cell_spec(arch, shape, **kw)
    return tree_structs(spec.abstract_args)


def lower_serve_programs(arch: str, config, programs=None) -> dict:
    """Abstractly lower + compile the serving programs an
    ``EngineConfig`` would hot-load, without allocating params or caches.

    The dry-run recipe (ShapeDtypeStruct stand-ins -> jit.lower.compile)
    applied to ``steps.serve_program_specs``: every input is abstract, so
    the only real cost is XLA compile time — this is how the autotuner's
    cost model prices knob settings that change program shape (a different
    horizon H, kv_block, spec_k, batch) without ever running them.

    ``programs`` optionally restricts to a subset of names (the cost
    model wants decode-path programs only).  Single-device lowering:
    ``config.shard`` is ignored — per-device cost of a TP engine is
    approximated by total/n downstream, and the ProgramStore keys warm
    boots per mesh shape separately.

    Returns ``{name: record}`` with, per program:
      hlo            compiled HLO text (feed to ``hlo_analysis.analyze``)
      cost           loop-aware ``hlo_analysis.Cost`` (1 device)
      out_shape      output tree of (shape, dtype) pairs from eval_shape
      memory         ``memory_analysis()`` argument/output/temp bytes
      lower_s / compile_s
    """
    from repro import steps as steps_lib
    from repro.engine_config import ShardConfig

    if config.shard.n_devices > 1:
        config = config.replace(shard=ShardConfig())
    cfg = registry.get_config(arch, reduced=config.reduced)
    rules = make_rules()
    specs = steps_lib.serve_program_specs(cfg, rules, config)
    out = {}
    for name, spec in specs.items():
        if programs is not None and name not in programs:
            continue
        structs = tree_structs(spec.abstract_args)
        shapes = jax.eval_shape(spec.fn, *structs)
        t0 = time.time()
        jf = jax.jit(spec.fn, donate_argnums=spec.donate_argnums)
        lowered = jf.lower(*structs)
        lower_s = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
        hlo = compiled.as_text()
        ma = compiled.memory_analysis()
        out[name] = {
            "hlo": hlo,
            "cost": ha.analyze(hlo, 1),
            "out_shape": jax.tree.map(
                lambda s: (tuple(s.shape), str(s.dtype)), shapes),
            "memory": {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
            },
            "lower_s": round(lower_s, 3),
            "compile_s": round(compile_s, 3),
        }
    return out


def _default_knobs(spec) -> dict:
    """Baseline per-kind configuration (recorded in EXPERIMENTS.md)."""
    return {
        # ZeRO-style FSDP sharding of the embed axis for training only:
        # inference keeps params resident (replicated over DP) for latency.
        "fsdp": spec.kind == "train",
        "seq_parallel": False,
    }


def compile_cell(arch: str, shape: str, *, multi_pod: bool,
                 fsdp=None, seq_parallel=None, remat=None, attn_impl=None,
                 accum=None, cache_heads=None, grad_constraint=False,
                 kv_replicate=True, grad_of_scan=False,
                 tag: str = "baseline") -> dict:
    spec = registry.cell_spec(arch, shape, remat=remat, attn_impl=attn_impl,
                              cache_heads=cache_heads)
    knobs = _default_knobs(spec)
    if fsdp is not None:
        knobs["fsdp"] = fsdp
    if seq_parallel is not None:
        knobs["seq_parallel"] = seq_parallel

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(**knobs)
    # replicate kv projection weights when kv heads don't divide TP: the
    # kv->heads repeat becomes a local slice (no GSPMD replicate-fallback)
    knobs["kv_replicate"] = bool(
        kv_replicate and spec.cfg.n_kv_heads % mesh.shape["model"] != 0)
    if knobs["kv_replicate"]:
        rules = dict(rules, kv_heads_w=None)
    dp = mesh_lib.dp_size(mesh)
    if spec.global_batch % dp != 0:
        # long_500k (batch=1): no data parallelism — model axes only.
        rules = dict(rules, batch=None)
        dp = 1

    # gradient-accumulation default: microbatch = 1 sequence per device
    # (keeps every train cell under 16 GB HBM; see EXPERIMENTS.md §Dry-run)
    if spec.kind == "train":
        per_dev = max(1, spec.global_batch // dp)
        knobs["accum"] = accum if accum is not None else per_dev
    else:
        knobs["accum"] = 1

    knobs["grad_constraint"] = bool(grad_constraint)
    knobs["grad_of_scan"] = bool(grad_of_scan)
    knobs["cache_heads"] = cache_heads
    structs = tree_structs(spec.abstract_args)
    shardings = tree_shardings(spec.abstract_args, rules, mesh)
    step = registry.build_step_fn(spec, rules, accum=knobs["accum"],
                                  grad_constraint=bool(grad_constraint),
                                  grad_of_scan=bool(grad_of_scan))

    out_shardings = None
    if spec.kind == "train":
        out_shardings = (shardings[0], None)       # state' matches state
    elif spec.kind == "prefill":
        out_shardings = (shardings[1], None)       # caches' match caches
    else:
        out_shardings = (shardings[1], None, None)

    rec = {"arch": arch, "shape": shape, "kind": spec.kind, "tag": tag,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": mesh.size, "knobs": knobs,
           "global_batch": spec.global_batch, "seq_len": spec.seq_len}
    with jax.set_mesh(mesh):
        jf = jax.jit(step, in_shardings=shardings, out_shardings=out_shardings,
                     donate_argnums=spec.donate_argnums)
        t0 = time.time()
        lowered = jf.lower(*structs)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    rec["memory"]["peak_bytes_per_device"] = int(peak)
    rec["memory"]["fits_16gb_hbm"] = bool(peak < 16e9)
    # XLA-CPU upcasts bf16 dots to f32 and hoists the converts, materializing
    # f32 copies of stacked weights/caches that do not exist on TPU (native
    # bf16 MXU).  Report the artifact and a TPU-adjusted peak.
    hlo_early = compiled.as_text()
    artifact = ha.cpu_upcast_artifact_bytes(hlo_early)
    rec["memory"]["cpu_bf16_upcast_bytes"] = int(artifact)
    adj = max(0, peak - artifact)
    rec["memory"]["peak_adjusted_tpu"] = int(adj)
    rec["memory"]["fits_16gb_hbm_adjusted"] = bool(adj < 16e9)

    # XLA's cost_analysis counts while bodies ONCE — record it for reference
    # but derive the roofline from the loop-aware analyzer (hlo_analysis.py).
    ca = compiled.cost_analysis() or {}
    rec["xla_reported"] = {"flops": float(ca.get("flops", 0.0)),
                           "bytes": float(ca.get("bytes accessed", 0.0))}

    hlo = hlo_early
    cost = ha.analyze(hlo, mesh.size)
    flops, bytes_ = cost.flops, cost.bytes_ideal
    rec["cost"] = {"flops_per_device": flops,
                   "bytes_per_device": bytes_,
                   "bytes_per_device_unfused": cost.bytes_cpu,
                   "bytes_by_op": ha.ideal_bytes_by_opcode(hlo, mesh.size)}
    intra, cross = ha.wire_bytes_split(cost)
    rec["collectives"] = {"by_kind": ha.summarize_collectives(cost),
                          "wire_bytes_intra": intra,
                          "wire_bytes_cross_pod": cross,
                          "n_ops": len(cost.collectives)}
    rec["roofline"] = rl.roofline_terms(flops, bytes_, intra, cross)

    mf = registry.model_flops(spec.cfg, shape)
    rec["model_flops_total"] = mf
    hlo_total = flops * mesh.size
    rec["model_flops_over_hlo"] = mf / hlo_total if hlo_total else 0.0
    rec["params"] = registry.param_counts(spec.cfg)
    return rec


def run_cell(arch, shape, meshes, outdir: Path, **kw):
    results = []
    for multi in meshes:
        name = f"{arch}__{shape}__{'multi' if multi else 'single'}"
        tag = kw.get("tag", "baseline")
        if tag != "baseline":
            name += f"__{tag}"
        path = outdir / f"{name}.json"
        if path.exists() and not kw.get("force"):
            print(f"[skip-existing] {name}")
            continue
        try:
            rec = compile_cell(arch, shape, multi_pod=multi,
                               **{k: v for k, v in kw.items()
                                  if k not in ("force",)})
            path.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(f"[ok] {name}: compile={rec['compile_s']}s "
                  f"peak={rec['memory']['peak_bytes_per_device']/1e9:.2f}GB "
                  f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                  f"coll={r['collective_s']:.4f}s dom={r['dominant']}",
                  flush=True)
            results.append(rec)
        except Exception as e:  # a failure here is a bug in the system
            path.with_suffix(".FAILED.json").write_text(json.dumps(
                {"arch": arch, "shape": shape, "multi_pod": multi,
                 "error": repr(e), "traceback": traceback.format_exc()},
                indent=1))
            print(f"[FAIL] {name}: {e!r}", flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--seq-parallel", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--cache-heads", type=int, default=None)
    ap.add_argument("--grad-constraint", action="store_true")
    ap.add_argument("--no-kv-replicate", dest="kv_replicate",
                    action="store_false", default=True)
    ap.add_argument("--grad-of-scan", action="store_true")
    ap.add_argument("--v2", action="store_true",
                    help="sweep every cell with the optimized defaults "
                         "validated in EXPERIMENTS.md §Perf (tag=v2)")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    kw = dict(tag=args.tag, force=args.force,
              fsdp=None if args.fsdp is None else bool(args.fsdp),
              seq_parallel=(None if args.seq_parallel is None
                            else bool(args.seq_parallel)),
              remat=args.remat, attn_impl=args.attn_impl, accum=args.accum,
              cache_heads=args.cache_heads,
              grad_constraint=args.grad_constraint,
              kv_replicate=args.kv_replicate,
              grad_of_scan=args.grad_of_scan)

    if args.v2:
        # optimized defaults per EXPERIMENTS.md §Perf: block-skipping
        # attention + ZeRO grad constraint everywhere; kv weight folding to
        # the TP degree where head counts permit (H % 16 == 0, 16 % kv == 0).
        t0 = time.time()
        for arch, shape in registry.all_cells():
            cfg = registry.get_config(arch)
            foldable = (cfg.n_heads % 16 == 0 and cfg.n_kv_heads < 16
                        and 16 % cfg.n_kv_heads == 0
                        and cfg.family != "ssm")
            kw2 = dict(tag="v2", force=args.force,
                       attn_impl="unrolled",
                       grad_constraint=True,
                       cache_heads=16 if foldable else None,
                       kv_replicate=not foldable)
            run_cell(arch, shape, meshes, outdir, **kw2)
        print(f"V2 TOTAL {time.time() - t0:.1f}s")
        return

    if args.all:
        cells = registry.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    t0 = time.time()
    for arch, shape in cells:
        run_cell(arch, shape, meshes, outdir, **kw)
    print(f"TOTAL {time.time() - t0:.1f}s for {len(cells)} cells")


if __name__ == "__main__":
    main()
