# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# ``--smoke`` runs every bench at toy sizes (CI budget: the whole sweep in
# well under 60 s) — modules whose ``run`` accepts a ``smoke`` kwarg get it
# passed through; the rest are already toy-sized.
import argparse
import inspect
import os
import sys
import traceback

# allow `python benchmarks/run.py` standalone: the bench package lives at the
# repo root and the repro package under src/
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes for CI (<60 s total)")
    args = ap.parse_args()

    from benchmarks import (bench_autotune, bench_boot, bench_cluster,
                            bench_elastic, bench_fused, bench_hostcall,
                            bench_load_exec, bench_paging, bench_pipeline,
                            bench_placement, bench_prefix, bench_roofline,
                            bench_spec, bench_tp, bench_treeload)
    modules = [
        ("load_exec(Table1+Fig2)", bench_load_exec),
        ("boot(Table1-store)", bench_boot),
        ("cluster(fleet-failover)", bench_cluster),
        ("elastic(fleet-scale)", bench_elastic),
        ("autotune(knob-search)", bench_autotune),
        ("paging(S3.4-kv)", bench_paging),
        ("prefix(S3.4-sharing)", bench_prefix),
        ("spec(Table1-decode)", bench_spec),
        ("fused(S3.3-horizon)", bench_fused),
        ("tp(S3-sharded)", bench_tp),
        ("placement(Table2)", bench_placement),
        ("hostcall(S3.5)", bench_hostcall),
        ("treeload(Fig2)", bench_treeload),
        ("pipeline(cross-pod)", bench_pipeline),
        ("roofline(dry-run)", bench_roofline),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for label, mod in modules:
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        try:
            for name, value, derived in mod.run(**kwargs):
                print(f"{name},{value:.3f},{derived}", flush=True)
        except Exception as e:
            failures += 1
            print(f"{label},-1,ERROR {e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
