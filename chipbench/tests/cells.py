"""Cells of the benchmark shrunk to the reduced preset, for the CPU."""
import json
from pathlib import Path

from chipbench import bench, traffic

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


def reduced_config() -> dict:
    """``qwen3-0.6b.json`` with the program's reduced preset: the same
    keys, the preset's sizes, a small engine and a limit for float32."""
    from repro.configs.qwen3_0_6b import REDUCED as m
    cfg = json.loads((ROOT / "chipbench/configs/qwen3-0.6b.json").read_text())
    cfg.update(num_hidden_layers=m.n_layers, hidden_size=m.d_model,
               num_attention_heads=m.n_heads,
               num_key_value_heads=m.n_kv_heads, head_dim=m.head_dim,
               intermediate_size=m.d_ff, vocab_size=m.vocab_size,
               torch_dtype=m.dtype)
    cfg["program"] = dict(cfg["program"], reduced=True)
    cfg["engine"] = {"batch": 2, "max_len": 64, "prefill_len": 32,
                     "max_queue": 64, "eos_id": None,
                     "paging": {"kv_block": 8}, "horizon": {"length": 4}}
    # the program and the reference both compute in float32 here
    cfg["limits"] = {"widest_logit_gap": 1e-3,
                     "min_served_tokens_checked": 10}
    return cfg


def reduced_cell(traffic_name: str) -> bench.Cell:
    mix = traffic.load_mix(traffic_name)
    mix["prompt_len"] = dict(mix["prompt_len"], min=4, max=32)
    if "median" in mix["prompt_len"]:
        mix["prompt_len"]["median"] = 12
    mix["output_len"] = dict(mix["output_len"], min=2, max=32)
    if "median" in mix["output_len"]:
        mix["output_len"]["median"] = 8
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"qwen3-0.6b.{traffic_name}"

    def mine(m):
        return name in m.get("workloads", [name])

    return bench.Cell(name=name, chips=1, config=reduced_config(), mix=mix,
                      traffic=traffic_name,
                      end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                      per_layer=[m for m in spec["per_layer"] if mine(m)])
