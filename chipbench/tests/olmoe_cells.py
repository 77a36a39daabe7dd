"""The olmoe-1b-7b cell shrunk to the program's reduced preset, for the
CPU: the configuration file with the preset's sizes and a small engine."""
import json

from chipbench import bench, traffic
from chipbench.tests.cells import ROOT

NAME = "olmoe-1b-7b.chat"


def reduced_config(n_devices: int = 1) -> dict:
    """``olmoe-1b-7b.json`` with the reduced preset's sizes (the published
    block: full-width qk-norm, no top-k renormalisation, eps 1e-5), an
    engine of 2 slots over ``n_devices`` and a limit for float32."""
    from repro.configs.olmoe_1b_7b import REDUCED as m
    cfg = json.loads((ROOT / "chipbench/configs/olmoe-1b-7b.json")
                     .read_text())
    cfg.update(num_hidden_layers=m.n_layers, hidden_size=m.d_model,
               num_attention_heads=m.n_heads,
               num_key_value_heads=m.n_kv_heads, intermediate_size=m.d_ff,
               num_experts=m.n_experts, num_experts_per_tok=m.experts_per_token,
               vocab_size=m.vocab_size, torch_dtype=m.dtype)
    cfg["program"] = dict(cfg["program"], reduced=True)
    cfg["engine"] = {"batch": 2, "max_len": 64, "prefill_len": 32,
                     "max_queue": 64, "eos_id": None,
                     "paging": {"kv_block": 8}, "horizon": {"length": 4},
                     "shard": {"n_devices": n_devices}}
    # the program and the reference both compute in float32 here
    cfg["limits"] = {"widest_logit_gap": 1e-3,
                     "min_served_tokens_checked": 10}
    return cfg


def reduced_cell() -> bench.Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {w["name"]: w for w in spec["workloads"]}[NAME]
    mix = traffic.load_mix(w["traffic"])
    mix["prompt_len"] = dict(mix["prompt_len"], median=12, min=4, max=32)
    mix["output_len"] = dict(mix["output_len"], median=8, min=2, max=32)
    mix["rate_per_s"] = 4.0

    def mine(m):
        return NAME in m.get("workloads", [NAME])

    return bench.Cell(name=NAME, chips=1, config=reduced_config(), mix=mix,
                      traffic=w["traffic"],
                      end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                      per_layer=[m for m in spec["per_layer"] if mine(m)])
