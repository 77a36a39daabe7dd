"""Work counts of qwen3-0.6b against hand arithmetic from its config."""
import json
from pathlib import Path

import pytest

from chipbench import bench

CFG = json.loads((Path(bench.HERE) / "configs/qwen3-0.6b.json").read_text())
Q = bench.load_module(bench.HERE / "models/qwen3.py", "model")

# 28 layers of q (1024x2048), k and v (1024x1024 each), o (2048x1024) and
# the MLP (3 x 1024x3072)
LINEAR = 28 * (2097152 + 2 * 1048576 + 2097152 + 3 * 3145728)
HEAD = 151936 * 1024
NORMS = 28 * (2 * 1024 + 2 * 128) + 1024


def test_parameter_and_cache_sizes():
    assert Q.linear_params(CFG) == LINEAR == 440_401_920
    # 28 layers x (k, v) x 8 heads x 128 x 2 bytes
    assert Q.kv_bytes_per_token(CFG) == 114_688
    assert Q.weight_bytes(CFG) == 2 * (LINEAR + HEAD + NORMS)
    assert Q.weight_bytes(CFG) == pytest.approx(1.192e9, rel=1e-3)


def test_prefill_work():
    s = 384
    flops, nbytes = Q.prefill_work(CFG, s)
    attn = 2 * 28 * 16 * 128 * s * (s + 1)
    assert flops == 2 * LINEAR * s + attn + 2 * HEAD
    assert nbytes == 2 * (LINEAR + HEAD + NORMS) + s * 114_688


def test_decode_work():
    flops, nbytes = Q.decode_work(CFG, [100, 900], steps=3)
    assert flops == 2 * (2 * LINEAR + 2 * HEAD) + 4 * 28 * 16 * 128 * 1000
    assert nbytes == 3 * 2 * (LINEAR + HEAD + NORMS) + 114_688 * 1000


def test_the_file_is_the_published_config():
    from repro.models import registry
    Q.check_program_config(CFG, registry.get_config("qwen3-0.6b"))
    wrong = dict(CFG, intermediate_size=4096)
    with pytest.raises(ValueError):
        Q.check_program_config(wrong, registry.get_config("qwen3-0.6b"))
