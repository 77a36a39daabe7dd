"""Compiles of the main serving path for one described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler compiles against a described
``v5e:2x2`` topology, which refuses what the chip would refuse — a program
that outgrows the 16 GB of HBM, a kernel the Mosaic backend cannot lower —
at no chip time.  The topology is described only inside the ``one_chip``
fixture, so collecting this file never loads the TPU library; where it
cannot be described, every test here skips from that fixture.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro import steps
from repro.engine_config import EngineConfig, HorizonConfig, PagingConfig
from repro.kernels import ops
from repro.models import registry
from repro.sharding import make_rules, tree_shardings, tree_structs

HBM_BYTES = 16e9                # one TPU v5e chip
ARCH = "qwen3-0.6b"             # at its published widths
SERVE = EngineConfig(reduced=False, batch=8, max_len=2048)


@pytest.fixture(scope="module")
def topo():
    """A described ``v5e:2x2``, with the persistent compilation cache off:
    an entry compiled for a described chip cannot be read back without
    one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The serving mesh over all four chips of the described host."""
    return Mesh(np.array(topo.devices).reshape(4), ("model",))


@pytest.fixture(scope="module")
def serve_specs():
    cfg = registry.get_config(ARCH, reduced=False)
    return steps.serve_program_specs(cfg, make_rules(), SERVE)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("program", ["decode", "prefill_slot", "prefill"])
def test_serve_program_fits_one_v5e_chip(one_chip, serve_specs, program):
    """Each program the dense engine hot-loads, compiled the way a
    mesh-less Syscore compiles it, fits one chip's HBM — and the donated
    KV cache is aliased to the output, not copied."""
    spec = serve_specs[program]
    args = _on(one_chip, tree_structs(spec.abstract_args))
    compiled = jax.jit(spec.fn, donate_argnums=spec.donate_argnums).lower(
        *args).compile()
    mem = compiled.memory_analysis()
    kv_bytes = sum(x.size * x.dtype.itemsize for x in
                   jax.tree.leaves((args[1]["groups"], args[1]["tail"])))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES, \
        (program, mem)
    assert mem.alias_size_in_bytes >= kv_bytes, (program, mem)


def test_matmul_kernel_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1024, 3072), jnp.bfloat16, sharding=one_chip)
    text = ops.matmul.lower(x, w, impl="pallas").compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_kernel_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((16, 1024, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 1024, 128), jnp.bfloat16, sharding=one_chip)
    text = ops.flash_attention.lower(q, kv, kv,
                                     impl="pallas").compile().as_text()
    assert "tpu_custom_call" in text


def _arena_copies(text, stack, layer):
    """The instructions of compiled HLO ``text`` that copy, restack or
    allocate a whole stack of layer arenas (shape ``stack``, e.g.
    ``bf16[28,2560,16,8,128]``) or slice or copy out one layer's arena
    (shape ``layer``): what a layer scan that threads the arenas through
    its xs and ys makes on every step.  In-place updates (the write's
    scatter) and the kernel's reads are not listed."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(",
                     line)
        if not m:
            continue
        name, shape, op = m.groups()
        if shape == stack and (op in ("copy", "dynamic-update-slice")
                               or 'custom_call_target="AllocateBuffer"'
                               in line):
            found.append(name)
        if shape == layer and op in ("copy-done", "dynamic-slice"):
            found.append(name)
    return found


@pytest.mark.parametrize("program", ["decode", "decode_horizon"])
def test_paged_decode_with_kernel_fits_one_v5e_chip(one_chip, monkeypatch,
                                                     program):
    """The paged ``decode`` and ``decode_horizon`` programs at the chip
    benchmark's engine size (32 slots, 2,048 positions, a 2,560-block arena
    of 16-token blocks, horizons of 8), with the paged read the TPU backend
    selects, compile for one chip with the Pallas kernel in them and fit
    the chip's HBM.  The layer scan updates the stacked arenas in place:
    no whole-arena copy, restacking or buffer, no per-layer slice or copy
    of an arena, and under 1 GB of temporaries beside the 4.7 GB arena."""
    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    cfg = registry.get_config(ARCH, reduced=False)
    config = EngineConfig(reduced=False, batch=32, max_len=2048,
                          prefill_len=1024, horizon=HorizonConfig(length=8),
                          paging=PagingConfig(kv_block=16, arena_blocks=2560))
    spec = steps.serve_program_specs(cfg, make_rules(), config)[program]
    args = _on(one_chip, tree_structs(spec.abstract_args))
    compiled = jax.jit(spec.fn, donate_argnums=spec.donate_argnums).lower(
        *args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES, mem
    assert mem.temp_size_in_bytes < 1e9, mem
    assert "paged_decode_attention" in text
    assert _arena_copies(text, "bf16[28,2560,16,8,128]",
                         "bf16[2560,16,8,128]") == []


def test_olmoe_decode_horizon_fits_four_v5e_chips(four_chips, monkeypatch):
    """OLMoE-1B-7B whole, at its published widths, over the four chips of a
    described v5e host as the chip benchmark serves it (64 slots, 2,048
    positions, a 5,120-block arena of 16-token blocks, horizons of 8):
    ``decode_horizon`` compiles with the paged-attention kernel run per KV
    head shard, and each chip's arguments (3.46 GB of weights, 2.68 GB of
    arena) and temporaries fit its HBM, with no copy, restacking or slice
    of its share of the arena."""
    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    cfg = registry.get_config("olmoe-1b-7b", reduced=False)
    config = EngineConfig(reduced=False, batch=64, max_len=2048,
                          prefill_len=1024, horizon=HorizonConfig(length=8),
                          paging=PagingConfig(kv_block=16, arena_blocks=5120))
    rules = make_rules()
    spec = steps.serve_program_specs(cfg, rules, config)["decode_horizon"]
    shardings = tree_shardings(spec.abstract_args, rules, four_chips)
    args = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_structs(spec.abstract_args), shardings)
    with jax.set_mesh(four_chips):
        compiled = jax.jit(
            spec.fn, in_shardings=shardings,
            out_shardings=tree_shardings(spec.out_logical, rules, four_chips),
            donate_argnums=spec.donate_argnums).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert 6.0e9 < mem.argument_size_in_bytes < 6.3e9, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES, mem
    assert mem.alias_size_in_bytes >= 2.68e9, mem
    text = compiled.as_text()
    assert "paged_decode_attention" in text
    assert _arena_copies(text, "bf16[16,5120,16,4,128]",
                         "bf16[5120,16,4,128]") == []
