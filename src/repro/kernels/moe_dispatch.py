"""MoE grouped expert-FFN kernel — the dynamic-call table at VMEM level (C4).

Experts are "functions resident in global memory" (HBM); the routing table
is the jump table.  Grid = (experts, capacity_blocks): each expert's weights
stream HBM -> VMEM exactly once per grid column (Pallas revisiting-block
reuse), token blocks stream through, and the fused silu(x@w1)*(x@w3) @ w2
never materializes the hidden activations in HBM.

VMEM budget per step (qwen3-moe numbers): w1+w3 (d x f) + w2 (f x d) bf16 =
3 * 2048 * 768 * 2B = 9.4 MB, plus a (bc x d) token block and (bc x f)
hidden scratch — comfortably inside the ~128 MB v5e VMEM at bc = 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(buf_ref, w1_ref, w3_ref, w2_ref, o_ref):
    x = buf_ref[0]                                     # (bc, d)
    w1 = w1_ref[0]                                     # (d, f)
    w3 = w3_ref[0]
    w2 = w2_ref[0]                                     # (f, d)
    g = jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, w3, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)           # (bc, f) VMEM-only
    o_ref[0] = jax.lax.dot_general(
        h, w2, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def moe_ffn(buf: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array, *,
            block_c: int = 128, interpret: bool = False) -> jax.Array:
    """buf: (E, C, d) routed token blocks; w1/w3: (E, d, f); w2: (E, f, d).

    Returns (E, C, d).  The (token gather -> buf) dispatch runs in XLA
    (repro.models.moe) — scatter/gather is the one step Pallas TPU leaves to
    the host program; the compute + expert-weight streaming lives here.
    """
    e, c, d = buf.shape
    f = w1.shape[-1]
    block_c = min(block_c, c)
    assert c % block_c == 0
    grid = (e, c // block_c)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda ei, ci: (ei, ci, 0)),
            pl.BlockSpec((1, d, f), lambda ei, ci: (ei, 0, 0)),
            pl.BlockSpec((1, d, f), lambda ei, ci: (ei, 0, 0)),
            pl.BlockSpec((1, f, d), lambda ei, ci: (ei, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d), lambda ei, ci: (ei, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c, d), buf.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(buf, w1, w3, w2)
