"""Tiled MXU matmul kernel — the paper's Cannon-benchmark hot loop, TPU-style.

Epiphany's Table 2 keeps the inner MatrixMultiply() in 32 KB local memory;
the TPU analogue keeps (block_m x block_k) + (block_k x block_n) operand
tiles plus an fp32 accumulator resident in VMEM while streaming K-blocks
from HBM.  Blocks are 128-multiples (MXU systolic dims); K is the innermost
("arbitrary") grid dim so the accumulator carries across K steps and the
output writes once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(x: jax.Array, w: jax.Array, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128,
           interpret: bool = False) -> jax.Array:
    """x: (M, K) @ w: (K, N) -> (M, N), fp32 accumulation."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    k_steps = k // block_k
    grid = (m // block_m, n // block_n, k_steps)

    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, w)
