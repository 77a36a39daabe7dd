"""RG-LRU linear-recurrence kernel (RecurrentGemma's temporal mixing).

h_t = a_t * h_{t-1} + b_t over the sequence, per (batch, lane-block).
Grid = (batch, lru_blocks, chunks); chunks sequential with the carried state
in VMEM scratch.  Within a chunk the recurrence is evaluated with an
associative scan (log2(Q) depth) — VPU-friendly — and the carried state is
folded in as a closed-form prefix: h = A_prefix * h0 + B_scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_out_ref, hf_ref, h_ref, *, chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                                   # (Q, L) f32
    b = b_ref[0]

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    av, bv = jax.lax.associative_scan(combine, (a, b), axis=0)
    h0 = h_ref[...]                                # (L,)
    h_all = av * h0[None, :] + bv                  # (Q, L)
    h_ref[...] = h_all[-1]
    h_out_ref[0] = h_all.astype(h_out_ref.dtype)

    @pl.when(ci == chunks - 1)
    def _final():
        hf_ref[0] = h_ref[...]


def rglru_scan(a: jax.Array, b: jax.Array, *, chunk: int = 256,
               block_l: int = 512, interpret: bool = False):
    """a, b: (B, S, L) f32 -> (h (B,S,L), h_final (B,L))."""
    bsz, s, l = a.shape
    chunk = min(chunk, s)
    block_l = min(block_l, l)
    assert s % chunk == 0 and l % block_l == 0
    chunks = s // chunk
    grid = (bsz, l // block_l, chunks)

    h, hf = pl.pallas_call(
        functools.partial(_kernel, chunks=chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_l), lambda i, j, kk: (i, kk, j)),
            pl.BlockSpec((1, chunk, block_l), lambda i, j, kk: (i, kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_l), lambda i, j, kk: (i, kk, j)),
            pl.BlockSpec((1, block_l), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, l), a.dtype),
            jax.ShapeDtypeStruct((bsz, l), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_l,), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b)
    return h, hf
