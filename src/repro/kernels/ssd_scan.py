"""Mamba-2 SSD chunked-scan kernel.

Grid = (batch, heads, chunks); the chunk axis is sequential ("arbitrary")
and the (P x N) state lives in VMEM scratch across chunk steps — the
HBM<->VMEM contract is: stream one chunk of (x, dt, B, C) in, one chunk of
y out, state never leaves VMEM.  Inside a chunk the SSD dual form runs the
quadratic intra-chunk term on the MXU (Q x Q decay-masked attention) plus
the rank-1 inter-chunk update, mirroring repro.models.ssm.ssd_chunked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, hf_ref, h_ref, *,
            chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                                  # () scalar decay for head
    x = x_ref[0, 0]                               # (Q, P)
    dt = dt_ref[0, 0]                             # (Q,)
    b = b_ref[0]                                  # (Q, N)
    c = c_ref[0]                                  # (Q, N)

    da = dt * a                                   # (Q,)
    da_cs = jnp.cumsum(da)                        # inclusive
    q = x.shape[0]
    seg = da_cs[:, None] - da_cs[None, :]         # (Q, Q)
    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # mask before the exp (above-diagonal seg is positive and overflows;
    # masking after hides the inf but poisons any gradient with 0 * inf)
    l_mat = jnp.exp(jnp.where(causal, seg, -1e30))
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    att = cb * l_mat * dt[None, :]
    y_intra = jax.lax.dot_general(att.astype(x.dtype), x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h = h_ref[...]                                # (P, N) f32
    y_inter = jax.lax.dot_general(c, h, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32
                                  ) * jnp.exp(da_cs)[:, None]     # (Q, P)
    decay_to_end = jnp.exp(da_cs[-1] - da_cs)     # (Q,)
    xw = x.astype(jnp.float32) * (dt * decay_to_end)[:, None]     # (Q, P)
    contrib = jax.lax.dot_general(xw, b, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (P,N)
    h_ref[...] = h * jnp.exp(da_cs[-1]) + contrib
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(ci == chunks - 1)
    def _final():
        hf_ref[0, 0] = h_ref[...]


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B,S,H,P) dt: (B,S,H) a: (H,) b,c: (B,S,N).

    Returns (y (B,S,H,P), h_final (B,H,P,N)).  D-skip (y += D*x) and initial
    state folding are applied by the ops wrapper."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    chunks = s // chunk
    grid = (bsz, h, chunks)

    # layout: put head axis in front of seq so blocks are (1,1,chunk,*)
    xt = x.transpose(0, 2, 1, 3)                  # (B,H,S,P)
    dtt = dt.transpose(0, 2, 1)                   # (B,H,S)

    y, hf = pl.pallas_call(
        functools.partial(_kernel, chunks=chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j, kk: (j,)),                # a (H,)
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, kk: (i, j, kk, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j, kk: (i, j, kk)),
            pl.BlockSpec((1, chunk, n), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, kk: (i, j, kk, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, kk: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, xt, dtt, b, c)
    return y.transpose(0, 2, 1, 3), hf
