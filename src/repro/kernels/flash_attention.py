"""Flash attention kernel: online softmax, causal + sliding-window, GQA.

Schedule: grid = (batch*heads, q_blocks, kv_blocks), kv innermost and
sequential; running (max, denom, acc) live in VMEM scratch across kv steps.
Two structural optimizations vs the XLA baseline path:

  * GQA without materialized repeat: the kv index_map maps head bh -> bh//G,
    so each query head streams its shared KV block straight from HBM (the
    XLA path pays an explicit repeat; see repro.models.attention docstring).
  * causal/window block skipping: fully-masked (q,kv) blocks are skipped via
    ``pl.when`` — the 2x causal FLOPs waste of the scanned XLA baseline and
    the full-length waste for gemma3 local layers disappear (this is the
    kernel form of the `attn_impl="unrolled"` hillclimb; EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, kv_steps: int, q_offset: int,
            causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = q_offset + qi * block_q           # absolute first q position
    k_lo = ki * block_k
    # block-level skip decision (static per grid point at trace time is not
    # possible — qi/ki are dynamic — so pl.when guards the compute)
    q_hi = q_lo + block_q - 1
    k_hi = k_lo + block_k - 1
    needed = jnp.bool_(True)
    if causal:
        needed &= q_hi >= k_lo               # some key <= some query
    if window > 0:
        needed &= (q_lo - k_hi) < window     # some key within window

    @pl.when(needed)
    def _compute():
        q = q_ref[0]                          # (bq, D)
        k = k_ref[0]                          # (bk, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, D); k, v: (BHk, Sk, D), BH % BHk == 0 (GQA via index_map).

    Queries are right-aligned against keys (q position i attends as absolute
    position Sk - Sq + i) so the same kernel serves prefill (Sq == Sk) and
    chunked prefill against a longer cache.
    """
    bh, sq, d = q.shape
    bhk, sk, _ = k.shape
    assert bh % bhk == 0
    g = bh // bhk
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    grid = (bh, sq // block_q, sk // block_k)
    q_offset = sk - sq

    return pl.pallas_call(
        functools.partial(
            _kernel, block_q=block_q, block_k=block_k,
            kv_steps=sk // block_k, q_offset=q_offset, causal=causal,
            window=window, scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, g=g: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
