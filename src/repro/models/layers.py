"""Shared model layers: norms, RoPE, embeddings, MLP, sharded cross-entropy.

Everything is functional: ``*_abstract(cfg)`` returns a pytree of
:class:`repro.sharding.LogicalArray` (shapes + logical axes, no allocation);
``apply_*`` consumes a matching pytree of concrete arrays.  This split is what
lets the multi-pod dry-run lower/compile every architecture without ever
materializing 26B parameters on the CPU container.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.sharding import LogicalArray, constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_abstract(dim: int, dtype) -> LogicalArray:
    return LogicalArray((dim,), dtype, ("norm",))


def apply_rmsnorm(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: (..., S)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    if x.ndim == angles.ndim + 1:  # has a heads axis
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings + sharded loss
# ---------------------------------------------------------------------------

def embedding_abstract(vocab: int, dim: int, dtype) -> LogicalArray:
    return LogicalArray((vocab, dim), dtype, ("vocab", "embed"))


def apply_embedding(table: jax.Array, ids: jax.Array, rules) -> jax.Array:
    out = jnp.take(table, ids, axis=0)
    return constrain(out, ("batch", "seq", "embed"), rules)


def apply_lm_head(table: jax.Array, x: jax.Array, rules,
                  transpose: bool = False) -> jax.Array:
    """x: (B, S, d) -> logits (B, S, V), vocab axis model-sharded."""
    if transpose:  # tied embedding table (V, d)
        logits = jnp.einsum("bsd,vd->bsv", x, table)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, table)
    return constrain(logits, ("batch", "seq_attn", "vocab"), rules)


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 valid_vocab: int) -> jax.Array:
    """Cross-entropy that never gathers the (model-sharded) vocab axis.

    max / log-sum-exp are reductions over the sharded axis (GSPMD lowers them
    to cheap scalar all-reduces); the label logit is a fused one-hot
    select-reduce rather than a cross-shard gather.  Vocab padding rows are
    masked out of the partition function.
    """
    vocab = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if valid_vocab < vocab:
        pad_mask = jnp.arange(vocab) < valid_vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, vocab, dtype=logits.dtype)
    label_logit = jnp.sum(shifted * onehot, axis=-1) + m[..., 0]
    return lse - label_logit  # (B, S)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_abstract(d_model: int, d_ff: int, dtype, stack: int = 0) -> Params:
    lead = (stack,) if stack else ()
    lax = ("layers",) if stack else ()
    return {
        "w_gate": LogicalArray(lead + (d_model, d_ff), dtype, lax + ("embed_fsdp", "ff")),
        "w_up": LogicalArray(lead + (d_model, d_ff), dtype, lax + ("embed_fsdp", "ff")),
        "w_down": LogicalArray(lead + (d_ff, d_model), dtype, lax + ("ff", "embed_fsdp")),
    }


def apply_mlp(p: Params, x: jax.Array, rules, act=jax.nn.silu) -> jax.Array:
    h = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"])) * jnp.einsum(
        "bsd,df->bsf", x, p["w_up"])
    h = constrain(h, ("batch", "seq_attn", "ff"), rules)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return constrain(out, ("batch", "seq", "embed"), rules)


# ---------------------------------------------------------------------------
# parameter materialization
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("leaves",))
def _init_leaves(key, *, leaves):
    """One program for a whole tree: ``leaves`` is ((shape, dtype, std),
    ...), with ``std=None`` for a zero-initialized leaf."""
    keys = jax.random.split(key, max(len(leaves), 1))
    # the barrier keeps the scale out of the sampler's fusion, so the draws
    # round exactly as they do op by op
    return [jnp.zeros(shape, dtype) if std is None else
            (jax.lax.optimization_barrier(
                jax.random.normal(k, shape, jnp.float32)) * std).astype(dtype)
            for (shape, dtype, std), k in zip(leaves, keys)]


def materialize(abstract_tree, key: jax.Array, init_scale: float = 1.0):
    """LogicalArray pytree -> initialized arrays (host-side, for real runs).

    Norm scales, biases and scalars start at zero; matrices are normal with
    std ``init_scale / sqrt(fan_in)``.  The whole tree is drawn by one
    compiled program, cached per tree shape."""
    leaves, treedef = jax.tree.flatten(
        abstract_tree, is_leaf=lambda x: isinstance(x, LogicalArray))
    specs = tuple(
        (la.shape, la.dtype,
         None if len(la.shape) <= 1 else init_scale / (la.shape[-2] ** 0.5))
        for la in leaves)
    return jax.tree.unflatten(treedef, _init_leaves(key, leaves=specs))
