"""Mixture-of-experts FFN with expert parallelism over the ``model`` axis.

Design (see DESIGN.md §5): activations entering the MoE block are replicated
over the ``model`` axis (standard Megatron TP layout), expert weights are
sharded expert-major over ``model``.  Inside a ``shard_map`` each model shard
routes the *full local token set* over all E experts and computes the part of
the output its own E/tp experts give, then ``psum``s over ``model``.  No
all-to-all is required in this layout — the only collective is the same psum
any TP FFN pays.

Routing: the router's logits, softmax and top-k are float32 (from the
served activations); the top-k gates are renormalised to sum to one only
where ``cfg.norm_topk_prob`` (Qwen3-MoE), and are the plain softmax
probabilities otherwise (OLMoE).  Two dispatches:

* training: a sort-free, capacity-bounded scatter (GShard-style drops,
  token-order priority) into dense (E_local, C, d) buffers;
* serving (prefill and decode): drop-free — every held expert runs over
  every local token, weighted by its gate, which is zero where the token
  did not pick it.  Every token gets all of its k experts, so a served
  token never depends on which requests share its batch.

This is also the arch-level realization of the paper's *dynamic calls* (C4):
an expert is a "function resident in global memory" that is paged into the
compute arena on demand by the routing table; `repro.kernels.moe_dispatch`
implements the same contract at the VMEM level and
`repro.core.dynamic_calls` manages host-resident expert pages with LRU.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import LogicalArray, get_abstract_mesh_or_none


def moe_abstract(cfg, stack: int = 0) -> Dict[str, Any]:
    lead = (stack,) if stack else ()
    lax_ = ("layers",) if stack else ()
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    dt = cfg.dtype
    return {
        "router": LogicalArray(lead + (d, e), dt, lax_ + ("embed", None)),
        "w_gate": LogicalArray(lead + (e, d, f), dt,
                               lax_ + ("experts", "embed_fsdp", "expert_ff")),
        "w_up": LogicalArray(lead + (e, d, f), dt,
                             lax_ + ("experts", "embed_fsdp", "expert_ff")),
        "w_down": LogicalArray(lead + (e, f, d), dt,
                               lax_ + ("experts", "expert_ff", "embed_fsdp")),
    }


def _capacity(cfg, tokens_local: int) -> int:
    c = int(cfg.capacity_factor * cfg.experts_per_token * tokens_local
            / cfg.n_experts)
    return max(4, c)


def _route(cfg, xf, router):
    """Router probabilities (T, E) and each token's top-k gates and experts
    (T, k), all float32."""
    logits = jnp.einsum("td,de->te", xf, router,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_i


def _moe_dropfree(cfg, x, router, w_gate, w_up, w_down, *, e_local0,
                  n_local, model_axis=None):
    """Per-shard serving body: each held expert over every local token,
    weighted by its gate (zero where not picked). x: (B_l, S, d)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe/route"):
        _, top_p, top_i = _route(cfg, xf, router)
        held = e_local0 + jnp.arange(n_local)
        gates = jnp.sum(jnp.where(top_i[:, :, None] == held, top_p[:, :, None],
                                  0.0), axis=1)               # (T, E_l)
    with jax.named_scope("moe/experts"):
        h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, w_gate)) * jnp.einsum(
            "td,edf->etf", xf, w_up)
        y = jnp.einsum("etf,efd->etd", h, w_down)             # (E_l, T, d)
    with jax.named_scope("moe/combine"):
        out = jnp.einsum("etd,te->td", y.astype(jnp.float32), gates)
        out = out.astype(x.dtype)
        if model_axis:
            out = jax.lax.psum(out, model_axis)
    return out.reshape(b, s, d)


def _moe_local(cfg, x, router, w_gate, w_up, w_down, *, e_local0, n_local,
               capacity, model_axis=None, dp_axes=None):
    """Per-shard training body. x: (B_l, S, d); weights: local expert
    slices."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    probs, top_p, top_i = _route(cfg, xf, router)             # (T, E), (T, k)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.mean(
        (jax.nn.one_hot(top_i, cfg.n_experts, dtype=jnp.float32)).sum(1), axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)

    tok_ids = jnp.arange(t)

    def one_expert(j):
        e = e_local0 + j
        match = (top_i == e)                                  # (T, k)
        gate = jnp.sum(jnp.where(match, top_p, 0.0), axis=-1)  # (T,)
        hit = jnp.any(match, axis=-1)                         # (T,)
        pos = jnp.cumsum(hit) - 1
        keep = hit & (pos < capacity)
        slot = jnp.where(keep, pos, capacity)                 # drop slot = C
        # gather tokens into the expert buffer (C+1 rows, last = trash)
        buf = jnp.zeros((capacity + 1, d), x.dtype)
        buf = buf.at[slot].add(jnp.where(keep[:, None], xf, 0))
        src = jnp.zeros((capacity + 1,), jnp.int32).at[slot].set(
            jnp.where(keep, tok_ids, 0))
        occ = jnp.zeros((capacity + 1,), jnp.float32).at[slot].add(
            keep.astype(jnp.float32))
        return buf[:capacity], src[:capacity], occ[:capacity], gate

    bufs, srcs, occs, gates = jax.vmap(one_expert)(jnp.arange(n_local))
    # expert FFNs as batched matmuls over (E_local, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufs, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", bufs, w_up)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)                  # (E_l, C, d)
    # combine: scatter-add back to token rows, weighted by router prob
    w = jnp.take_along_axis(gates, srcs, axis=1) * occs        # (E_l, C)
    out = jnp.zeros((t, d), jnp.float32)
    out = out.at[srcs.reshape(-1)].add(
        (y * w[..., None].astype(y.dtype)).reshape(-1, d).astype(jnp.float32))
    out = out.astype(x.dtype)
    if model_axis:
        out = jax.lax.psum(out, model_axis)
    return out.reshape(b, s, d), aux


def apply_moe(cfg, p: Dict[str, Any], x: jax.Array, rules,
              mode: str = "train") -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss). Dispatches to shard_map when a mesh with a
    ``model`` axis is ambient; otherwise runs the single-shard body.  Any
    ``mode`` but ``"train"`` (prefill, decode) takes the drop-free body, whose
    aux loss is zero."""
    mesh = get_abstract_mesh_or_none()
    mapped = mesh is not None and not mesh.empty and "model" in mesh.axis_names
    serving = mode != "train"
    no_aux = jnp.zeros((), jnp.float32)
    if not mapped:
        if serving:
            return _moe_dropfree(cfg, x, p["router"], p["w_gate"], p["w_up"],
                                 p["w_down"], e_local0=0,
                                 n_local=cfg.n_experts), no_aux
        cap = _capacity(cfg, x.shape[0] * x.shape[1])
        return _moe_local(cfg, x, p["router"], p["w_gate"], p["w_up"],
                          p["w_down"], e_local0=0, n_local=cfg.n_experts,
                          capacity=cap)

    tp = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    n_local = cfg.n_experts // tp
    b, s, _ = x.shape
    cap = _capacity(cfg, (b // dp) * s)

    def body(x_l, router, wg, wu, wd):
        mi = jax.lax.axis_index("model")
        if serving:
            return _moe_dropfree(cfg, x_l, router, wg, wu, wd,
                                 e_local0=mi * n_local, n_local=n_local,
                                 model_axis="model"), no_aux
        return _moe_local(cfg, x_l, router, wg, wu, wd,
                          e_local0=mi * n_local, n_local=n_local,
                          capacity=cap, model_axis="model", dp_axes=dp_axes)

    batch_axes = dp_axes if dp_axes else None
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_axes, None, None), P()),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux
