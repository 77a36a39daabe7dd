"""Generic pattern-stacked language model.

One machine covers all decoder-only assigned archs:
  dense (llama/qwen/internvl-backbone), windowed patterns (gemma3 "LLLLLG"),
  MoE (qwen3-moe / olmoe), SSM (mamba2, pattern "M"), hybrid (recurrentgemma
  "RRA"->"R","R","L").

Layers are grouped by the repeating pattern unit and scanned with stacked
parameters (compact HLO -> fast 512-device SPMD compiles); remainder layers
("tail") are applied unrolled.  Every layer = temporal-mixing(kind) +
optional FFN (dense MLP or MoE).

Modes: "train" (no cache), "prefill" (writes cache), "decode" (one token).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels import paged_attention as paged_attn
from repro.models import attention as attn_mod
from repro.models import hybrid as hybrid_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_embedding, apply_lm_head, apply_mlp,
                                 apply_rmsnorm, apply_rope, embedding_abstract,
                                 mlp_abstract, rmsnorm_abstract)
from repro.sharding import (LogicalArray, constrain,
                            get_abstract_mesh_or_none)

Params = Dict[str, Any]

ATTN_KINDS = ("G", "L")


def default_unit(cfg) -> Tuple[str, ...]:
    if cfg.layer_pattern:
        return cfg.layer_pattern
    if cfg.family == "ssm":
        return ("M",)
    return ("G",)


def split_layers(cfg) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    unit = default_unit(cfg)
    n_groups = cfg.n_layers // len(unit)
    tail = tuple(unit[i % len(unit)]
                 for i in range(n_groups * len(unit), cfg.n_layers))
    return unit, n_groups, tail


def _stack_abstract(tree, n: int):
    return jax.tree.map(
        lambda la: LogicalArray((n,) + la.shape, la.dtype, ("layers",) + la.logical),
        tree, is_leaf=lambda x: isinstance(x, LogicalArray))


# ---------------------------------------------------------------------------
# attention layer
# ---------------------------------------------------------------------------

def _attn_abstract(cfg) -> Params:
    d, dt = cfg.d_model, cfg.dtype
    hd = cfg.resolved_head_dim
    p = {
        "ln": rmsnorm_abstract(d, dt),
        "wq": LogicalArray((d, cfg.n_heads * hd), dt, ("embed_fsdp", "heads")),
        "wk": LogicalArray((d, cfg.n_kv_heads * hd), dt,
                           ("embed_fsdp", "kv_heads_w")),
        "wv": LogicalArray((d, cfg.n_kv_heads * hd), dt,
                           ("embed_fsdp", "kv_heads_w")),
        "wo": LogicalArray((cfg.n_heads * hd, d), dt, ("heads", "embed_fsdp")),
    }
    if cfg.qk_norm == "full":
        p["q_norm"] = LogicalArray((cfg.n_heads * hd,), dt, ("heads",))
        p["k_norm"] = LogicalArray((cfg.n_kv_heads * hd,), dt,
                                   ("kv_heads_w",))
    elif cfg.qk_norm:
        p["q_norm"] = rmsnorm_abstract(hd, dt)
        p["k_norm"] = rmsnorm_abstract(hd, dt)
    return p


def _cache_heads(cfg) -> int:
    return cfg.decode_cache_heads or cfg.n_kv_heads


def _attn_cache_abstract(cfg, kind, batch, cache_len, ring=True) -> Params:
    """``ring=False`` gives windowed ("L") layers a full-length buffer
    instead of the window-sized ring — the layout the paged arena needs,
    where logical block j must hold positions [j*bs, (j+1)*bs)."""
    hd = cfg.resolved_head_dim
    c = cache_len
    if ring and kind == "L" and cfg.local_window:
        c = min(cfg.local_window, cache_len)
    shp = (batch, c, _cache_heads(cfg), hd)
    la = ("batch", None, "kv_heads", None)
    return {"k": LogicalArray(shp, cfg.dtype, la),
            "v": LogicalArray(shp, cfg.dtype, la)}


def _decode_kv_spec(cfg):
    """Sharding for the repeated decode KV: heads when they divide the TP
    degree, else head_dim (never forces a cross-layout reshard of the cache)."""
    mesh = get_abstract_mesh_or_none()
    tp = 1
    if mesh is not None and not mesh.empty and "model" in mesh.axis_names:
        tp = mesh.shape["model"]
    ch = _cache_heads(cfg)
    if tp <= 1 or (ch % tp == 0 and cfg.n_heads % tp == 0):
        return ("batch", None, "heads", None)
    if cfg.resolved_head_dim % tp == 0:
        return ("batch", None, None, "heads")   # model axis on head_dim
    return ("batch", None, None, None)


def _kv_head_shards(rules) -> Tuple[Tuple[str, ...], int]:
    """The mesh axes the ambient mesh splits KV heads over (the axes
    ``rules`` maps them to), and the number of shards; ((), 1) without a
    mesh."""
    mesh = get_abstract_mesh_or_none()
    axes = rules.get("kv_heads")
    if mesh is None or axes is None:
        return (), 1
    axes = axes if isinstance(axes, (tuple, list)) else (axes,)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    return axes, math.prod(mesh.shape[a] for a in axes)


def _paged_attention_impl(cfg, rules, window: int) -> str:
    """The paged decode read a layer takes: the Pallas kernel over live
    blocks where the backend runs one (``ops.default_impl()``), the layer
    attends its whole context (``window == 0``) and its KV heads are
    unsharded or split evenly, query heads alike, over the axes ``rules``
    maps them to (the kernel then runs per shard); else the XLA gather of
    every slot's whole block table (the head_dim layout, where the heads
    do not divide)."""
    impl = ops.default_impl()
    if impl == "xla" or window:
        return "xla"
    _, n = _kv_head_shards(rules)
    if n > 1 and (_cache_heads(cfg) % n or cfg.n_heads % n
                  or rules.get("heads") != rules.get("kv_heads")):
        return "xla"
    return impl


def _paged_decode_kernel(rules, q, k_arena, v_arena, block_table, lengths,
                         layer, impl: str):
    """The kernel over the whole stack of arenas at ``layer``, or, where a
    mesh splits the KV heads, once per shard under ``shard_map``: queries,
    arenas and output split on heads, block table, lengths and layer
    replicated, no collective (a shard's query heads read only its own KV
    heads)."""
    call = functools.partial(ops.paged_decode_attention, impl=impl)
    axes, n = _kv_head_shards(rules)
    if n == 1:
        return call(q, k_arena, v_arena, block_table, lengths, layer)
    heads = P(None, axes, None)
    arena = P(None, None, None, axes, None)
    # check_vma off: the kernel's output shape carries no varying axes
    return jax.shard_map(
        call, mesh=get_abstract_mesh_or_none(),
        in_specs=(heads, arena, arena, P(), P(), P()), out_specs=heads,
        check_vma=False,
    )(q, k_arena, v_arena, block_table, lengths, layer)


def paged_attention_context() -> str:
    """The paged decode read this backend selects, for the fingerprint
    context of every program that decodes from a paged arena: the step's
    source does not show model code, and a program store must never hand a
    program built on one read to an engine built on the other."""
    impl = ops.default_impl()
    return repr(("paged_attention", impl,
                 None if impl == "xla" else paged_attn.VERSION))


def _write_prefill_cache(cache_kv, full, window: int, lengths=None):
    """Write prefill keys/values (B,S,..) into a cache buffer (B,C,..).

    ``lengths`` (B,) marks the valid (un-padded) length of each row.  For
    ring (window) caches the ring invariant is: slot j holds position p with
    p % window == j, for the *last* window valid positions — with right-
    padded rows that set differs per row, so the slots are gathered
    per-row instead of rolled.  Slots beyond a row's length hold arbitrary
    values; decode masks them via its per-slot valid-length check.
    """
    b, s = full.shape[0], full.shape[1]
    c = cache_kv.shape[1]
    if window and c == window and s >= window:
        if lengths is None:
            ring = jnp.roll(full[:, s - window:], (s - window) % window,
                            axis=1)
            return ring.astype(cache_kv.dtype)
        lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                                (b,)).reshape(b, 1)
        j = jnp.arange(window)[None, :]
        # latest valid position p with p % window == j (negative when the
        # row is shorter than j+1 positions: clamped, masked at decode)
        p = lens - 1 - ((lens - 1 - j) % window)
        p = jnp.clip(p, 0, s - 1)
        ring = jnp.take_along_axis(full, p[:, :, None, None], axis=1)
        return ring.astype(cache_kv.dtype)
    return jax.lax.dynamic_update_slice(
        cache_kv, full[:, :c].astype(cache_kv.dtype), (0, 0, 0, 0))


def _apply_attn(cfg, p: Params, x, *, rules, mode, cache, pos, kind,
                block_table=None, live=None, layer=None):
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    window = cfg.local_window if kind == "L" else 0
    theta = cfg.rope_theta
    if kind == "L" and cfg.rope_theta_local is not None:
        theta = cfg.rope_theta_local

    residual = x
    xn = apply_rmsnorm(p["ln"], x, cfg.norm_eps)
    ch = _cache_heads(cfg)
    wk, wv = p["wk"], p["wv"]
    if ch != cfg.n_kv_heads:
        # kv WEIGHT folding (decode_cache_heads=R): tile wk/wv from kv heads
        # to R so k/v come out natively R-head-sharded — no activation-side
        # repeat across shard boundaries, no extra per-device FLOPs, at the
        # cost of an R/kv x larger KV cache.  §Perf HC1/HC3.
        rep = ch // cfg.n_kv_heads
        wk = jnp.repeat(wk.reshape(d, cfg.n_kv_heads, hd), rep, axis=1
                        ).reshape(d, ch * hd)
        wv = jnp.repeat(wv.reshape(d, cfg.n_kv_heads, hd), rep, axis=1
                        ).reshape(d, ch * hd)
    q = jnp.einsum("bsd,dh->bsh", xn, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", xn, wk)
    if cfg.qk_norm == "full":
        # one norm over the whole projection width, before the split into
        # heads: under a mesh that splits the heads, its mean of squares is
        # a reduction across devices
        k_norm = p["k_norm"]
        if ch != cfg.n_kv_heads:
            k_norm = jnp.repeat(k_norm.reshape(cfg.n_kv_heads, hd),
                                ch // cfg.n_kv_heads, axis=0).reshape(-1)
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(k_norm, k, cfg.norm_eps)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, ch, hd)
    v = jnp.einsum("bsd,dh->bsh", xn, wv).reshape(b, s, ch, hd)
    q = constrain(q, ("batch", "seq_attn", "heads", None), rules)
    if ch != cfg.n_kv_heads:
        k = constrain(k, ("batch", "seq_attn", "kv_heads", None), rules)
        v = constrain(v, ("batch", "seq_attn", "kv_heads", None), rules)
    elif rules.get("kv_heads_w", "model") is None:
        # kv projections replicated (kv_heads % tp != 0): pin k/v replicated
        # so the cache write can't back-propagate a conflicting sharding
        k = constrain(k, ("batch", "seq_attn", None, None), rules)
        v = constrain(v, ("batch", "seq_attn", None, None), rules)
    if cfg.qk_norm and cfg.qk_norm != "full":
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)

    new_cache = None
    out_spec = ("batch", "seq_attn", "heads", None)
    if mode == "decode":
        assert cache is not None
        # ``pos`` is () (whole batch at one position) or (B,) — per-slot
        # positions for continuous batching: each row RoPE-rotates, writes
        # its KV row and masks attention at its own absolute position.
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        q = apply_rope(q, pos_b[:, None], theta)
        k = apply_rope(k, pos_b[:, None], theta)
        ch = _cache_heads(cfg)
        k = attn_mod.repeat_kv(k, ch)
        v = attn_mod.repeat_kv(v, ch)
        if block_table is not None:
            # paged KV: the cache leaf is a stack of (P, bs, ch, hd)
            # physical-block arenas shared by every slot, one per layer,
            # and this layer's is ``layer``: the write scatters into the
            # stack and the read indexes it, so nothing slices the layer's
            # arena out (_run_stack carries the stack through its scan).
            # This row's write destination and its reads both resolve
            # through the block table (the data-page jump table of
            # repro.core.paging).  The read is the
            # Pallas kernel over each row's live blocks where
            # _paged_attention_impl allows it, else the XLA gather of the
            # whole table with the simple full-repeat attention (no
            # head_dim-sharded GQA variant).  The scopes name the paged-KV
            # work in each operation's metadata, so a device trace can
            # charge it to this layer.
            with jax.named_scope("paged_kv/write"):
                k_arena = attn_mod.write_paged_kv(
                    cache["k"], layer, block_table, pos_b, k[:, 0], live=live)
                v_arena = attn_mod.write_paged_kv(
                    cache["v"], layer, block_table, pos_b, v[:, 0], live=live)
            impl = _paged_attention_impl(cfg, rules, window)
            if impl == "xla":
                with jax.named_scope("paged_kv/gather"):
                    k_log = attn_mod.gather_paged_kv(k_arena, layer,
                                                     block_table)
                    v_log = attn_mod.gather_paged_kv(v_arena, layer,
                                                     block_table)
                with jax.named_scope("paged_kv/attend"):
                    out = attn_mod.decode_attention(
                        q, k_log, v_log, pos_b + 1, window=window, ring=False)
            else:
                with jax.named_scope("paged_kv/attend"):
                    out = _paged_decode_kernel(
                        rules, q[:, 0], k_arena, v_arena, block_table,
                        pos_b + 1, layer, impl)[:, None]
            out = constrain(out, out_spec, rules)
            out = jnp.einsum("bsh,hd->bsd",
                             out.reshape(b, s, cfg.n_heads * hd), p["wo"])
            out = constrain(out, ("batch", "seq", "embed"), rules)
            return residual + out, {"k": k_arena, "v": v_arena}
        c = cache["k"].shape[1]
        ring = bool(window) and c == window
        slot = (pos_b % c).astype(jnp.int32)
        # per-row write as an elementwise one-hot select: a scatter with
        # per-batch indices forces GSPMD into an involuntary full-remat of
        # the cache, while where() keeps the cache's sharding untouched
        hit = jnp.arange(c)[None, :] == slot[:, None]
        if not ring:
            # non-ring buffers address slots absolutely: a position past the
            # buffer (an idle slot left ticking, or speculative overshoot
            # past a request's horizon) must drop, not wrap-corrupt slot 0
            hit &= (pos_b < c)[:, None]
        if live is not None:
            # fused-horizon freeze: a finished row's KV must not move while
            # the rest of the batch keeps decoding (a ring write would land
            # inside the row's still-valid window)
            hit &= live[:, None]
        hit = hit[:, :, None, None]
        k_cache = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
        v_cache = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
        # sharding for the (huge) cache: heads when they divide TP cleanly,
        # else head_dim.  The head_dim path uses grouped-GQA math (no repeat
        # buffer, no resharding of the cache; costs one scores psum per
        # layer — see EXPERIMENTS.md §Perf decode hillclimb).
        spec = _decode_kv_spec(cfg)
        if spec[-1] is None and spec[2] == "heads":
            k_full = constrain(attn_mod.repeat_kv(k_cache, cfg.n_heads),
                               spec, rules)
            v_full = constrain(attn_mod.repeat_kv(v_cache, cfg.n_heads),
                               spec, rules)
            out = attn_mod.decode_attention(
                q, k_full, v_full, pos_b + 1, window=window, ring=ring)
        else:
            q = constrain(q, ("batch", None, None, "heads"), rules)
            k_c = constrain(k_cache, spec, rules)
            v_c = constrain(v_cache, spec, rules)
            out = attn_mod.decode_attention_gqa(
                q, k_c, v_c, pos_b + 1, window=window, ring=ring)
            # keep the output head_dim-sharded: pulling it to heads-sharded
            # here would force GSPMD to reshard the cache for the p@v dot
            # (involuntary full-replication fallback)
            out_spec = ("batch", "seq_attn", None, "heads")
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        positions = jnp.arange(s)[None] * jnp.ones((b, 1), jnp.int32)
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
        if mode == "prefill":
            assert cache is not None
            ch = _cache_heads(cfg)
            # in prefill mode ``pos`` carries the per-row valid lengths
            new_cache = {
                "k": _write_prefill_cache(cache["k"],
                                          attn_mod.repeat_kv(k, ch), window,
                                          lengths=pos),
                "v": _write_prefill_cache(cache["v"],
                                          attn_mod.repeat_kv(v, ch), window,
                                          lengths=pos)}
        # repeat kv -> full heads with one consistent 'heads' sharding
        # (avoids grouped-reshape sharding conflicts; see attention.py)
        k = constrain(attn_mod.repeat_kv(k, cfg.n_heads),
                      ("batch", "seq_attn", "heads", None), rules)
        v = constrain(attn_mod.repeat_kv(v, cfg.n_heads),
                      ("batch", "seq_attn", "heads", None), rules)
        out = attn_mod.attention(
            q, k, v, causal=True, window=window,
            chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
            impl=cfg.attn_impl)

    out = constrain(out, out_spec, rules)
    out = jnp.einsum("bsh,hd->bsd", out.reshape(b, s, cfg.n_heads * hd), p["wo"])
    out = constrain(out, ("batch", "seq", "embed"), rules)
    return residual + out, new_cache


# ---------------------------------------------------------------------------
# full layer = mixing + optional FFN
# ---------------------------------------------------------------------------

def layer_abstract(cfg, kind: str) -> Params:
    if kind in ATTN_KINDS:
        p = {"mix": _attn_abstract(cfg)}
    elif kind == "M":
        p = {"mix": ssm_mod.ssm_abstract(cfg)}
    elif kind == "R":
        p = {"mix": hybrid_mod.rglru_abstract(cfg)}
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0:
        if cfg.family == "moe":
            p["ffn_ln"] = rmsnorm_abstract(cfg.d_model, cfg.dtype)
            p["moe"] = moe_mod.moe_abstract(cfg)
        else:
            p["ffn_ln"] = rmsnorm_abstract(cfg.d_model, cfg.dtype)
            p["mlp"] = mlp_abstract(cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def layer_cache_abstract(cfg, kind: str, batch: int, cache_len: int,
                         ring: bool = True):
    if kind in ATTN_KINDS:
        return _attn_cache_abstract(cfg, kind, batch, cache_len, ring=ring)
    if kind == "M":
        return ssm_mod.ssm_cache_abstract(cfg, batch)
    if kind == "R":
        return hybrid_mod.rglru_cache_abstract(cfg, batch)
    raise ValueError(kind)


def apply_layer(cfg, kind: str, p: Params, x, *, rules, mode, cache, pos,
                block_table=None, live=None, layer=None):
    aux = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        x, new_cache = _apply_attn(cfg, p["mix"], x, rules=rules, mode=mode,
                                   cache=cache, pos=pos, kind=kind,
                                   block_table=block_table, live=live,
                                   layer=layer)
    elif kind == "M":
        x, new_cache = ssm_mod.apply_ssm_layer(cfg, p["mix"], x, rules=rules,
                                               mode=mode, cache=cache,
                                               live=live)
    elif kind == "R":
        x, new_cache = hybrid_mod.apply_rglru_layer(cfg, p["mix"], x,
                                                    rules=rules, mode=mode,
                                                    cache=cache, live=live)
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0:
        residual = x
        xn = apply_rmsnorm(p["ffn_ln"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            if cfg.family == "moe":
                out, aux = moe_mod.apply_moe(cfg, p["moe"], xn, rules,
                                             mode=mode)
            else:
                out = apply_mlp(p["mlp"], xn, rules)
        x = residual + out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# whole-model params / cache
# ---------------------------------------------------------------------------

def abstract_params(cfg) -> Params:
    unit, n_groups, tail = split_layers(cfg)
    group = {f"slot{i}": layer_abstract(cfg, k) for i, k in enumerate(unit)}
    params: Params = {
        "embed": embedding_abstract(cfg.padded_vocab, cfg.d_model, cfg.dtype),
        "groups": _stack_abstract(group, n_groups),
        "tail": {f"tail{i}": layer_abstract(cfg, k) for i, k in enumerate(tail)},
        "final_norm": rmsnorm_abstract(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = LogicalArray(
            (cfg.d_model, cfg.padded_vocab), cfg.dtype, ("embed", "vocab"))
    return params


def abstract_cache(cfg, batch: int, cache_len: int, ring: bool = True) -> Params:
    """Decode-state tree: per-layer KV/recurrent buffers plus a per-slot
    ``pos`` vector (B,) — each batch row's absolute decode position.  The
    position travels WITH the cache so hot-loaded decode programs need no
    host-fed position argument and rows can sit at diverging positions
    (continuous batching)."""
    unit, n_groups, tail = split_layers(cfg)
    group = {f"slot{i}": layer_cache_abstract(cfg, k, batch, cache_len,
                                              ring=ring)
             for i, k in enumerate(unit)}
    return {
        "pos": LogicalArray((batch,), jnp.int32, ("batch",)),
        "groups": _stack_abstract(group, n_groups),
        "tail": {f"tail{i}": layer_cache_abstract(cfg, k, batch, cache_len,
                                                  ring=ring)
                 for i, k in enumerate(tail)},
    }


def abstract_paged_cache(cfg, batch: int, cache_len: int, *, kv_block: int,
                         arena_blocks: int) -> Params:
    """Paged decode-state tree (repro.core.paging).

    Attention layers trade the per-slot (B, C, ...) buffer for a shared
    physical-block **arena** (arena_blocks, kv_block, heads, head_dim)
    addressed through a per-slot ``block_table`` (B, cache_len/kv_block)
    carried next to ``pos`` (-1 = unmapped).  Recurrent layers (SSM /
    RG-LRU) keep their O(1)-size per-slot state dense.  Windowed ("L")
    layers store the full logical length (no ring) — window masking happens
    at attention time, so the arena layout is uniform across layer kinds.
    """
    assert cache_len % kv_block == 0, (cache_len, kv_block)
    unit, n_groups, tail = split_layers(cfg)
    hd = cfg.resolved_head_dim

    def layer_c(kind):
        if kind in ATTN_KINDS:
            shp = (arena_blocks, kv_block, _cache_heads(cfg), hd)
            la = (None, None, "kv_heads", None)
            return {"k": LogicalArray(shp, cfg.dtype, la),
                    "v": LogicalArray(shp, cfg.dtype, la)}
        return layer_cache_abstract(cfg, kind, batch, cache_len)

    group = {f"slot{i}": layer_c(k) for i, k in enumerate(unit)}
    return {
        "pos": LogicalArray((batch,), jnp.int32, ("batch",)),
        "block_table": LogicalArray((batch, cache_len // kv_block),
                                    jnp.int32, ("batch", None)),
        "groups": _stack_abstract(group, n_groups),
        "tail": {f"tail{i}": layer_c(k) for i, k in enumerate(tail)},
    }


def paged_block_bytes(cfg, kv_block: int) -> int:
    """Bytes one KV block occupies across every attention layer (k + v) —
    the page-size unit of the arena's byte-capacity accounting."""
    n_attn = sum(1 for k in cfg.pattern_for_layers() if k in ATTN_KINDS)
    itemsize = jnp.zeros((), cfg.dtype).dtype.itemsize
    return 2 * n_attn * kv_block * _cache_heads(cfg) * \
        cfg.resolved_head_dim * itemsize


def init_params(cfg, key) -> Params:
    from repro.models.layers import materialize
    return materialize(abstract_params(cfg), key)


def init_cache(cfg, batch: int, cache_len: int, ring: bool = True) -> Params:
    return jax.tree.map(
        lambda la: jnp.zeros(la.shape, la.dtype),
        abstract_cache(cfg, batch, cache_len, ring=ring),
        is_leaf=lambda x: isinstance(x, LogicalArray))


def init_paged_cache(cfg, batch: int, cache_len: int, *, kv_block: int,
                     arena_blocks: int) -> Params:
    tree = jax.tree.map(
        lambda la: jnp.zeros(la.shape, la.dtype),
        abstract_paged_cache(cfg, batch, cache_len, kv_block=kv_block,
                             arena_blocks=arena_blocks),
        is_leaf=lambda x: isinstance(x, LogicalArray))
    tree["block_table"] = jnp.full((batch, cache_len // kv_block), -1,
                                   jnp.int32)
    return tree


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _maybe_remat(cfg, fn, mode):
    if mode != "train" or cfg.remat_policy == "full":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


def _run_stack(cfg, params, x, *, rules, mode, caches, pos, block_table=None,
               live=None):
    unit, n_groups, tail = split_layers(cfg)
    aux0 = jnp.zeros((), jnp.float32)
    # Paged decode: each attention slot's stacked arenas (n_groups, P, bs,
    # Hkv, D) ride in the scan's carry and every layer writes and reads them
    # by its index, so the loop updates them in place.  Scanned as xs/ys
    # they would be sliced out layer by layer and stacked back into a fresh
    # buffer, whole-arena copies on every step.  Per-slot recurrent state
    # stays in xs/ys.
    paged = block_table is not None
    carried = [f"slot{i}" for i, k in enumerate(unit)
               if paged and k in ATTN_KINDS]

    def group_body(carry, xs):
        x, aux, arenas = carry
        gp, gc, layer = xs
        arenas, new_gc = dict(arenas), {}
        for i, kind in enumerate(unit):
            slot = f"slot{i}"
            if slot in arenas:
                cache = arenas[slot]
            else:
                cache = None if gc is None else gc[slot]
            x, nc, a = apply_layer(
                cfg, kind, gp[slot], x, rules=rules, mode=mode, cache=cache,
                pos=pos, block_table=block_table, live=live, layer=layer)
            if slot in arenas:
                arenas[slot] = nc
            else:
                new_gc[slot] = nc
            aux = aux + a
        x = constrain(x, ("batch", "seq", "embed"), rules)
        return (x, aux, arenas), (None if mode == "train" else new_gc)

    body = _maybe_remat(cfg, group_body, mode)
    if n_groups > 0:
        gc = None if mode == "train" else dict(caches["groups"])
        arenas = {slot: gc.pop(slot) for slot in carried}
        layers = jnp.arange(n_groups, dtype=jnp.int32) if carried else None
        (x, aux, arenas), new_group_caches = jax.lax.scan(
            body, (x, aux0, arenas), (params["groups"], gc, layers))
        if carried:
            new_group_caches = {**new_group_caches, **arenas}
    else:
        new_group_caches, aux = None, aux0

    new_tail = {}
    for i, kind in enumerate(tail):
        name = f"tail{i}"
        cache = None if caches is None else caches["tail"][name]
        # an unscanned layer's arenas go through the same path as a stack
        # of one, read and written at layer 0 (the added axis is a bitcast)
        stacked = paged and kind in ATTN_KINDS
        if stacked:
            cache = jax.tree.map(lambda a: a[None], cache)
        x, nc, a = apply_layer(
            cfg, kind, params["tail"][name], x, rules=rules, mode=mode,
            cache=cache, pos=pos, block_table=block_table, live=live,
            layer=jnp.int32(0) if stacked else None)
        if stacked:
            nc = jax.tree.map(lambda a: a[0], nc)
        new_tail[name] = nc
        aux = aux + a

    new_caches = None
    if mode != "train":
        new_caches = {"groups": new_group_caches, "tail": new_tail}
    return x, new_caches, aux


def embed_inputs(cfg, params, tokens, prefix_embeds, rules):
    x = apply_embedding(params["embed"], tokens, rules)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return constrain(x, ("batch", "seq", "embed"), rules)


@jax.named_scope("logits")
def logits_from_hidden(cfg, params, x, rules):
    x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return apply_lm_head(params["embed"], x, rules, transpose=True)
    return apply_lm_head(params["lm_head"], x, rules)


def forward(cfg, params, tokens, *, rules, prefix_embeds=None, mode="train",
            caches=None, lengths=None):
    """tokens: (B, S_tok); prefix_embeds: (B, P, d) stub frontend embeddings.

    ``lengths`` (B,) marks per-row valid (un-padded) lengths for prefill of
    right-padded rows; defaults to the full sequence length.  In prefill
    mode the returned cache tree carries ``pos`` = lengths, i.e. each row's
    next decode position.

    Returns (logits (B, S, V_padded), new_caches_or_None, aux_loss).
    """
    x = embed_inputs(cfg, params, tokens, prefix_embeds, rules)
    b, s = x.shape[0], x.shape[1]
    if lengths is None:
        pos = jnp.full((b,), s, jnp.int32)
    else:
        pos = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    x, new_caches, aux = _run_stack(cfg, params, x, rules=rules, mode=mode,
                                    caches=caches, pos=pos)
    logits = logits_from_hidden(cfg, params, x, rules)
    if new_caches is not None:
        new_caches["pos"] = pos
    return logits, new_caches, aux


@jax.named_scope("sample")
def greedy_token(cfg, logits):
    """THE greedy-decoding argmax, shared by every decode mode.

    Masks vocab padding before the argmax; works over any leading dims
    (``logits`` (..., V_padded) -> (...) int32).  Single definition on
    purpose: the serving engine's bit-exactness guarantees (sequential ==
    verify == horizon) rest on all three computing the same token — a
    drifted copy would silently break the whole exactness matrix.
    """
    valid = jnp.arange(logits.shape[-1]) < cfg.vocab_size
    return jnp.argmax(jnp.where(valid, logits, -jnp.inf),
                      axis=-1).astype(jnp.int32)


def verify_decode(cfg, params, caches, tokens, *, rules):
    """Speculative verify: score S = k+1 tokens in ONE program, accept the
    longest greedy-matching draft prefix, roll rejected state back.

    tokens: (B, S) int32 — per row, the last accepted token followed by k
    draft tokens.  Returns ``(new_caches, out_tokens (B, S), n_new (B,))``:
    row b's accepted continuation is ``out_tokens[b, :n_new[b]]`` and its
    cache holds exactly the state of having decoded those tokens one at a
    time (``pos`` advanced by ``n_new``).

    Exactness by construction: the forward is a ``lax.scan`` of the SAME
    per-token :func:`decode_step` the non-speculative engine dispatches, so
    every candidate's logits are bit-identical to sequential decode —
    acceptance reproduces the sequential greedy stream exactly, never just
    approximately.  The scan amortizes S decode steps into one dispatch
    (the paper's re-execute-vs-reload lesson applied to the decode loop).
    The bits match only while XLA emits the scan body as it emits the
    top-level step.  Left to itself it hoists loop-invariant weight math
    out of the loop (on the CPU, the hybrid family's ``1 + scale`` norm
    weight, which changes the fused projection's rounding by one ulp), so
    the body passes the weights through an optimization barrier tied to
    the step's token.

    Rollback, per cache representation:
      * attention KV (dense or windowed non-ring): rejected positions sit
        at slots >= the rolled-back ``pos``; their bytes are restored from
        the pre-verify buffer so the tree is byte-identical to sequential
        decode (ring layouts are excluded — a rejected ring write lands on
        a slot still inside the window; the speculative engine therefore
        runs ``ring=False`` buffers);
      * paged KV: rejected writes are scatter-restored through the block
        table (:func:`repro.models.attention.rollback_paged_kv`);
      * recurrent state (SSM/RG-LRU): the scan snapshots each step's
        per-slot state and the accepted step's snapshot is selected per
        row — restoring the exact pre-rejection recurrence.
    """
    # the cache-tree leaf taxonomy (kv / state / meta, batch axis) is owned
    # by the pager, which walks the same trees host-side
    from repro.core.paging import leaf_axis, leaf_kind
    from repro.models import attention as attn_mod
    b, s = tokens.shape
    pos0 = caches["pos"]
    block_table = caches.get("block_table")
    orig = caches

    def body(c, tok):
        # tie the weights to a per-step value so XLA cannot hoist their
        # loop-invariant math (e.g. the norm's ``1 + scale``) out of the
        # scan: the body then compiles as the top-level step does
        p, tok = jax.lax.optimization_barrier((params, tok))
        logits, c2 = decode_step(cfg, p, c, tok[:, None], rules=rules)
        y = greedy_token(cfg, logits[:, 0])
        rec = [leaf for path, leaf in
               jax.tree_util.tree_flatten_with_path(c2)[0]
               if leaf_kind(path) == "state"]
        return c2, (y, rec)

    final, (ys, recs) = jax.lax.scan(body, caches, jnp.transpose(tokens))
    ys = jnp.transpose(ys)                                     # (B, S)
    # leading greedy matches: draft i+1 accepted iff it equals the model's
    # prediction at input i; +1 for the model's own (always-kept) token
    match = (tokens[:, 1:] == ys[:, :-1]).astype(jnp.int32)
    n_new = 1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1)    # (B,)
    pos_new = pos0 + n_new

    rec_stacked = iter(recs)
    if block_table is not None:
        pos_cand = pos0[:, None] + jnp.arange(s)[None, :]      # (B, S)
        reject = jnp.arange(s)[None, :] >= n_new[:, None]

    def fix(path, leaf, old):
        kind = leaf_kind(path)
        if kind == "state":
            # stacked: (S, ...) with batch at leaf_axis + 1; pick, per
            # row, the state after its last accepted input (step n_new-1)
            stacked = next(rec_stacked)
            shape = [1] * stacked.ndim
            shape[leaf_axis(path) + 1] = b
            idx = jnp.broadcast_to((n_new - 1).reshape(shape),
                                   (1,) + stacked.shape[1:])
            return jnp.take_along_axis(stacked, idx, axis=0)[0]
        if kind == "kv":
            if block_table is not None:
                if leaf_axis(path) == 1:        # leading (layers,) axis
                    return jax.vmap(
                        attn_mod.rollback_paged_kv,
                        in_axes=(0, 0, None, None, None))(
                        leaf, old, block_table, pos_cand, reject)
                return attn_mod.rollback_paged_kv(leaf, old, block_table,
                                                  pos_cand, reject)
            ba = leaf_axis(path)
            c = leaf.shape[ba + 1]
            keep = jnp.arange(c)[None, :] < pos_new[:, None]   # (B, C)
            shape = [1] * leaf.ndim
            shape[ba], shape[ba + 1] = b, c
            return jnp.where(keep.reshape(shape), leaf, old)
        return leaf

    new_caches = jax.tree_util.tree_map_with_path(fix, final, orig)
    if block_table is not None:
        # only mapped slots advance, mirroring the sequential paged decode
        # (-1 = unmapped; a shared-prefix head block encodes as -(p+2) and
        # is every bit as mapped)
        new_caches["pos"] = jnp.where(block_table[:, 0] != -1, pos_new, pos0)
    else:
        new_caches["pos"] = pos_new
    return new_caches, ys, n_new


def decode_step(cfg, params, caches, token, pos=None, *, rules, live=None):
    """token: (B, 1) int32; pos: () or (B,) int32 absolute position(s),
    defaulting to the per-slot ``pos`` vector carried in the cache tree.

    ``live`` (B,) bool freezes rows in-graph: a non-live row's KV write,
    recurrent-state update and ``pos`` advance are all masked out, so its
    cache tree is byte-identical before and after the step while the live
    rows step normally (the fused decode-horizon's per-slot termination —
    EOS or an exhausted budget mid-horizon must not perturb any state).
    ``None`` (the default) means every row is live and the step is exactly
    the classic one-token decode.

    Returns (logits (B, 1, V_padded), new_caches) where each live row's
    ``pos`` advanced by one.
    """
    b = token.shape[0]
    if pos is None:
        pos = caches["pos"]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    block_table = caches.get("block_table")
    x = apply_embedding(params["embed"], token, rules)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x, new_caches, _ = _run_stack(cfg, params, x, rules=rules, mode="decode",
                                  caches=caches, pos=pos,
                                  block_table=block_table, live=live)
    logits = logits_from_hidden(cfg, params, x, rules)
    advance = live
    if block_table is not None:
        # paged tree: the block table rides along unchanged, and only
        # mapped slots advance — an unmapped (released) slot's pos stays
        # frozen so its block index can never creep out of range.  A row
        # whose head block is a read-only shared mapping (-(p+2)) is
        # mapped; only the -1 sentinel means unmapped.
        new_caches["block_table"] = block_table
        mapped = block_table[:, 0] != -1
        advance = mapped if advance is None else advance & mapped
    new_caches["pos"] = (pos + 1 if advance is None
                         else jnp.where(advance, pos + 1, pos))
    return logits, new_caches


def decode_horizon(cfg, params, caches, tokens, budget, *, rules,
                   horizon: int, eos_id=None):
    """Fused multi-step decode: ``horizon`` greedy steps in ONE program.

    The host pays one dispatch (and one device→host sync) per *horizon*
    instead of per token — the paper's re-execute arithmetic applied to the
    generation loop itself: control stays resident on the device
    (``lax.scan``) and the boundary is crossed once per H tokens.

    tokens: (B, 1) int32 — each slot's last accepted token (the in-graph
    greedy feedback starts from it); budget: (B,) int32 — tokens row b may
    emit this horizon (``min(remaining max_new, remaining cache, H)``;
    0 holds the row frozen for the whole horizon, e.g. an empty slot).

    Per-slot termination is masked in-graph: a row freezes the step after
    it emits ``eos_id`` or exhausts its budget — its KV/recurrent state and
    ``pos`` stop moving (``decode_step(live=...)``) while the other rows
    keep decoding, so a mid-horizon finish perturbs nothing.

    Exactness by construction: the scan body is the SAME per-token
    :func:`decode_step` the sequential engine dispatches, and the fed-back
    token is the same vocab-masked argmax, so every live row's logits,
    emitted tokens and cache bytes are bit-identical to stepping one token
    at a time.

    Returns ``(new_caches, events)`` — the device-side event buffer read
    back with ONE transfer instead of per-step hostcalls:

      * ``events["tokens"]``   (B, H) int32: token emitted at each step
        (frozen rows repeat their last token; slice by ``n_emitted``);
      * ``events["n_emitted"]`` (B,) int32: valid tokens for row b — also
        its finish step when it terminated mid-horizon;
      * ``events["occupancy"]`` (H,) f32: fraction of rows live per step.
    """
    b = tokens.shape[0]

    def body(carry, _):
        caches, tok, emitted, live = carry
        p, tok = jax.lax.optimization_barrier((params, tok))  # as in verify
        logits, caches2 = decode_step(cfg, p, caches, tok, rules=rules,
                                      live=live)
        y = jnp.where(live, greedy_token(cfg, logits[:, 0]), tok[:, 0])
        emitted = emitted + live.astype(jnp.int32)
        next_live = live & (emitted < budget)
        if eos_id is not None:
            next_live &= y != eos_id
        occ = jnp.mean(live.astype(jnp.float32))
        return (caches2, y[:, None], emitted, next_live), (y, occ)

    live0 = budget > 0
    carry0 = (caches, tokens, jnp.zeros((b,), jnp.int32), live0)
    (new_caches, _, n_emitted, _), (ys, occ) = jax.lax.scan(
        body, carry0, None, length=horizon)
    events = {"tokens": jnp.transpose(ys), "n_emitted": n_emitted,
              "occupancy": occ}
    return new_caches, events
