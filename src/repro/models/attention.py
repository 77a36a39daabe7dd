"""Attention: chunked online-softmax (flash-style) in pure JAX.

GQA handling: weights and KV caches store ``n_kv_heads`` heads; K/V are
repeated up to ``n_heads`` on the fly *before* the attention math, so every
tensor entering these kernels carries a single (B, S, H, D) layout with one
consistent head sharding.  (Grouped-head einsums with kv_heads < tensor-
parallel degree force GSPMD into involuntary full rematerialization — the
repeat trades a free re-read of K/V for a clean 16-way head sharding; the
Pallas kernel performs the repeat implicitly via index_map, paying no HBM
duplication on TPU.)

Three execution paths:
  * ``chunked_attention``  — scan over (q-block, kv-block): O(S*ck) memory,
    masks out-of-range blocks (baseline; ~2x FLOPs waste on causal, full-seq
    compute for sliding windows).
  * ``blockwise_attention_unrolled`` — unrolled q blocks with *static*
    triangular / windowed kv ranges: no FLOPs on fully-masked blocks.  The
    beyond-paper compute optimization (EXPERIMENTS.md §Perf).
  * ``decode_attention``   — one query token against a KV cache (linear in S).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, H, D) by repeating each kv head G times."""
    hkv = k.shape[2]
    if hkv == n_heads:
        return k
    return jnp.repeat(k, n_heads // hkv, axis=2)


def _block_attend(qb, kb, vb, mask, scale):
    """qb: (B,cq,H,D) kb/vb: (B,ck,H,D) mask: (cq,ck) -> (o, m, l)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32) * scale
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                      # (B,H,cq)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb.dtype), vb)
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None].astype(o1.dtype) + o2 * a2[..., None].astype(o2.dtype)
    return o, m, l


def _finish(o, l):
    # o: (B,H,cq,D) l: (B,H,cq) -> (B,cq,H,D)
    out = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
    return out.transpose(0, 2, 1, 3)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      chunk_q: int = 512, chunk_k: int = 1024,
                      q_offset: int = 0) -> jax.Array:
    """Flash-style attention via nested lax.scan. q,k,v: (B,S,H,D)."""
    import math
    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    assert hk == h, "repeat_kv before calling"
    chunk_q = math.gcd(min(chunk_q, sq), sq)   # gcd fallback for odd lengths
    chunk_k = math.gcd(min(chunk_k, sk), sk)
    nq, nk = sq // chunk_q, sk // chunk_k
    scale = d ** -0.5

    qb = q.reshape(b, nq, chunk_q, h, d).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(b, nk, chunk_k, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, chunk_k, h, d).transpose(1, 0, 2, 3, 4)
    q_pos_base = jnp.arange(chunk_q)
    k_pos_base = jnp.arange(chunk_k)

    def q_step(_, qi_qblk):
        qi, qblk = qi_qblk
        q_pos = q_offset + qi * chunk_q + q_pos_base

        def kv_step(carry, kj_blk):
            o, m, l = carry
            kj, kblk, vblk = kj_blk
            k_pos = kj * chunk_k + k_pos_base
            mask = jnp.ones((chunk_q, chunk_k), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            o2, m2, l2 = _block_attend(qblk, kblk, vblk, mask, scale)
            return _merge(o, m, l, o2, m2, l2), None

        o0 = jnp.zeros((b, h, chunk_q, d), q.dtype)
        m0 = jnp.full((b, h, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk_q), jnp.float32)
        (o, m, l), _ = jax.lax.scan(kv_step, (o0, m0, l0),
                                    (jnp.arange(nk), kb, vb))
        return None, _finish(o, l)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)


def blockwise_attention_unrolled(q: jax.Array, k: jax.Array, v: jax.Array, *,
                                 causal: bool = True, window: int = 0,
                                 chunk_q: int = 2048, chunk_k: int = 1024,
                                 q_offset: int = 0) -> jax.Array:
    """Block-skipping variant: q blocks unrolled in Python so each gets a
    *static* kv range — no compute on fully-masked (causal/window) blocks."""
    import math
    b, sq, h, d = q.shape
    _, sk, _, _ = k.shape
    chunk_q = math.gcd(min(chunk_q, sq), sq)
    chunk_k = math.gcd(min(chunk_k, sk), sk)
    nq = sq // chunk_q
    scale = d ** -0.5
    outs = []
    for qi in range(nq):
        q_lo = q_offset + qi * chunk_q
        q_hi = q_lo + chunk_q
        k_lo = 0 if window <= 0 else max(0, q_lo - window + 1)
        k_hi = min(sk, q_hi) if causal else sk
        k_lo = (k_lo // chunk_k) * chunk_k
        k_hi = min(-(-k_hi // chunk_k) * chunk_k, sk)
        qblk = q[:, q_lo - q_offset:q_hi - q_offset]
        nkb = (k_hi - k_lo) // chunk_k
        kb = k[:, k_lo:k_hi].reshape(b, nkb, chunk_k, h, d).transpose(1, 0, 2, 3, 4)
        vb = v[:, k_lo:k_hi].reshape(b, nkb, chunk_k, h, d).transpose(1, 0, 2, 3, 4)
        q_pos = q_lo + jnp.arange(chunk_q)

        def kv_step(carry, kj_blk, q_pos=q_pos, qblk=qblk):
            o, m, l = carry
            kj, kblk, vblk = kj_blk
            k_pos = kj * chunk_k + jnp.arange(chunk_k)
            mask = jnp.ones((chunk_q, chunk_k), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            o2, m2, l2 = _block_attend(qblk, kblk, vblk, mask, scale)
            return _merge(o, m, l, o2, m2, l2), None

        o0 = jnp.zeros((b, h, chunk_q, d), q.dtype)
        m0 = jnp.full((b, h, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk_q), jnp.float32)
        (o, m, l), _ = jax.lax.scan(
            kv_step, (o0, m0, l0),
            (k_lo // chunk_k + jnp.arange(nkb), kb, vb))
        outs.append(_finish(o, l))
    return jnp.concatenate(outs, axis=1).reshape(b, sq, h, d)


def _valid_cache_slots(cache_len: jax.Array, b: int, c: int, *, window: int,
                       ring: bool) -> jax.Array:
    """(B, C) bool mask of readable cache slots.

    ``cache_len`` may be a scalar (all rows share one length — the seed
    engine's drain-then-refill layout) or a (B,) vector of per-slot lengths
    (continuous batching: every sequence in the batch is at its own
    position).
    """
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32),
                          (b,)).reshape(b, 1)
    slot = jnp.arange(c)[None, :]
    if ring:
        return slot < jnp.minimum(cl, c)
    valid = slot < cl
    if window > 0:
        valid &= slot >= cl - window
    return valid


def gather_paged_kv(arena: jax.Array, layer,
                    block_table: jax.Array) -> jax.Array:
    """Block-table-indexed cache read (the paged-KV jump-table dereference).

    arena: a stack of layer arenas (L, P, bs, H, D) of physical blocks,
    read at ``layer``; block_table: (B, M) physical block id per logical
    block, -1 = unmapped, ``-(p + 2)`` = physical block p mapped READ-ONLY
    (a cross-request shared prefix block — the write path keys its guard
    on ``phys >= 0``, so the encoding makes shared blocks unwritable for
    free while this gather decodes them back).  Returns the logical
    per-row cache (B, M*bs, H, D): logical block j of row b is
    arena[layer, decode(block_table[b, j])].  Unmapped entries clamp to
    block 0 and read garbage — callers mask them through the valid-length
    check of ``decode_attention``.
    """
    b, m = block_table.shape
    bs = arena.shape[2]
    phys = jnp.where(block_table >= 0, block_table, -block_table - 2)
    gathered = arena[layer, jnp.clip(phys, 0)]
    return gathered.reshape(b, m * bs, *arena.shape[3:])


def write_paged_kv(arena: jax.Array, layer, block_table: jax.Array,
                   pos: jax.Array, val: jax.Array, live=None) -> jax.Array:
    """Block-table-indexed cache write of one token per row.

    arena: a stack of layer arenas (L, P, bs, H, D); row b's value (B, H,
    D) lands in layer ``layer``'s physical block
    ``block_table[b, pos[b] // bs]`` at offset ``pos[b] % bs``: one scatter
    into the stack, which a loop carrying it updates in place.  Rows whose
    block is unmapped (released slots, table entry -1) are dropped — and so
    is any write aimed at a READ-ONLY shared-prefix mapping (encoded
    ``-(p + 2)``, see :func:`gather_paged_kv`): the ``phys >= 0`` guard is
    the write protection for cross-request shared blocks.  Also dropped are
    rows whose position lies beyond the table entirely (speculative
    overshoot past the reservation) — their physical destination is pushed
    out of range and ``mode='drop'`` elides the scatter, so an idle slot or
    a rejected draft can never corrupt a live request's block.

    ``live`` (B,) bool additionally drops rows frozen in-graph (a fused
    decode horizon holds a finished row's state still while the other rows
    keep stepping); ``None`` = all rows write.
    """
    p, bs = arena.shape[1], arena.shape[2]
    m = block_table.shape[1]
    blk = pos // bs
    phys = jnp.take_along_axis(block_table, jnp.clip(blk, 0, m - 1)[:, None],
                               axis=1)[:, 0]
    writable = (phys >= 0) & (blk < m)
    if live is not None:
        writable &= live
    dest = jnp.where(writable, phys, p)
    return arena.at[layer, dest, pos % bs].set(val.astype(arena.dtype),
                                               mode="drop")


def rollback_paged_kv(arena: jax.Array, orig: jax.Array,
                      block_table: jax.Array, pos_cand: jax.Array,
                      reject: jax.Array) -> jax.Array:
    """Undo rejected speculative writes in a paged arena, byte-exactly.

    A verify step writes KV for every candidate position before knowing
    which drafts the target model accepts; rolling the arena back to the
    pre-verify bytes at the rejected positions makes the post-verify cache
    identical to having decoded only the accepted tokens one at a time.

    arena: (P, bs, H, D) post-verify; orig: same shape, pre-verify;
    pos_cand: (B, S) absolute position of each candidate write;
    reject: (B, S) bool, True where the write must be undone.  Unmapped or
    out-of-table positions were dropped by :func:`write_paged_kv` and are
    dropped here symmetrically.
    """
    p, bs = arena.shape[0], arena.shape[1]
    m = block_table.shape[1]
    blk = pos_cand // bs
    phys = jnp.take_along_axis(block_table, jnp.clip(blk, 0, m - 1), axis=1)
    dest = jnp.where(reject & (phys >= 0) & (blk < m), phys, p)
    vals = orig[jnp.clip(phys, 0), pos_cand % bs]          # (B, S, H, D)
    return arena.at[dest, pos_cand % bs].set(vals, mode="drop")


def decode_attention_gqa(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array, *, window: int = 0,
                         ring: bool = False) -> jax.Array:
    """Grouped-head decode without materializing the KV repeat.

    Used on the head_dim-sharded decode path: every head axis is unsharded
    there, so the grouped einsum is local and the 6x (GQA 48/8) repeat
    buffer + its resharding all-to-alls disappear entirely.
    q: (B, 1, H, D); caches: (B, C, Hk, D) with H % Hk == 0;
    cache_len: () or (B,) valid lengths.
    """
    b, _, h, d = q.shape
    c, hk = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    scale = d ** -0.5
    qg = q.reshape(b, hk, g, d)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg,
                        k_cache).astype(jnp.float32) * scale
    valid = _valid_cache_slots(cache_len, b, c, window=window, ring=ring)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, d)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, window: int = 0,
                     ring: bool = False) -> jax.Array:
    """One-token attention against a cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, C, Hkv, D) — repeated here;
    cache_len: () or (B,) number of valid positions per row.  With
    ``ring=True`` the cache is a circular buffer of size C=window and every
    slot < min(cache_len, C) is valid.
    """
    b, _, h, d = q.shape
    k_cache = repeat_kv(k_cache, h)
    v_cache = repeat_kv(v_cache, h)
    c = k_cache.shape[1]
    scale = d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhk", q, k_cache).astype(jnp.float32) * scale
    valid = _valid_cache_slots(cache_len, b, c, window=window, ring=ring)
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p.astype(v_cache.dtype), v_cache)
    return out[:, None]


def attention(q, k, v, *, causal=True, window=0, chunk_q=512, chunk_k=1024,
              q_offset=0, impl: str = "scan") -> jax.Array:
    k = repeat_kv(k, q.shape[2])
    v = repeat_kv(v, q.shape[2])
    if impl == "unrolled":
        return blockwise_attention_unrolled(
            q, k, v, causal=causal, window=window,
            chunk_q=chunk_q, chunk_k=chunk_k, q_offset=q_offset)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             chunk_q=chunk_q, chunk_k=chunk_k, q_offset=q_offset)


def reference_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """O(S^2)-memory oracle used by tests."""
    b, sq, h, d = q.shape
    k = repeat_kv(k, h)
    v = repeat_kv(v, h)
    sk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * d ** -0.5
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.reshape(b, sq, h, d)
