"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each function is the mathematical definition with no tiling/blocking —
tests sweep shapes/dtypes and assert the kernels (interpret=True on this
CPU container; compiled on real TPU) match these to tolerance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn


def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(x.dtype)


def flash_attention(q, k, v, *, causal=True, window=0):
    """q: (BH, Sq, D); k, v: (BHk, Sk, D) with BH % BHk == 0 (GQA)."""
    bh, sq, d = q.shape
    bhk, sk, _ = k.shape
    g = bh // bhk
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    scores = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * d ** -0.5
    q_pos = jnp.arange(sq) + (sk - sq)      # right-aligned (decode-friendly)
    k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def ssd_scan(x, dt, a, b, c, *, h0=None):
    """Sequential (unchunked) SSD recurrence — the ground truth.

    x: (B,S,H,P), dt: (B,S,H) (post-softplus), a: (H,) negative,
    b, c: (B,S,N).  Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(hprev, t):
        xt, dtt, bt, ct = t
        decay = jnp.exp(dtt * a)[..., None, None]           # (B,H,1,1)
        upd = jnp.einsum("bn,bhp->bhpn", bt,
                         (xt * dtt[..., None]).astype(jnp.float32))
        hnew = hprev * decay + upd
        y = jnp.einsum("bn,bhpn->bhp", ct.astype(jnp.float32), hnew)
        return hnew, y.astype(x.dtype)

    xs = (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
          b.transpose(1, 0, 2), c.transpose(1, 0, 2))
    hf, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), hf


def rglru_scan(a, b, *, h0=None):
    """Sequential linear recurrence h_t = a_t h_{t-1} + b_t.

    a, b: (B, S, L) f32; h0: (B, L) or None. Returns (h (B,S,L), h_final)."""
    bsz, s, l = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, l), jnp.float32)

    def step(h, t):
        at, bt = t
        h = at * h + bt
        return h, h

    hf, hs = jax.lax.scan(step, h0, (a.transpose(1, 0, 2),
                                     b.transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2), hf


def moe_ffn(buf, w1, w3, w2):
    """Grouped expert FFN: buf (E,C,d), w1/w3 (E,d,f), w2 (E,f,d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1)) * jnp.einsum(
        "ecd,edf->ecf", buf, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def paged_decode_attention(q, k_arena, v_arena, block_table, lengths, layer):
    """The XLA paged decode read: gather every row's whole block table out
    of the arena ``layer``, repeat it to the query heads, attend the first
    ``lengths`` positions.  q: (B, H, D); arenas: (L, P, bs, Hkv, D)."""
    k = attn.gather_paged_kv(k_arena, layer, block_table)
    v = attn.gather_paged_kv(v_arena, layer, block_table)
    return attn.decode_attention(q[:, None], k, v, lengths)[:, 0]
