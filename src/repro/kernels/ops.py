"""jit'd wrappers with implementation switching for every kernel.

impl:
  "pallas"    — compiled Pallas TPU kernel (the production path)
  "interpret" — Pallas kernel body interpreted on CPU (this container's
                validation path: same code, Python semantics)
  "xla"       — the pure-jnp reference (ref.py), also the dry-run path

``default_impl()`` picks by backend so model code can stay agnostic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import moe_dispatch as _moe
from repro.kernels import paged_attention as _pa
from repro.kernels import ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: Optional[str]) -> str:
    return impl if impl is not None else default_impl()


@functools.partial(jax.jit, static_argnames=("impl", "block_m", "block_n",
                                             "block_k"))
def matmul(x, w, *, impl: Optional[str] = None, block_m: int = 128,
           block_n: int = 128, block_k: int = 128):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.matmul(x, w)
    return _mm.matmul(x, w, block_m=block_m, block_n=block_n,
                      block_k=block_k, interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "causal", "window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: Optional[str] = None, block_q: int = 128,
                    block_k: int = 128):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.flash_attention(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def ssd_scan(x, dt, a, b, c, *, impl: Optional[str] = None, chunk: int = 128):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.ssd_scan(x, dt, a, b, c)
    return _ssd.ssd_scan(x, dt, a, b, c, chunk=chunk,
                         interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "block_l"))
def rglru_scan(a, b, *, impl: Optional[str] = None, chunk: int = 256,
               block_l: int = 512):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.rglru_scan(a, b)
    return _rg.rglru_scan(a, b, chunk=chunk, block_l=block_l,
                          interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_c"))
def moe_ffn(buf, w1, w3, w2, *, impl: Optional[str] = None,
            block_c: int = 128):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.moe_ffn(buf, w1, w3, w2)
    return _moe.moe_ffn(buf, w1, w3, w2, block_c=block_c,
                        interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "blocks_per_wave"))
def paged_decode_attention(q, k_arena, v_arena, block_table, lengths, layer,
                           *, impl: Optional[str] = None,
                           blocks_per_wave: int = 8):
    """q (B, H, D) against each row's live blocks of layer ``layer`` of
    (L, P, bs, Hkv, D) arenas through ``block_table`` (B, M); ``lengths``
    (B,)."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.paged_decode_attention(q, k_arena, v_arena, block_table,
                                          lengths, layer)
    return _pa.paged_decode_attention(q, k_arena, v_arena, block_table,
                                      lengths, layer,
                                      blocks_per_wave=blocks_per_wave,
                                      interpret=(impl == "interpret"))
