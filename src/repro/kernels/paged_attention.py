"""Paged decode attention: one query token per slot against its live blocks.

The KV arenas keep their serving layout ``(L, P, bs, Hkv, D)`` (layer,
physical block, offset, kv head, head dim) and stay in HBM; the kernel
reads one layer's arena by its index, so the stack a layer scan carries is
handed over whole, never sliced, and DMAs only each slot's live blocks,
found through the block table, into VMEM.  Nothing is
gathered into a per-slot logical cache and nothing is repeated to the query
heads: the XLA path copies every slot's whole table out of the arena and
repeats it ``G`` times, so its cost follows ``slots x max_len``; this one
follows the live tokens.

Schedule: grid = (B,), one slot per step, run in order.  Inside a step a
loop walks the slot's waves of ``blocks_per_wave`` blocks; the next wave
(or the next slot's first wave) is DMA'd into the other half of a double
buffer while the current one is attended, so the pipeline runs unbroken
across slots.  Online softmax with f32 max, denominator and accumulator.

Grouped-query attention without a repeat: a wave's keys, flattened to
``(tokens * Hkv, D)``, meet all ``H`` query heads in one matmul, and the
scores of a query head against another kv head's columns are masked out
(query head ``h`` reads kv head ``h // G``).  The masked probabilities are
exactly zero, so the P.V matmul over the same flattened values sums each
head's own tokens only.  Decode is bound by HBM bytes; the ``Hkv``-fold
MXU work this spends is a small fraction of a wave's DMA time.

Block-table encoding (``repro.core.paging``): ``p >= 0`` a physical block,
``-1`` unmapped, ``-(p + 2)`` block ``p`` mapped read-only (a shared
prefix).  A row attends its positions ``< length`` up to its first unmapped
block; a row with no mapped block reads nothing and returns zeros, which
the engine discards.  No DMA is issued for an unmapped entry or for a block
past the live length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Folded into the serving programs' fingerprint context: bump it with any
# change to what the kernel computes or how it is scheduled (2: it also
# runs once per shard of the KV heads where a mesh splits them; 3: it
# reads a stack of layer arenas by layer index).
VERSION = "paged_decode_attention/3"


def _live_lengths(block_table: jax.Array, lengths: jax.Array,
                 block_size: int) -> jax.Array:
    """(B,) positions each row may read: ``lengths`` cut at the row's first
    unmapped (-1) block and at the end of its table."""
    m = block_table.shape[1]
    unmapped = block_table == -1
    first = jnp.where(jnp.any(unmapped, axis=1),
                      jnp.argmax(unmapped, axis=1), m)
    return jnp.minimum(lengths.astype(jnp.int32), first * block_size)


def _kernel(lens_ref, table_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
            n_rows: int, table_w: int, block_size: int, nb: int,
            group: int, scale: float):
    b = pl.program_id(0)
    hkv = k_buf.shape[3]
    wave = nb * block_size
    layer = layer_ref[0]

    def live_blocks(row):
        return (lens_ref[row] + block_size - 1) // block_size

    def n_blocks(row, w):
        return jnp.clip(live_blocks(row) - w * nb, 0, nb)

    def copies(row, w, slot, j):
        e = table_ref[row * table_w + w * nb + j]
        phys = jnp.where(e >= 0, e, -e - 2)
        return (pltpu.make_async_copy(k_hbm.at[layer, phys],
                                      k_buf.at[slot, j], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, phys],
                                      v_buf.at[slot, j], sems.at[1, slot]))

    def each_block(row, w, slot, op):
        n = n_blocks(row, w)
        for j in range(nb):
            @pl.when(j < n)
            def _():
                for c in copies(row, w, slot, j):
                    op(c)

    def start(row, w, slot):
        each_block(row, w, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _prime():
        # a wave's unread blocks still meet the P.V matmul (with zero
        # weight), so the buffers must never hold uninitialised bits
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...].astype(jnp.float32) * scale                  # (H, D)
    d = q.shape[1]
    # an empty row still takes one (DMA-less) wave, so the prefetch of the
    # next row's first wave is issued from every grid step
    waves = jnp.maximum((live_blocks(b) + nb - 1) // nb, 1)

    def body(w, carry):
        slot = slot_ref[0]
        nxt = 1 - slot
        last = w + 1 == waves

        @pl.when(jnp.logical_not(last))
        def _():
            start(b, w + 1, nxt)

        @pl.when(last & (b + 1 < n_rows))
        def _():
            start(b + 1, 0, nxt)

        each_block(b, w, slot, lambda c: c.wait())

        @pl.when(n_blocks(b, w) > 0)
        def _attend():
            k = k_buf[slot].astype(jnp.float32).reshape(wave * hkv, d)
            v = v_buf[slot].astype(jnp.float32).reshape(wave * hkv, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ok = ((col % hkv == row // group)
                  & (w * wave + col // hkv < lens_ref[b]))
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[...]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            m_ref[...] = m_next
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, waves, body, 0)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks_per_wave", "interpret"))
def paged_decode_attention(q, k_arena, v_arena, block_table, lengths, layer,
                           *, blocks_per_wave: int = 8, interpret=False):
    """q: (B, H, D); k/v_arena: (L, P, bs, Hkv, D) with H % Hkv == 0, read
    at ``layer`` (an int32 scalar); block_table: (B, M) int32; lengths: (B,)
    positions to attend (``pos + 1`` in decode).  Returns (B, H, D) in q's
    dtype."""
    bsz, h, d = q.shape
    _, _, bs, hkv, _ = k_arena.shape
    m = block_table.shape[1]
    assert h % hkv == 0, (h, hkv)
    nb = min(blocks_per_wave, m)
    lens = _live_lengths(block_table, lengths, bs)
    kernel = functools.partial(
        _kernel, n_rows=bsz, table_w=m, block_size=bs, nb=nb,
        group=h // hkv, scale=d ** -0.5)
    row_block = pl.BlockSpec((None, h, d), lambda b, *_: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz,),
            in_specs=[row_block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_block,
            scratch_shapes=[
                pltpu.VMEM((2, nb, bs, hkv, d), k_arena.dtype),
                pltpu.VMEM((2, nb, bs, hkv, d), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(lens, block_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_arena, v_arena)
