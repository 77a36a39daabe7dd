"""Top-level step functions: train_step / prefill_step / serve_step.

These are the programs the persistent executor (repro.core.syscore) hot-loads:
pure functions of (params/opt_state/caches, batch) with donated buffers, one
per (arch x shape) cell.  ``make_*`` returns a closure suitable for
``jax.jit`` with explicit in/out shardings supplied by the launcher, and
``*_program_spec*`` wraps the closures into typed
:class:`~repro.core.program_store.ProgramSpec`s — the hot-loadable unit of
the Executor API v2 (closure-captured config is folded into the spec's
fingerprint ``context`` so a persistent ProgramStore never confuses two
architectures that happen to share shapes).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.layers import softmax_xent
from repro.optim import AdamWConfig, adamw_update
from repro.sharding import constrain


def model_module(cfg):
    return encdec if cfg.is_encdec else transformer


def _lm_loss(cfg, logits, labels, aux, rules):
    """labels < 0 are masked (e.g. frontend prefix positions)."""
    losses = softmax_xent(logits, jnp.maximum(labels, 0), cfg.vocab_size)
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux / max(cfg.n_layers, 1)
    return loss


def make_train_step(cfg, rules, opt_cfg: AdamWConfig, accum: int = 1,
                    grad_constraint: bool = False,
                    grad_of_scan: bool = False):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params": ..., "opt": ...};
    batch (decoder-only) = {"tokens": (B,S_tok), "labels": (B,S)[, "prefix_embeds"]}
    batch (enc-dec)      = {"frames": (B,Se,d), "tokens": (B,Sd), "labels": (B,Sd)}

    ``accum`` > 1 runs gradient accumulation over microbatches via lax.scan:
    activation temps scale with the microbatch while the gradient buffer is
    carried (fp32, param-sharded).  This is how the big train cells stay under
    per-chip HBM (EXPERIMENTS.md §Dry-run).

    ``grad_constraint`` pins every microbatch gradient to its parameter's
    sharding, turning GSPMD's full-size gradient all-reduce into a
    reduce-scatter (ZeRO-style; ~2x less gradient wire — §Perf HC2).

    ``grad_of_scan`` differentiates THROUGH the microbatch scan instead of
    scanning value_and_grad: the parameter cotangent accumulates inside the
    loop and the cross-device gradient reduction happens ONCE per step
    instead of once per microbatch (accum x less gradient wire).  Gradients
    still accumulate in f32: parameters are upcast at the step boundary so
    the cotangent dtype is f32, and compute casts back to the model dtype.
    """
    from repro.sharding import LogicalArray, constrain as _constrain
    mod = encdec if cfg.is_encdec else transformer
    abs_params = mod.abstract_params(cfg) if grad_constraint else None

    def constrain_grads(g):
        if abs_params is None:
            return g
        return jax.tree.map(
            lambda la, gi: _constrain(gi, la.logical, rules),
            abs_params, g,
            is_leaf=lambda x: isinstance(x, LogicalArray))
    def loss_fn(params, batch):
        if cfg.is_encdec:
            logits, _, aux = encdec.forward(
                cfg, params, batch["frames"], batch["tokens"], rules=rules,
                mode="train")
        else:
            logits, _, aux = transformer.forward(
                cfg, params, batch["tokens"], rules=rules,
                prefix_embeds=batch.get("prefix_embeds"), mode="train")
        return _lm_loss(cfg, logits, batch["labels"], aux, rules)

    def grads_of(params, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        return loss, constrain_grads(g)

    def split(x):
        b = x.shape[0]
        assert b % accum == 0, (b, accum)
        return x.reshape(accum, b // accum, *x.shape[1:])

    def _upcast(p):
        return p.astype(jnp.float32) if jnp.issubdtype(
            p.dtype, jnp.floating) else p

    def _downcast_like(p32, p):
        return p32.astype(p.dtype)

    def grads_grad_of_scan(params, batch):
        micro = jax.tree.map(split, batch)
        params32 = jax.tree.map(_upcast, params)

        def total_loss(params32):
            def body(acc, mb):
                p = jax.tree.map(_downcast_like, params32, params)
                return acc + loss_fn(p, mb), None

            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
            total, _ = jax.lax.scan(body, 0.0, micro)
            return total / accum

        loss, g32 = jax.value_and_grad(total_loss)(params32)
        return loss, constrain_grads(g32)

    def train_step(state, batch):
        params = state["params"]
        if accum <= 1:
            loss, grads = grads_of(params, batch)
        elif grad_of_scan:
            loss, grads = grads_grad_of_scan(params, batch)
        else:
            micro = jax.tree.map(split, batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)

            def acc_step(carry, mb):
                g, l = carry
                li, gi = grads_of(params, mb)
                g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 g, gi)
                return (g, l + li), None

            (gsum, lsum), _ = jax.lax.scan(acc_step, (g0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
        new_params, new_opt, metrics = adamw_update(
            opt_cfg, grads, state["opt"], state["params"])
        metrics = dict(metrics, loss=loss)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_prefill_step(cfg, rules):
    """prefill_step(params, caches, batch) -> (caches, last_logits).

    Decoder-only batches may carry ``lengths`` (B,) for right-padded rows:
    the returned cache's per-slot ``pos`` is set per row and
    ``last_logits`` is gathered at each row's final *valid* position.
    """
    def prefill_step(params, caches, batch):
        if cfg.is_encdec:
            logits, new_caches, _ = encdec.forward(
                cfg, params, batch["frames"], batch["tokens"], rules=rules,
                mode="prefill", caches=caches)
            return new_caches, logits[:, -1]
        lengths = batch.get("lengths")
        logits, new_caches, _ = transformer.forward(
            cfg, params, batch["tokens"], rules=rules,
            prefix_embeds=batch.get("prefix_embeds"), mode="prefill",
            caches=caches, lengths=lengths)
        if lengths is None:
            last = logits[:, -1]
        else:
            idx = (jnp.asarray(lengths, jnp.int32) - 1)[:, None, None]
            last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
        return new_caches, last

    return prefill_step


def make_prefill_slot_step(cfg, rules, cache_len: int, ring: bool = True):
    """prefill_slot(params, caches, tokens, slot, length) -> (caches, last).

    Admission path of the continuous-batching engine: prefill ONE request
    (tokens (1, S) right-padded, ``length`` () valid prompt length) through
    a fresh batch-1 cache and scatter the resulting rows into slot ``slot``
    of the live batched cache tree — including its ``pos`` entry.  Nothing
    outside row ``slot`` is touched, so the other slots keep decoding
    between executions of this program; hot-loading it once means admission
    never recompiles.  ``last`` is the (V,) logits at the final valid
    prompt position (the first generated token's distribution).

    ``ring=False`` matches the full-length windowed-layer buffers of the
    speculative engine (rollback needs absolute slot addressing).
    """
    assert not cfg.is_encdec, "decoder-only serving path"

    def prefill_slot(params, caches, tokens, slot, length):
        fresh = transformer.init_cache(cfg, 1, cache_len, ring=ring)
        logits, c1, _ = transformer.forward(
            cfg, params, tokens, rules=rules, mode="prefill", caches=fresh,
            lengths=jnp.reshape(length, (1,)))
        # group-stacked leaves carry a leading (layers,) axis -> batch is
        # axis 1; tail leaves and ``pos`` index batch at axis 0
        new_caches = {
            "pos": caches["pos"].at[slot].set(c1["pos"][0]),
            "groups": jax.tree.map(
                lambda cb, c1l: cb.at[:, slot].set(
                    c1l[:, 0].astype(cb.dtype)),
                caches["groups"], c1["groups"]),
            "tail": jax.tree.map(
                lambda cb, c1l: cb.at[slot].set(c1l[0].astype(cb.dtype)),
                caches["tail"], c1["tail"]),
        }
        last = jnp.take(logits[0], length - 1, axis=0)
        return new_caches, last

    return prefill_slot


def make_paged_prefill_slot_step(cfg, rules, cache_len: int, kv_block: int):
    """Paged-arena admission program (repro.core.paging).

    Same contract as :func:`make_prefill_slot_step`, but the live cache
    tree carries a physical-block KV arena + per-slot block table instead
    of dense per-slot buffers: the fresh batch-1 prefill cache is computed
    exactly as in the dense path (so admission stays token-exact), then its
    attention rows are scattered — block by block — into the arena blocks
    the host-side pager mapped for this slot, while recurrent state rows
    scatter into the slot as before.  Unmapped table entries (-1, beyond
    the request's reservation) are dropped, and so are read-only
    shared-prefix mappings (encoded ``-(p + 2)``): a full prefill over a
    prompt whose head blocks are shared recomputes those positions but
    never writes through the shared copy — bit-identical bytes land on the
    floor, which is what makes tier-2 prefix admission exact for every
    family including recurrent-state ones.
    """
    assert not cfg.is_encdec, "decoder-only serving path"
    n_blocks = cache_len // kv_block

    def _is_kv(path):
        return getattr(path[-1], "key", None) in ("k", "v")

    def prefill_slot(params, caches, tokens, slot, length):
        # ring=False: windowed layers prefill a full-length buffer so
        # logical block j holds positions [j*bs, (j+1)*bs) for every kind
        fresh = transformer.init_cache(cfg, 1, cache_len, ring=False)
        logits, c1, _ = transformer.forward(
            cfg, params, tokens, rules=rules, mode="prefill", caches=fresh,
            lengths=jnp.reshape(length, (1,)))
        row = caches["block_table"][slot]                     # (n_blocks,)

        def scatter_group(path, cb, c1l):
            if _is_kv(path):
                dest = jnp.where(row >= 0, row, cb.shape[1])
                blocks = c1l[:, 0].reshape(
                    c1l.shape[0], n_blocks, kv_block, *c1l.shape[3:])
                return cb.at[:, dest].set(blocks.astype(cb.dtype),
                                          mode="drop")
            return cb.at[:, slot].set(c1l[:, 0].astype(cb.dtype))

        def scatter_tail(path, cb, c1l):
            if _is_kv(path):
                dest = jnp.where(row >= 0, row, cb.shape[0])
                blocks = c1l[0].reshape(n_blocks, kv_block, *c1l.shape[2:])
                return cb.at[dest].set(blocks.astype(cb.dtype), mode="drop")
            return cb.at[slot].set(c1l[0].astype(cb.dtype))

        new_caches = {
            "pos": caches["pos"].at[slot].set(c1["pos"][0]),
            "block_table": caches["block_table"],
            "groups": jax.tree_util.tree_map_with_path(
                scatter_group, caches["groups"], c1["groups"]),
            "tail": jax.tree_util.tree_map_with_path(
                scatter_tail, caches["tail"], c1["tail"]),
        }
        last = jnp.take(logits[0], length - 1, axis=0)
        return new_caches, last

    return prefill_slot


def make_paged_prefill_offset_step(cfg, rules, max_suffix: int):
    """Warm-prefix admission program (cross-request prefix sharing).

    Contract of :func:`make_paged_prefill_slot_step` —
    ``(params, caches, tokens, slot, offset, length) -> (caches, last)`` —
    except the slot's leading ``offset`` prompt tokens are already resident
    in shared arena blocks mapped read-only into its block-table row, so
    NO compute runs for them: only the suffix ``tokens[0, :length-offset]``
    is processed, as a ``lax.scan`` of the same per-token ``decode_step``
    the decode path dispatches, live-masked to this slot so no other row
    moves.  Suffix positions start at the divergence ``offset`` (the pager
    guarantees it is block-aligned and strictly below ``length``, so at
    least one token — the one producing the first-token logits — always
    runs, and every suffix write lands in the slot's private blocks; the
    ``-(p+2)`` write guard drops anything aimed at a shared block).
    Reusing ``decode_step`` rather than a batched suffix prefill is what
    keeps warm streams byte-exact: wherever the engine's sequential decode
    is bit-exact (the property the verify and horizon paths already gate
    on), this scan produces the identical KV bytes and logits.
    ``last`` is the (V,) logits at the final prompt position.
    """
    assert not cfg.is_encdec, "decoder-only serving path"
    assert max_suffix >= 1

    def prefill_offset(params, caches, tokens, slot, offset, length):
        b = caches["pos"].shape[0]
        lane = jnp.arange(b) == slot
        n_suffix = length - offset
        caches = dict(caches)
        caches["pos"] = jnp.where(lane, offset, caches["pos"])

        def body(c, xt):
            t, tok = xt
            live = lane & (t < n_suffix)
            tok_b = jnp.where(lane, tok, 0).astype(jnp.int32)[:, None]
            logits, c2 = transformer.decode_step(cfg, params, c, tok_b,
                                                 rules=rules, live=live)
            return c2, jnp.take(logits[:, 0], slot, axis=0)

        xs = (jnp.arange(max_suffix), tokens[0])
        new_caches, ys = jax.lax.scan(body, caches, xs)
        last = jnp.take(ys, jnp.clip(n_suffix - 1, 0, max_suffix - 1),
                        axis=0)
        return new_caches, last

    return prefill_offset


def make_serve_step(cfg, rules):
    """serve_step(params, caches, token) -> (caches, next_token, logits).

    One decode step: greedy next token against the KV cache / recurrent
    state.  Decoder-only models read each row's absolute position from the
    per-slot ``pos`` vector inside the cache tree (and return it advanced),
    so the host feeds only tokens.  Enc-dec keeps the explicit scalar
    ``pos`` argument: serve_step(params, caches, token, pos).
    """
    def serve_step_encdec(params, caches, token, pos):
        logits, new_caches = encdec.decode_step(
            cfg, params, caches, token, pos, rules=rules)
        return new_caches, _greedy(cfg, logits), logits

    def serve_step(params, caches, token):
        logits, new_caches = transformer.decode_step(
            cfg, params, caches, token, rules=rules)
        return new_caches, _greedy(cfg, logits), logits

    return serve_step_encdec if cfg.is_encdec else serve_step


def make_verify_step(cfg, rules):
    """verify_step(params, caches, tokens (B, k+1)) ->
    (caches, out_tokens (B, k+1), n_new (B,)).

    The speculative-decoding hot path: ONE program execution scores the
    last accepted token plus k drafts, accepts the longest greedy-matching
    prefix, and returns the cache rolled back to exactly the accepted
    state (:func:`repro.models.transformer.verify_decode`).  Pure array
    ops only, so it serializes into a ProgramStore and warm-boots by
    deserialization like the other serving programs.
    """
    assert not cfg.is_encdec, "decoder-only serving path"

    def verify_step(params, caches, tokens):
        return transformer.verify_decode(cfg, params, caches, tokens,
                                         rules=rules)

    return verify_step


def make_decode_horizon_step(cfg, rules, horizon: int, eos_id=None):
    """decode_horizon(params, caches, tokens (B, 1), budget (B,)) ->
    (caches, events).

    The fused generation loop: ``horizon`` greedy decode iterations in ONE
    program execution via ``lax.scan`` with in-graph feedback
    (:func:`repro.models.transformer.decode_horizon`).  Per-slot
    termination (EOS / exhausted budget) is masked in-graph, and the
    emitted tokens / per-slot finish steps / occupancy come back as a
    device-side event buffer — one host round trip per horizon instead of
    one dispatch plus several hostcalls per token.  Pure array ops, so the
    program serializes into a ProgramStore like the other serving
    programs; ``horizon`` and ``eos_id`` are closure-captured statics and
    MUST be folded into the spec's fingerprint context.
    """
    assert not cfg.is_encdec, "decoder-only serving path"
    assert horizon >= 2, horizon

    def decode_horizon_step(params, caches, tokens, budget):
        return transformer.decode_horizon(cfg, params, caches, tokens,
                                          budget, rules=rules,
                                          horizon=horizon, eos_id=eos_id)

    return decode_horizon_step


def _spec_context(cfg, rules, *extra) -> str:
    """Fingerprint context for closure-captured configuration: the frozen
    config dataclass repr, the sharding rules and any extra scalars."""
    return "|".join([repr(cfg), repr(sorted(rules.items()))]
                    + [repr(e) for e in extra])


def serve_program_specs(cfg, rules, config=None, *,
                        batch: Optional[int] = None,
                        max_len: Optional[int] = None,
                        prefill_len: Optional[int] = None,
                        spec_k: Optional[int] = None,
                        horizon: Optional[int] = None, eos_id=None,
                        paged: bool = False, kv_block: int = 8,
                        arena_blocks: Optional[int] = None):
    """The serving engine's programs as typed ProgramSpecs — ONE builder
    for every cache layout, keyed on an :class:`EngineConfig`.

    ``prefill`` (dense layout only) admits a cold-start burst over the
    whole batch, ``prefill_slot`` admits ONE request into a live batch,
    ``decode`` advances every slot one greedy token.  With ``config.spec``
    a fourth ``verify`` program scores ``spec.k`` draft tokens per slot in
    one execution (speculative decoding) — and the dense cache layout
    switches to full-length (``ring=False``) windowed buffers, because
    verify rollback needs rejected writes to land at absolute slots beyond
    the truncated ``pos``, never inside a live ring window.  With
    ``config.horizon`` a ``decode_horizon`` program fuses ``horizon.length``
    greedy steps into one dispatch (in-graph feedback + per-slot
    termination masking); its closure-captured ``(horizon, eos_id)``
    statics are folded into its fingerprint context so a ProgramStore
    never confuses two horizon lengths.  With ``config.paging`` the cache
    tree becomes the block-table-addressed physical-block arena of
    ``repro.core.paging`` and ``prefill_slot`` scatters block-wise.

    All programs donate the cache tree (argnum 1) and carry the sharding
    rules in their fingerprint context; their abstract argument AND output
    trees are LogicalArrays, so a mesh-holding Syscore resolves in- and
    out-shardings from one place — in particular the donated cache's
    output sharding is pinned to its input sharding (re-execution never
    reshards), and host-read outputs (tokens, event buffers) come back
    replicated.

    Legacy keyword form ``serve_program_specs(cfg, rules, batch=...,
    max_len=..., ...)`` builds the config internally; new callers pass
    ``config=EngineConfig(...)`` (program-irrelevant fields — clock, queue
    bound, seed, store location — are ignored by construction:
    :meth:`EngineConfig.program_context`).
    """
    from repro.core.program_store import ProgramSpec
    from repro.engine_config import (EngineConfig, HorizonConfig,
                                     PagingConfig, SpecConfig)
    from repro.sharding import LogicalArray
    if config is None:
        assert batch is not None and max_len is not None, \
            "legacy form needs batch= and max_len="
        config = EngineConfig(
            batch=batch, max_len=max_len, prefill_len=prefill_len,
            eos_id=eos_id,
            paging=(PagingConfig(kv_block=kv_block,
                                 arena_blocks=arena_blocks)
                    if paged else None),
            spec=SpecConfig(k=spec_k) if spec_k is not None else None,
            horizon=(HorizonConfig(length=horizon)
                     if horizon is not None and horizon >= 2 else None))
    elif (batch is not None or max_len is not None
          or prefill_len is not None or spec_k is not None
          or horizon is not None or eos_id is not None or paged
          or arena_blocks is not None):
        raise TypeError(
            "serve_program_specs: pass either config=EngineConfig(...) or "
            "the legacy keyword arguments, not both")

    assert not cfg.is_encdec, "decoder-only serving path"
    batch = config.batch
    max_len = config.max_len
    prefill_len = config.resolved_prefill_len
    spec_k = config.spec_k
    paged = config.paged
    ring = spec_k is None                    # dense layout only
    p_abstract = transformer.abstract_params(cfg)
    if paged:
        arena_blocks = config.paging.resolved_arena_blocks(batch, max_len)
        c_abstract = transformer.abstract_paged_cache(
            cfg, batch, max_len, kv_block=config.paging.kv_block,
            arena_blocks=arena_blocks)
    else:
        c_abstract = transformer.abstract_cache(cfg, batch, max_len,
                                                ring=ring)
    V = cfg.padded_vocab
    tok_slot = LogicalArray((1, prefill_len), jnp.int32, ("batch", "seq"))
    tok_decode = LogicalArray((batch, 1), jnp.int32, ("batch", None))
    scalar = LogicalArray((), jnp.int32, ())
    out_tok = LogicalArray((batch, 1), jnp.int32, ("batch", None))
    out_logits = LogicalArray((batch, 1, V), jnp.float32,
                              ("batch", None, "vocab"))
    context = _spec_context(cfg, rules, config.program_context())
    # the programs that decode from a paged arena also carry which paged
    # read (Pallas kernel or XLA gather) the backend selects: it lives in
    # model code, which the step's source does not show
    decode_context = context
    if paged:
        decode_context += "|" + transformer.paged_attention_context()

    specs = {
        "prefill_slot": ProgramSpec(
            key="prefill_slot",
            fn=(make_paged_prefill_slot_step(cfg, rules, max_len,
                                             config.paging.kv_block)
                if paged else
                make_prefill_slot_step(cfg, rules, max_len, ring=ring)),
            abstract_args=(p_abstract, c_abstract, tok_slot, scalar, scalar),
            donate_argnums=(1,), context=context,
            out_logical=(c_abstract,
                         LogicalArray((V,), jnp.float32, ("vocab",)))),
        "decode": ProgramSpec(
            key="decode", fn=make_serve_step(cfg, rules),
            abstract_args=(p_abstract, c_abstract, tok_decode),
            donate_argnums=(1,), context=decode_context,
            out_logical=(c_abstract, out_tok, out_logits)),
    }
    if not paged:
        tok_batch = LogicalArray((batch, prefill_len), jnp.int32,
                                 ("batch", "seq"))
        lens_batch = LogicalArray((batch,), jnp.int32, ("batch",))
        prefill = make_prefill_step(cfg, rules)

        def prefill_batch(params, caches, tokens, lengths):
            return prefill(params, caches,
                           {"tokens": tokens, "lengths": lengths})

        specs["prefill"] = ProgramSpec(
            key="prefill", fn=prefill_batch,
            abstract_args=(p_abstract, c_abstract, tok_batch, lens_batch),
            donate_argnums=(1,), context=context,
            out_logical=(c_abstract,
                         LogicalArray((batch, V), jnp.float32,
                                      ("batch", "vocab"))))
    if paged and config.prefix is not None:
        ms = config.resolved_prefix_suffix
        tok_suffix = LogicalArray((1, ms), jnp.int32, ("batch", "seq"))
        specs["prefill_offset"] = ProgramSpec(
            key="prefill_offset",
            fn=make_paged_prefill_offset_step(cfg, rules, ms),
            abstract_args=(p_abstract, c_abstract, tok_suffix, scalar,
                           scalar, scalar),
            donate_argnums=(1,),
            context=context + "|" + config.prefix_context(),
            out_logical=(c_abstract,
                         LogicalArray((V,), jnp.float32, ("vocab",))))
    if spec_k is not None:
        tok_verify = LogicalArray((batch, spec_k + 1), jnp.int32,
                                  ("batch", None))
        specs["verify"] = ProgramSpec(
            key="verify", fn=make_verify_step(cfg, rules),
            abstract_args=(p_abstract, c_abstract, tok_verify),
            donate_argnums=(1,), context=decode_context,
            out_logical=(c_abstract,
                         LogicalArray((batch, spec_k + 1), jnp.int32,
                                      ("batch", None)),
                         LogicalArray((batch,), jnp.int32, ("batch",))))
    H = config.horizon_length
    if H is not None:
        budget = LogicalArray((batch,), jnp.int32, ("batch",))
        specs["decode_horizon"] = ProgramSpec(
            key="decode_horizon",
            fn=make_decode_horizon_step(cfg, rules, H, config.eos_id),
            abstract_args=(p_abstract, c_abstract, tok_decode, budget),
            donate_argnums=(1,),
            context=decode_context + "|" + config.horizon_context(),
            out_logical=(c_abstract, {
                "tokens": LogicalArray((batch, H), jnp.int32,
                                       ("batch", None)),
                "n_emitted": LogicalArray((batch,), jnp.int32, ("batch",)),
                "occupancy": LogicalArray((H,), jnp.float32, (None,))}))
    return specs


def paged_serve_program_specs(cfg, rules, *, batch: int, max_len: int,
                              prefill_len: int, kv_block: int,
                              arena_blocks: int,
                              spec_k: Optional[int] = None,
                              horizon: Optional[int] = None, eos_id=None):
    """Deprecated shim over :func:`serve_program_specs` (one release): the
    paged layout is now selected by ``EngineConfig.paging``, not a forked
    builder."""
    import warnings
    warnings.warn(
        "paged_serve_program_specs is deprecated; call "
        "serve_program_specs(cfg, rules, config=EngineConfig(..., "
        "paging=PagingConfig(...)))", DeprecationWarning, stacklevel=2)
    from repro.engine_config import (EngineConfig, HorizonConfig,
                                     PagingConfig, SpecConfig)
    return serve_program_specs(cfg, rules, EngineConfig(
        batch=batch, max_len=max_len, prefill_len=prefill_len,
        eos_id=eos_id,
        paging=PagingConfig(kv_block=kv_block, arena_blocks=arena_blocks),
        spec=SpecConfig(k=spec_k) if spec_k is not None else None,
        horizon=(HorizonConfig(length=horizon)
                 if horizon is not None and horizon >= 2 else None)))


def train_program_spec(cfg, rules, opt_cfg: AdamWConfig, abstract_state,
                       abstract_batch, *, accum: int = 1, fn=None):
    """The train program as a typed ProgramSpec.  ``fn`` overrides the bare
    train step (e.g. a telemetry-wrapping closure); it still fingerprints
    under the full (cfg, opt_cfg, accum) context."""
    from repro.core.program_store import ProgramSpec
    if fn is None:
        fn = make_train_step(cfg, rules, opt_cfg, accum=accum)
    return ProgramSpec(
        key="train", fn=fn,
        abstract_args=(abstract_state, abstract_batch),
        donate_argnums=(0,),
        context=_spec_context(cfg, rules, opt_cfg, accum))


def _greedy(cfg, logits):
    # the one shared greedy argmax — transformer.greedy_token — so serve /
    # verify / horizon can never drift apart on vocab-padding or ties
    return transformer.greedy_token(cfg, logits)


def init_train_state(cfg, key, opt_cfg: Optional[AdamWConfig] = None):
    from repro.optim import adamw_init
    mod = model_module(cfg)
    params = mod.init_params(cfg, key)
    return {"params": params, "opt": adamw_init(params)}
