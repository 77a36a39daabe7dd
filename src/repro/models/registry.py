"""Architecture registry + per-(arch x shape) cell specification.

A *cell* is one (architecture, input-shape) pair from the assignment matrix.
``cell_spec`` returns everything the launcher/dry-run needs: which step
function to build, the abstract (LogicalArray) trees for every argument, and
donation info — all without allocating a single parameter.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.sharding import LogicalArray

ARCH_IDS = [
    "internvl2-26b", "mamba2-130m", "gemma3-12b", "llama3.2-3b",
    "qwen3-0.6b", "gemma3-4b", "seamless-m4t-medium", "qwen3-moe-30b-a3b",
    "olmoe-1b-7b", "recurrentgemma-2b",
]

# shape id -> (seq_len, global_batch, kind)
SHAPES: Dict[str, Tuple[int, int, str]] = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}


def _module_name(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "_")


def get_config(arch_id: str, reduced: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_module_name(arch_id)}")
    return mod.REDUCED if reduced else mod.CONFIG


def cell_skip_reason(cfg: ModelConfig, shape_id: str) -> Optional[str]:
    if shape_id == "long_500k" and not cfg.supports_long_context:
        return ("full-attention family: 500k decode state is not sub-quadratic"
                " (see DESIGN.md §4)")
    return None


def all_cells(include_skipped: bool = False) -> List[Tuple[str, str]]:
    out = []
    for a in ARCH_IDS:
        cfg = get_config(a)
        for s in SHAPES:
            if include_skipped or cell_skip_reason(cfg, s) is None:
                out.append((a, s))
    return out


@dataclass
class CellSpec:
    arch: str
    shape: str
    kind: str                      # train | prefill | decode
    cfg: ModelConfig
    abstract_args: Tuple[Any, ...]  # LogicalArray pytrees, step-fn order
    donate_argnums: Tuple[int, ...]
    seq_len: int
    global_batch: int


def _batch_abstract(cfg: ModelConfig, seq: int, batch: int,
                    with_labels: bool) -> Dict[str, Any]:
    if cfg.is_encdec:
        se = sd = seq // 2
        b = {
            "frames": LogicalArray((batch, se, cfg.d_model), cfg.dtype,
                                   ("batch", "seq", "embed")),
            "tokens": LogicalArray((batch, sd), jnp.int32, ("batch", "seq")),
        }
        if with_labels:
            b["labels"] = LogicalArray((batch, sd), jnp.int32, ("batch", "seq"))
        return b
    p = cfg.frontend_tokens
    b = {"tokens": LogicalArray((batch, seq - p), jnp.int32, ("batch", "seq"))}
    if p:
        b["prefix_embeds"] = LogicalArray((batch, p, cfg.d_model), cfg.dtype,
                                          ("batch", "seq", "embed"))
    if with_labels:
        b["labels"] = LogicalArray((batch, seq), jnp.int32, ("batch", "seq"))
    return b


def _abstract_cache(cfg: ModelConfig, batch: int, seq: int):
    if cfg.is_encdec:
        from repro.models import encdec
        return encdec.abstract_cache(cfg, batch, seq // 2, seq // 2)
    from repro.models import transformer
    return transformer.abstract_cache(cfg, batch, seq)


def cell_spec(arch_id: str, shape_id: str, *, reduced: bool = False,
              remat: Optional[str] = None, attn_impl: Optional[str] = None,
              cache_heads: Optional[int] = None) -> CellSpec:
    cfg = get_config(arch_id, reduced=reduced)
    if remat is not None:
        cfg = cfg.replace(remat_policy=remat)
    if attn_impl is not None:
        cfg = cfg.replace(attn_impl=attn_impl)
    if cache_heads is not None:
        cfg = cfg.replace(decode_cache_heads=cache_heads)
    seq, batch, kind = SHAPES[shape_id]
    if reduced:
        seq, batch = 64, 4

    from repro.models import transformer
    from repro.optim import adamw_abstract_state
    from repro.models import encdec

    mod = encdec if cfg.is_encdec else transformer
    params = mod.abstract_params(cfg)

    if kind == "train":
        state = {"params": params, "opt": adamw_abstract_state(params)}
        args = (state, _batch_abstract(cfg, seq, batch, with_labels=True))
        donate = (0,)
    elif kind == "prefill":
        caches = _abstract_cache(cfg, batch, seq)
        args = (params, caches, _batch_abstract(cfg, seq, batch,
                                                with_labels=False))
        donate = (1,)
    else:  # decode
        caches = _abstract_cache(cfg, batch, seq)
        token = LogicalArray((batch, 1), jnp.int32, ("batch", None))
        if cfg.is_encdec:
            # enc-dec decode still takes an explicit scalar position
            pos = LogicalArray((), jnp.int32, ())
            args = (params, caches, token, pos)
        else:
            # decoder-only: per-slot positions live inside the cache tree
            args = (params, caches, token)
        donate = (1,)
    return CellSpec(arch=arch_id, shape=shape_id, kind=kind, cfg=cfg,
                    abstract_args=args, donate_argnums=donate,
                    seq_len=seq, global_batch=batch)


def build_step_fn(spec: CellSpec, rules, opt_cfg=None, accum: int = 1,
                  grad_constraint: bool = False, grad_of_scan: bool = False):
    from repro.optim import AdamWConfig
    from repro import steps
    if spec.kind == "train":
        return steps.make_train_step(spec.cfg, rules,
                                     opt_cfg or AdamWConfig(), accum=accum,
                                     grad_constraint=grad_constraint,
                                     grad_of_scan=grad_of_scan)
    if spec.kind == "prefill":
        return steps.make_prefill_step(spec.cfg, rules)
    return steps.make_serve_step(spec.cfg, rules)


# ----------------------------------------------------------------------------
# analytic parameter / FLOP counts for the roofline MODEL_FLOPS column
# ----------------------------------------------------------------------------

def param_counts(cfg: ModelConfig) -> Dict[str, float]:
    """Analytic total and active parameter counts (embedding included)."""
    d, v = cfg.d_model, cfg.padded_vocab
    hd = cfg.resolved_head_dim
    pattern = cfg.pattern_for_layers()
    total = v * d + (0 if cfg.tie_embeddings else d * v)
    active = total
    for kind in pattern:
        if kind in ("G", "L"):
            n = d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
            if cfg.qk_norm == "full":       # one weight over each projection
                n += (cfg.n_heads + cfg.n_kv_heads) * hd
            elif cfg.qk_norm:
                n += 2 * hd
            total += n
            active += n
        elif kind == "M":
            d_in = cfg.ssm_expand * d
            h = d_in // cfg.ssm_head_dim
            n = d * (2 * d_in + 2 * cfg.ssm_state + h) + d_in * d
            total += n
            active += n
        elif kind == "R":
            lru = cfg.lru_width or d
            n = d * lru * 2 + lru * d
            total += n
            active += n
        if cfg.d_ff > 0:
            if cfg.family == "moe":
                per = 3 * d * cfg.d_ff
                total += cfg.n_experts * per + d * cfg.n_experts
                active += cfg.experts_per_token * per + d * cfg.n_experts
            else:
                n = 3 * d * cfg.d_ff
                total += n
                active += n
    if cfg.is_encdec:
        # encoder layers (attention + mlp), same widths
        n = cfg.n_enc_layers * (d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
                                + 3 * d * cfg.d_ff)
        # cross attention in every decoder layer
        n += cfg.n_layers * d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
        total += n
        active += n
    return {"total": float(total), "active": float(active)}


def model_flops(cfg: ModelConfig, shape_id: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (fwd-only), N = active params,
    D = processed tokens. Attention score FLOPs excluded by convention."""
    seq, batch, kind = SHAPES[shape_id]
    n_active = param_counts(cfg)["active"]
    if kind == "train":
        tokens = seq * batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * n_active * tokens
    return 2.0 * n_active * batch  # decode: one token per sequence
