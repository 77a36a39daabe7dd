"""Model configuration for all assigned architecture families.

A single frozen dataclass describes every family (dense / moe / ssm / hybrid /
encdec / vlm backbone).  Family-specific fields default to "off".  Configs for
the ten assigned architectures live in ``repro.configs.<id>`` and are built
from this class with the exact published numbers.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple, Union

VOCAB_PAD_MULTIPLE = 2048  # pad vocab so the vocab axis shards cleanly (16-way TP, 128-lane)


def pad_vocab(v: int, multiple: int = VOCAB_PAD_MULTIPLE) -> int:
    return ((v + multiple - 1) // multiple) * multiple


@dataclass(frozen=True)
class ModelConfig:
    # identity -----------------------------------------------------------
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    # transformer core ----------------------------------------------------
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # None -> d_model // n_heads
    # attention details ---------------------------------------------------
    # RMSNorm on q/k before RoPE: False off, True per head (qwen3, gemma3),
    # "full" over the whole projection width before the split into heads
    # (OLMoE: one (n_heads*hd,) and one (n_kv_heads*hd,) weight)
    qk_norm: Union[bool, str] = False
    rope_theta: float = 10_000.0
    rope_theta_local: Optional[float] = None  # gemma3: different theta for local layers
    scale_embeddings: bool = False            # gemma/seamless: embed *= sqrt(d_model)
    local_window: int = 0                    # sliding-window size for "L" layers
    layer_pattern: Tuple[str, ...] = ()      # repeating pattern, e.g. ("L",)*5+("G",)
                                             # "L" local attn, "G" global attn,
                                             # "R" RG-LRU recurrent, "M" mamba2 SSD
    # mixture of experts --------------------------------------------------
    n_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25             # training dispatch only;
                                             # serving is drop-free
    norm_topk_prob: bool = True              # renormalise the top-k gates
    router_aux_coef: float = 0.01
    # state-space (mamba2 / SSD) -----------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    # hybrid (RG-LRU) ------------------------------------------------------
    lru_width: int = 0
    # encoder-decoder ------------------------------------------------------
    n_enc_layers: int = 0                    # if > 0 the model is enc-dec
    # modality frontend stub ----------------------------------------------
    frontend: str = "none"                   # none | vision | audio
    frontend_tokens: int = 0                 # number of stub embedding positions
    # numerics / misc ------------------------------------------------------
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # training-time switches (not architecture) ----------------------------
    remat_policy: str = "nothing"            # nothing | dots | full(=no remat)
    attn_impl: str = "scan"                  # scan | unrolled (block-skipping)
    attn_chunk_q: int = 512
    attn_chunk_k: int = 1024
    # KV-cache head count (0 = n_kv_heads). Setting this to the TP degree
    # stores the cache pre-repeated so decode shards cleanly over heads
    # (2x memory for kv=8@tp=16, zero attention collectives) — the standard
    # serving layout; a §Perf hillclimb knob.
    decode_cache_heads: int = 0

    # derived --------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def q_groups(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """True when *decode state* is bounded (sub-quadratic / constant)."""
        return self.family in ("ssm", "hybrid")

    def pattern_for_layers(self, n: Optional[int] = None) -> Tuple[str, ...]:
        """Expand the repeating layer pattern to n layers."""
        n = n if n is not None else self.n_layers
        pat = self.layer_pattern or ("G",)
        return tuple(pat[i % len(pat)] for i in range(n))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # reduced configs for CPU smoke tests ----------------------------------
    def reduced(self) -> "ModelConfig":
        """Small config of the same family for CPU smoke tests.

        Keeps the structural features (GQA ratio, pattern, MoE top-k, SSD)
        while shrinking width/depth/vocab so a forward+train step runs on one
        CPU device in well under a second.
        """
        pat = self.layer_pattern
        n_layers = max(len(pat), 2) if pat else 2
        if self.family == "hybrid":
            n_layers = len(pat) + 2 if pat else 3   # exercise group + tail path
        if pat and self.family == "dense":
            n_layers = len(pat) + 2                  # exercise tail path too
        kv = max(1, min(self.n_kv_heads, 2))
        heads = kv * min(self.q_groups, 2)
        hd = 16
        return self.replace(
            n_layers=n_layers,
            d_model=heads * hd if self.family != "hybrid" else 32,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=hd,
            d_ff=64,
            vocab_size=512,
            n_experts=min(self.n_experts, 8) if self.n_experts else 0,
            experts_per_token=min(self.experts_per_token, 2) if self.experts_per_token else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=8 if self.ssm_state else 64,
            lru_width=32 if self.lru_width else 0,
            n_enc_layers=2 if self.n_enc_layers else 0,
            local_window=min(self.local_window, 8) if self.local_window else 0,
            frontend_tokens=4 if self.frontend != "none" else 0,
            attn_chunk_q=8,
            attn_chunk_k=8,
            dtype="float32",
        )
