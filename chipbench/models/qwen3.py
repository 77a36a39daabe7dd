"""Qwen3 dense decoder: weights, the plain reference, and its work counts.

Everything here is written from the published architecture (Qwen3
technical report, arXiv:2505.09388, and the Hugging Face ``Qwen3`` model
card): pre-norm RMSNorm blocks, grouped-query attention with a per-head
RMSNorm on queries and keys before rotary embeddings, SwiGLU MLP, and an
output head tied to the input embedding.  It imports nothing of the
system under test.

* :func:`draw` makes the weights from a seed, on the device, in the type
  they are served in, in this file's own layout (stacked per layer).
* :func:`to_program` lays those weights out as the serving engine's
  parameter tree: the one place that knows the program's names.
* :func:`logit_gaps` is the reference: float32 at ``highest`` matmul
  precision, one sequence at a time, optionally with every matmul operand
  rounded to float8 (e4m3) as the control.
* :func:`prefill_work` / :func:`decode_work` count the operations and
  bytes a step needs, from the configuration's shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_SPREAD = 0.1        # norm weights are 1 + NORM_SPREAD * N(0, 1)
EMBED_STD = 0.02         # the published initializer_range


def dims(cfg: dict) -> dict:
    hd = cfg["head_dim"]
    return dict(L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                H=cfg["num_attention_heads"], K=cfg["num_key_value_heads"],
                hd=hd, ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]))


def dtype_of(cfg: dict):
    return jnp.dtype(cfg["torch_dtype"])


# -- weights -------------------------------------------------------------------

def _shapes(cfg: dict) -> dict:
    g = dims(cfg)
    L, d, H, K, hd, ff = g["L"], g["d"], g["H"], g["K"], g["hd"], g["ff"]
    return {
        "embed": (g["V"], d),
        "final_norm": (d,),
        "input_norm": (L, d), "q_norm": (L, hd), "k_norm": (L, hd),
        "post_norm": (L, d),
        "q_proj": (L, d, H * hd), "k_proj": (L, d, K * hd),
        "v_proj": (L, d, K * hd), "o_proj": (L, H * hd, d),
        "gate_proj": (L, d, ff), "up_proj": (L, d, ff),
        "down_proj": (L, ff, d),
    }


def _key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % 2**31), seed // 2**31)


def _draw(cfg: dict, key):
    dt = dtype_of(cfg)
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(cfg).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm"):
            w = jnp.clip(1.0 + NORM_SPREAD * z, 0.5, 1.5)
        elif name == "embed":
            w = EMBED_STD * z
        else:
            w = z / np.sqrt(shape[-2])
        out[name] = w.astype(dt)
    return out


class _Frozen(dict):
    """A configuration that can key a cache of compiled programs."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def cfg_frozen(cfg: dict) -> _Frozen:
    return cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)


@functools.lru_cache(maxsize=None)
def _draw_fn(frozen):
    return jax.jit(functools.partial(_draw, frozen))


def draw(cfg: dict, seed: int):
    """The weights of ``seed``, in the served type, drawn in one program."""
    return _draw_fn(cfg_frozen(cfg))(_key(seed))


def to_program(cfg: dict, w: dict, padded_vocab: int):
    """The serving engine's parameter tree for the weights ``w``.

    The program's norms scale by ``1 + scale``, its embedding table is
    padded to ``padded_vocab`` rows, and its layers are stacked in one
    group of attention layers."""
    V = dims(cfg)["V"]
    embed = jnp.zeros((padded_vocab, w["embed"].shape[1]), w["embed"].dtype)
    embed = embed.at[:V].set(w["embed"])

    def norm(x):
        return x - jnp.ones((), x.dtype)

    return {
        "embed": embed,
        "final_norm": norm(w["final_norm"]),
        "tail": {},
        "groups": {"slot0": {
            "mix": {"ln": norm(w["input_norm"]),
                    "wq": w["q_proj"], "wk": w["k_proj"], "wv": w["v_proj"],
                    "wo": w["o_proj"],
                    "q_norm": norm(w["q_norm"]),
                    "k_norm": norm(w["k_norm"])},
            "ffn_ln": norm(w["post_norm"]),
            "mlp": {"w_gate": w["gate_proj"], "w_up": w["up_proj"],
                    "w_down": w["down_proj"]}}},
    }


def program_params(cfg: dict, seed: int, padded_vocab: int):
    """Draw and lay out in one jitted program; only the program tree is
    kept on the device."""
    return _program_fn(cfg_frozen(cfg), padded_vocab)(_key(seed))


@functools.lru_cache(maxsize=None)
def _program_fn(frozen, padded_vocab: int):
    return jax.jit(lambda key: to_program(frozen, _draw(frozen, key),
                                          padded_vocab))


# -- reference -----------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8):
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, -2)
    return x @ w


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd), rotate-half convention."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _hidden(cfg, w, tokens, fp8):
    """Final-normed hidden states (S, d) of one causal sequence."""
    g = dims(cfg)
    H, K, hd, eps = g["H"], g["K"], g["hd"], g["eps"]
    s = tokens.shape[0]
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        h = _rmsnorm(x, lw["input_norm"], eps)
        q = _mm(h, lw["q_proj"], fp8).reshape(s, H, hd)
        k = _mm(h, lw["k_proj"], fp8).reshape(s, K, hd)
        v = _mm(h, lw["v_proj"], fp8).reshape(s, K, hd)
        q = _rope(_rmsnorm(q, lw["q_norm"], eps), g["theta"])
        k = _rope(_rmsnorm(k, lw["k_norm"], eps), g["theta"])
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        if fp8:
            q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
        att = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        if fp8:
            att = _fp8(att, -1)
        o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, H * hd)
        x = x + _mm(o, lw["o_proj"], fp8)
        h = _rmsnorm(x, lw["post_norm"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, lw["gate_proj"], fp8))
                    * _mm(h, lw["up_proj"], fp8), lw["down_proj"], fp8)
        return x, None

    layers = {k: v for k, v in w.items()
              if k not in ("embed", "final_norm")}
    x, _ = jax.lax.scan(layer, x, layers)
    return _rmsnorm(x, w["final_norm"], eps)


def _gaps(cfg, w, tokens, lo, n, control):
    """Per position p in [lo, lo + n): the reference's best logit minus its
    logit of ``tokens[p + 1]`` (the served token) and, with ``control``,
    minus its logit of the token the float8 reference puts first."""
    with jax.default_matmul_precision("highest"):
        hid = _hidden(cfg, w, tokens, False)
        hid8 = _hidden(cfg, w, tokens, True) if control else None
        s = tokens.shape[0]
        idx = jnp.clip(lo + jnp.arange(s), 0, s - 1)
        served = tokens[jnp.clip(idx + 1, 0, s - 1)]
        head = w["embed"].T
        chunk = min(256, s)

        def block(i):
            rows = jax.lax.dynamic_slice_in_dim(idx, i * chunk, chunk)
            logits = hid[rows] @ head
            best = jnp.max(logits, -1)
            got = jnp.take_along_axis(
                logits, jax.lax.dynamic_slice_in_dim(served, i * chunk,
                                                     chunk)[:, None], -1)[:, 0]
            gap = best - got
            if control:
                pick = jnp.argmax(_mm(hid8[rows], head, True), -1)
                gap8 = best - jnp.take_along_axis(logits, pick[:, None],
                                                  -1)[:, 0]
            else:
                gap8 = jnp.zeros_like(gap)
            return gap, gap8

        gap, gap8 = jax.lax.map(block, jnp.arange(s // chunk))
        valid = jnp.arange(s) < n
        gap = jnp.where(valid, gap.reshape(-1), -jnp.inf)
        gap8 = jnp.where(valid, gap8.reshape(-1), -jnp.inf)
        return gap, gap8


@functools.lru_cache(maxsize=None)
def _gaps_fn(frozen, control):
    return jax.jit(functools.partial(_gaps, frozen, control=control))


def reference_weights(cfg: dict, seed: int):
    """The weights of ``seed`` as the reference holds them: the served
    values, upcast to float32."""
    w = draw(cfg, seed)
    return jax.tree.map(lambda x: x.astype(jnp.float32), w)


def logit_gaps(cfg: dict, w, prompt, served, length: int, control=False):
    """Gaps of every served token of one request (see :func:`_gaps`).

    ``prompt`` and ``served`` are token ids; the sequence is padded to
    ``length`` so that every request compiles to one program.  Returns
    numpy arrays (n_served,) of the served tokens' gaps and, with
    ``control``, of the float8 reference's."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n = len(served)
    assert len(seq) <= length and length % min(256, length) == 0, \
        (len(seq), length)
    tokens = np.zeros((length,), np.int32)
    tokens[:len(seq)] = seq
    gap, gap8 = _gaps_fn(cfg_frozen(cfg), bool(control))(
        w, jnp.asarray(tokens), jnp.asarray(len(prompt) - 1, jnp.int32),
        jnp.asarray(n, jnp.int32))
    return np.asarray(gap)[:n], np.asarray(gap8)[:n]


# -- work counts ---------------------------------------------------------------

def linear_params(cfg: dict) -> int:
    """Weights that every token multiplies, outside the output head."""
    g = dims(cfg)
    d, H, K, hd, ff = g["d"], g["H"], g["K"], g["hd"], g["ff"]
    return g["L"] * (d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff)


def kv_bytes_per_token(cfg: dict) -> int:
    g = dims(cfg)
    return 2 * g["L"] * g["K"] * g["hd"] * dtype_of(cfg).itemsize


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight a step reads: the linear layers, the tied
    head (the vocabulary's rows) and the norms."""
    g = dims(cfg)
    norms = g["L"] * (2 * g["d"] + 2 * g["hd"]) + g["d"]
    return (linear_params(cfg) + g["V"] * g["d"] + norms) \
        * dtype_of(cfg).itemsize


def prefill_work(cfg: dict, prompt_len: int):
    """(flops, bytes) that admitting one prompt needs: every prompt token
    through the layers, causal attention over the prompt, logits at the
    last position only; the weights read once and the prompt's keys and
    values written once."""
    g = dims(cfg)
    s = prompt_len
    flops = (2 * linear_params(cfg) * s
             + 2 * g["L"] * g["H"] * g["hd"] * s * (s + 1)
             + 2 * g["V"] * g["d"])
    nbytes = weight_bytes(cfg) + s * kv_bytes_per_token(cfg)
    return float(flops), float(nbytes)


def decode_work(cfg: dict, contexts, steps: int):
    """(flops, bytes) of one decode dispatch that runs ``steps`` in-graph
    steps and emits one token per entry of ``contexts``, each attending to
    that many positions (its own included).  Each step reads the weights
    once; each token reads the keys and values of the positions before it
    and writes its own."""
    g = dims(cfg)
    c = np.asarray(contexts, np.float64)
    per_tok = 2 * linear_params(cfg) + 2 * g["V"] * g["d"]
    flops = per_tok * len(c) + 4 * g["L"] * g["H"] * g["hd"] * c.sum()
    nbytes = steps * weight_bytes(cfg) + kv_bytes_per_token(cfg) * c.sum()
    return float(flops), float(nbytes)


def check_program_config(cfg: dict, mcfg) -> None:
    """Refuse to run when the program's model is not the one this file
    describes: the configuration file is what is run."""
    g = dims(cfg)
    want = {"n_layers": g["L"], "d_model": g["d"], "n_heads": g["H"],
            "n_kv_heads": g["K"], "resolved_head_dim": g["hd"],
            "d_ff": g["ff"], "vocab_size": g["V"], "norm_eps": g["eps"],
            "rope_theta": g["theta"], "qk_norm": True, "n_experts": 0,
            "tie_embeddings": cfg["tie_word_embeddings"],
            "dtype": cfg["torch_dtype"], "local_window": 0}
    got = {k: getattr(mcfg, k) for k in want}
    diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if diff or set(mcfg.pattern_for_layers()) != {"G"}:
        raise ValueError(f"the program's {mcfg.name} differs from the "
                         f"configuration file: {diff}")
