"""OLMoE mixture-of-experts decoder: weights, the plain reference, and its
work counts.

Everything here is written from the published architecture (OLMoE,
arXiv:2409.02060, and the Hugging Face ``OlmoeForCausalLM`` model of
``allenai/OLMoE-1B-7B-0924``): pre-norm RMSNorm blocks; multi-head
attention whose queries and keys each pass one RMSNorm over the whole
projection width (all heads at once) before the split into heads and the
rotary embeddings; a mixture-of-experts FFN whose router takes a softmax
over every expert and keeps the top ``num_experts_per_tok`` probabilities as
they are (``norm_topk_prob`` false: no renormalisation), each expert a
SwiGLU, no shared expert; a final RMSNorm and an untied output head.

The weights of the whole model do not fit one chip, so they are drawn
sharded over the chips of the configuration's engine (``engine.shard``):
the reference's own layout splits experts, heads and the vocabulary over
the mesh; the program's layout is the serving engine's (``program_params``).
Both hold the served type (bf16); the reference upcasts one layer at a
time inside its computation.

* :func:`draw` makes the weights from a seed, on the devices, in the type
  they are served in, in this file's own layout (stacked per layer).
* :func:`to_program` lays those weights out as the serving engine's
  parameter tree: the one place that knows the program's names.
* :func:`logit_gaps` is the reference: float32 at ``highest`` matmul
  precision, one sequence at a time, no cache, no padding that reaches a
  compared position, every expert computed for every token and weighted
  by its gate (zero for the experts a token did not pick, which is exact);
  optionally with every matmul operand rounded to float8 (e4m3) as the
  control.
* :func:`prefill_work` / :func:`decode_work` count the operations and
  bytes a step needs, from the configuration's shapes;
  :func:`moe_decode_work` counts the MoE layer's share of one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

NORM_SPREAD = 0.1        # norm weights are 1 + NORM_SPREAD * N(0, 1)
EMBED_STD = 0.02         # the published initializer_range


def dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=H,
                K=cfg["num_key_value_heads"], hd=d // H,
                ff=cfg["intermediate_size"], E=cfg["num_experts"],
                k=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
                norm_topk=bool(cfg["norm_topk_prob"]))


def dtype_of(cfg: dict):
    return jnp.dtype(cfg["torch_dtype"])


def chips(cfg: dict) -> int:
    """Devices the engine divides each layer over."""
    return int(cfg["engine"].get("shard", {}).get("n_devices", 1))


# -- weights -------------------------------------------------------------------

M = "model"
# name -> (shape from dims, the reference's split over the mesh)
_LAYOUT = {
    "embed": (lambda g: (g["V"], g["d"]), P(M, None)),
    "lm_head": (lambda g: (g["d"], g["V"]), P(None, M)),
    "final_norm": (lambda g: (g["d"],), P()),
    "input_norm": (lambda g: (g["L"], g["d"]), P()),
    "post_norm": (lambda g: (g["L"], g["d"]), P()),
    "q_norm": (lambda g: (g["L"], g["H"] * g["hd"]), P(None, M)),
    "k_norm": (lambda g: (g["L"], g["K"] * g["hd"]), P(None, M)),
    "q_proj": (lambda g: (g["L"], g["d"], g["H"] * g["hd"]), P(None, None, M)),
    "k_proj": (lambda g: (g["L"], g["d"], g["K"] * g["hd"]), P(None, None, M)),
    "v_proj": (lambda g: (g["L"], g["d"], g["K"] * g["hd"]), P(None, None, M)),
    "o_proj": (lambda g: (g["L"], g["H"] * g["hd"], g["d"]), P(None, M, None)),
    "router": (lambda g: (g["L"], g["d"], g["E"]), P()),
    "gate_proj": (lambda g: (g["L"], g["E"], g["d"], g["ff"]),
                  P(None, M, None, None)),
    "up_proj": (lambda g: (g["L"], g["E"], g["d"], g["ff"]),
                P(None, M, None, None)),
    "down_proj": (lambda g: (g["L"], g["E"], g["ff"], g["d"]),
                  P(None, M, None, None)),
}


def _shapes(cfg: dict) -> dict:
    g = dims(cfg)
    return {name: shape(g) for name, (shape, _) in _LAYOUT.items()}


def _mesh(cfg: dict):
    """The engine's mesh over the configuration's chips; ``None`` on one."""
    if chips(cfg) == 1:
        return None
    from repro.launch.mesh import serving_mesh
    return serving_mesh(chips(cfg))


def _shardings(cfg: dict):
    mesh = _mesh(cfg)
    if mesh is None:
        return None
    return {name: NamedSharding(mesh, spec)
            for name, (_, spec) in _LAYOUT.items()}


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
            # fan-in: the contracted axis, second from last
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
    return jax.jit(functools.partial(_draw, frozen),
                   out_shardings=_shardings(frozen))


def draw(cfg: dict, seed: int):
    """The weights of ``seed``, in the served type, drawn in one program
    straight onto the configuration's chips."""
    return _draw_fn(cfg_frozen(cfg))(_key(seed))


def to_program(cfg: dict, w: dict, padded_vocab: int):
    """The serving engine's parameter tree for the weights ``w``.

    The program's norms scale by ``1 + scale``, its embedding table and
    output head are padded to ``padded_vocab`` ids, and its layers are
    stacked in one group of attention layers with a MoE FFN."""
    V = dims(cfg)["V"]
    pad = padded_vocab - V
    embed = jnp.pad(w["embed"], ((0, pad), (0, 0)))
    head = jnp.pad(w["lm_head"], ((0, 0), (0, pad)))

    def norm(x):
        return x - jnp.ones((), x.dtype)

    return {
        "embed": embed,
        "lm_head": head,
        "final_norm": norm(w["final_norm"]),
        "tail": {},
        "groups": {"slot0": {
            "mix": {"ln": norm(w["input_norm"]),
                    "wq": w["q_proj"], "wk": w["k_proj"], "wv": w["v_proj"],
                    "wo": w["o_proj"],
                    "q_norm": norm(w["q_norm"]),
                    "k_norm": norm(w["k_norm"])},
            "ffn_ln": norm(w["post_norm"]),
            "moe": {"router": w["router"], "w_gate": w["gate_proj"],
                    "w_up": w["up_proj"], "w_down": w["down_proj"]}}},
    }


def program_params(cfg: dict, seed: int, padded_vocab: int):
    """Draw and lay out in one jitted program, straight into the serving
    engine's shardings; only the program tree is kept on the devices."""
    return _program_fn(cfg_frozen(cfg), padded_vocab)(_key(seed))


@functools.lru_cache(maxsize=None)
def _program_fn(frozen, padded_vocab: int):
    out = None
    mesh = _mesh(frozen)
    if mesh is not None:
        from repro.models import registry, transformer
        from repro.sharding import make_rules, tree_shardings
        prog = frozen["program"]
        mcfg = registry.get_config(prog["arch"], reduced=prog["reduced"])
        out = tree_shardings(transformer.abstract_params(mcfg), make_rules(),
                             mesh)
    return jax.jit(lambda key: to_program(frozen, _draw(frozen, key),
                                          padded_vocab), out_shardings=out)


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


def _moe(cfg, h, lw, fp8):
    """The MoE FFN as its equation: y = sum over the top-k experts of the
    router's softmax probability times the expert's SwiGLU, computed as
    every expert weighted by a gate that is zero off the top k."""
    g = dims(cfg)
    probs = jax.nn.softmax(_mm(h, lw["router"], fp8), axis=-1)   # (S, E)
    top_p, top_i = jax.lax.top_k(probs, g["k"])
    if g["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(top_i, g["E"], dtype=jnp.float32)
                    * top_p[..., None], axis=1)                  # (S, E)
    hx = _fp8(h, -1) if fp8 else h
    wg, wu, wd = lw["gate_proj"], lw["up_proj"], lw["down_proj"]
    if fp8:
        wg, wu, wd = _fp8(wg, -2), _fp8(wu, -2), _fp8(wd, -2)
    a = jax.nn.silu(jnp.einsum("sd,edf->esf", hx, wg)) * jnp.einsum(
        "sd,edf->esf", hx, wu)
    if fp8:
        a = _fp8(a, -1)
    y = jnp.einsum("esf,efd->esd", a, wd)
    return jnp.einsum("esd,se->sd", y, gates)


def _hidden(cfg, w, tokens, fp8):
    """Final-normed hidden states (S, d) of one causal sequence."""
    g = dims(cfg)
    H, K, hd, eps = g["H"], g["K"], g["hd"], g["eps"]
    s = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        h = _rmsnorm(x, lw["input_norm"], eps)
        # one norm over each whole projection, before the split into heads
        q = _rmsnorm(_mm(h, lw["q_proj"], fp8), lw["q_norm"], eps)
        k = _rmsnorm(_mm(h, lw["k_proj"], fp8), lw["k_norm"], eps)
        v = _mm(h, lw["v_proj"], fp8).reshape(s, K, hd)
        q = _rope(q.reshape(s, H, hd), g["theta"])
        k = _rope(k.reshape(s, K, hd), g["theta"])
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
        return x + _moe(cfg, h, lw, fp8), None

    layers = {k: v for k, v in w.items()
              if k not in ("embed", "lm_head", "final_norm")}
    x, _ = jax.lax.scan(layer, x, layers)
    return _rmsnorm(x, w["final_norm"].astype(jnp.float32), eps)


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
        head = w["lm_head"].astype(jnp.float32)
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
    values, on the configuration's chips (upcast inside the computation,
    a layer at a time)."""
    return draw(cfg, seed)


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


def reference_logits(cfg: dict, w, tokens):
    """The reference's logits (S, V) over one whole sequence."""
    with jax.default_matmul_precision("highest"):
        hid = _hidden(cfg, w, jnp.asarray(tokens, jnp.int32), False)
        return np.asarray(hid @ w["lm_head"].astype(jnp.float32))


# -- work counts ---------------------------------------------------------------

def linear_params(cfg: dict) -> int:
    """Weights that every token multiplies, outside the output head: the
    attention projections, the router and its top-k experts."""
    g = dims(cfg)
    d, H, K, hd = g["d"], g["H"], g["K"], g["hd"]
    return g["L"] * (2 * d * H * hd + 2 * d * K * hd + d * g["E"]
                     + g["k"] * 3 * d * g["ff"])


def kv_bytes_per_token(cfg: dict) -> int:
    g = dims(cfg)
    return 2 * g["L"] * g["K"] * g["hd"] * dtype_of(cfg).itemsize


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight a step reads: the attention projections, the
    router, every expert (a decode batch routes to nearly all of them),
    the output head (the vocabulary's rows) and the norms."""
    g = dims(cfg)
    d, H, K, hd = g["d"], g["H"], g["K"], g["hd"]
    layer = (2 * d * H * hd + 2 * d * K * hd + d * g["E"]
             + g["E"] * 3 * d * g["ff"] + 2 * d + (H + K) * hd)
    return (g["L"] * layer + g["V"] * d + d) * dtype_of(cfg).itemsize


def prefill_work(cfg: dict, prompt_len: int):
    """(flops, bytes) that admitting one prompt needs: every prompt token
    through the layers and its k experts, causal attention over the
    prompt, logits at the last position only; the weights read once and
    the prompt's keys and values written once."""
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


def moe_decode_work(cfg: dict, tokens: int, steps: int):
    """(flops, bytes) of the MoE layers on one chip for one decode dispatch
    of ``steps`` in-graph steps that emits ``tokens``: each token's routed
    work (its k experts' SwiGLUs) divided over the chips, and the chip's
    held experts and the router read once per step."""
    g = dims(cfg)
    n = chips(cfg)
    expert = 3 * g["d"] * g["ff"]
    flops = tokens * g["L"] * g["k"] * 2 * expert / n
    nbytes = steps * g["L"] * (g["E"] // n * expert + g["d"] * g["E"]) \
        * dtype_of(cfg).itemsize
    return float(flops), float(nbytes)


def check_program_config(cfg: dict, mcfg) -> None:
    """Refuse to run when the program's model is not the one this file
    describes: the configuration file is what is run."""
    g = dims(cfg)
    want = {"family": "moe", "n_layers": g["L"], "d_model": g["d"],
            "n_heads": g["H"], "n_kv_heads": g["K"],
            "resolved_head_dim": g["hd"], "d_ff": g["ff"],
            "vocab_size": g["V"], "norm_eps": g["eps"],
            "rope_theta": g["theta"], "qk_norm": "full",
            "n_experts": g["E"], "experts_per_token": g["k"],
            "norm_topk_prob": g["norm_topk"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "dtype": cfg["torch_dtype"], "local_window": 0}
    got = {k: getattr(mcfg, k, None) for k in want}
    diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if diff or set(mcfg.pattern_for_layers()) != {"G"}:
        raise ValueError(f"the program's {mcfg.name} differs from the "
                         f"configuration file: {diff}")
