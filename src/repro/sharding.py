"""Logical-axis sharding rules (MaxText-style) for multi-pod meshes.

Parameters are annotated with *logical* axis names at init time; a rules
table maps logical axes onto physical mesh axes.  This keeps model code
mesh-agnostic and makes hillclimbing a sharding change a one-line rule edit.

Physical axes:
  pod    — inter-pod data parallelism (2 pods in the production mesh)
  data   — intra-pod data parallelism (16)
  model  — tensor / expert / sequence parallelism (16)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rules: Megatron-style TP on the model axis, DP over (pod, data).
# "fsdp" variants additionally shard a weight axis over the DP axes so that
# optimizer state for the big archs fits per-chip.
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_attn": None,   # within-block seq: NEVER sharded (SP gathers at block edges)
    "embed": None,              # d_model axis of activations / weights
    "embed_fsdp": None,         # d_model axis on params when FSDP enabled
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    # kv *projection weights*: replicated when n_kv_heads % tp != 0 (the
    # launcher overrides this per arch) so the kv->heads repeat is a local
    # slice instead of a GSPMD replicate-fallback; Megatron's kv-replication.
    "kv_heads_w": "model",
    "qkv": None,
    "ff": "model",
    "experts": "model",         # expert parallelism
    "expert_ff": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "lru": "model",
    "conv": None,
    "layers": None,             # stacked-scan leading axis — never sharded
    "norm": None,
}

FSDP_RULES = dict(DEFAULT_RULES, embed_fsdp=("pod", "data"))

# Sequence-parallel rules (hillclimb knob): long activations shard over model.
SP_RULES = dict(DEFAULT_RULES, seq="model")


def make_rules(fsdp: bool = False, seq_parallel: bool = False) -> Dict[str, Any]:
    rules = dict(FSDP_RULES if fsdp else DEFAULT_RULES)
    if seq_parallel:
        rules["seq"] = "model"
    return rules


def spec_from_logical(logical: Tuple[Optional[str], ...], rules: Dict[str, Any],
                      mesh: Optional[Mesh] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    Drops physical axes that are absent from the mesh (so the same logical
    annotations work on 1-device CPU, single-pod and multi-pod meshes).
    """
    names = set(mesh.axis_names) if mesh is not None else None

    def resolve(ax):
        if ax is None:
            return None
        phys = rules.get(ax, None)
        if phys is None:
            return None
        if isinstance(phys, (tuple, list)):
            kept = tuple(p for p in phys if names is None or p in names)
            return kept if kept else None
        return phys if (names is None or phys in names) else None

    return P(*[resolve(ax) for ax in logical])


class LogicalArray:
    """A ShapeDtypeStruct + logical axes pair used during abstract init."""

    __slots__ = ("shape", "dtype", "logical")

    def __init__(self, shape, dtype, logical):
        assert len(shape) == len(logical), (shape, logical)
        self.shape = tuple(shape)
        self.dtype = dtype
        self.logical = tuple(logical)

    def __repr__(self):
        return f"LogicalArray({self.shape}, {self.dtype}, {self.logical})"


def _axis_factor(ax, mesh: Mesh) -> int:
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def fit_spec(shape: Tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Make a PartitionSpec valid as a pjit *argument* sharding.

    jit in_shardings require every sharded dimension to divide evenly.  When
    a dim fails (e.g. kv_heads=8 over a 16-way model axis), the ``model``
    axis is MOVED to the right-most free dim that divides (for KV caches that
    is head_dim — the layout real engines use); other axes are dropped
    (replicated).  Intermediate constraints don't need this (GSPMD pads)."""
    specl = list(spec) + [None] * (len(shape) - len(spec))
    for i, ax in enumerate(list(specl)):
        if ax is None:
            continue
        if shape[i] % _axis_factor(ax, mesh) == 0:
            continue
        specl[i] = None
        if ax == "model" or (isinstance(ax, tuple) and ax == ("model",)):
            for j in range(len(shape) - 1, -1, -1):
                if (j != i and specl[j] is None
                        and shape[j] % _axis_factor(ax, mesh) == 0
                        and shape[j] > 1):
                    specl[j] = ax
                    break
    return P(*specl)


def tree_specs(logical_tree, rules: Dict[str, Any], mesh: Optional[Mesh] = None):
    """pytree of LogicalArray -> pytree of PartitionSpec."""
    return jax.tree.map(
        lambda la: spec_from_logical(la.logical, rules, mesh),
        logical_tree, is_leaf=lambda x: isinstance(x, LogicalArray))


def tree_shardings(logical_tree, rules, mesh: Mesh):
    def resolve(la: LogicalArray):
        spec = spec_from_logical(la.logical, rules, mesh)
        return NamedSharding(mesh, fit_spec(la.shape, spec, mesh))
    return jax.tree.map(resolve, logical_tree,
                        is_leaf=lambda x: isinstance(x, LogicalArray))


def tree_structs(logical_tree):
    """pytree of LogicalArray -> pytree of ShapeDtypeStruct (for AOT lowering)."""
    return jax.tree.map(
        lambda la: jax.ShapeDtypeStruct(la.shape, la.dtype),
        logical_tree, is_leaf=lambda x: isinstance(x, LogicalArray))


def constrain(x: jax.Array, logical: Tuple[Optional[str], ...],
              rules: Dict[str, Any]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op outside a mesh context.

    The resolved spec goes through :func:`fit_spec` so a constraint can
    never demand a sharding the shape doesn't divide (e.g. 4 heads over an
    8-way model axis): GSPMD would satisfy it by padding + full
    rematerialization of the tensor, the exact resharding storm the
    constraint is meant to prevent.  Dividing shapes are unaffected."""
    mesh = get_abstract_mesh_or_none()
    if mesh is None or mesh.empty:
        return x
    spec = spec_from_logical(logical, rules, mesh)
    spec = fit_spec(tuple(x.shape), spec, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def get_abstract_mesh_or_none():
    """The mesh the current trace resolves logical axes against (the one
    ``jax.set_mesh`` entered), or None outside any mesh context."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m
