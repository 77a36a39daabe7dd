"""treeload — distributed tree loader (paper §3.3 Fig. 2, contribution C3).

The eSDK loader copied the program serially from the host to each of N cores:
cost = N * bytes over the slow host link.  COPRTHR-2 copies ONCE to core 0 and
fans out over the on-chip NoC in log2(N) rounds.

TPU analogue: a checkpoint/weight shard is read from host storage ONCE and
placed on a single root device of each replica group; the fan-out to the other
(dp-1) replicas runs over ICI with log2(dp) ``collective_permute`` rounds —
orders of magnitude faster than host DMA, and the host link cost no longer
scales with the pod count.  This is the restore path used by
``repro.checkpoint`` and the elastic re-shard path in ``repro.runtime``.

``serial_load`` (the eSDK analogue) is kept as the measured baseline.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _is_pow2(n: int) -> bool:
    return n & (n - 1) == 0 and n > 0


@functools.lru_cache(maxsize=64)
def _broadcast_fn(mesh: Mesh, axis: str, ndim: int):
    """Cached jitted tree-broadcast program per (mesh, axis, rank) — repeat
    restores re-dispatch the same executable (syscore re-execute semantics)."""
    n = mesh.shape[axis]
    spec = P(*([axis] + [None] * (ndim - 1)))

    def body(xs):
        i = jax.lax.axis_index(axis)
        for k in range(int(math.log2(n))):
            sz = 1 << k
            perm = [(src, src + sz) for src in range(sz)]
            recv = jax.lax.ppermute(xs, axis, perm)
            take = (i >= sz) & (i < 2 * sz)
            xs = jnp.where(take, recv, xs)
        return xs

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                 out_specs=spec))


def tree_broadcast_stacked(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Broadcast replica-0's slice of a stacked array to all replicas.

    x: (n, *shape) sharded P(axis) — slice 0 holds the payload, other slices
    are arbitrary.  Returns (n, *shape), every slice = payload, still sharded
    P(axis), after log2(n) ppermute rounds (each device sends/receives the
    payload at most once — the tree property).
    """
    n = mesh.shape[axis]
    assert _is_pow2(n), f"tree fan-out needs power-of-two axis, got {n}"
    return _broadcast_fn(mesh, axis, x.ndim)(x)


def tree_broadcast_replicate(host_array: np.ndarray, mesh: Mesh,
                             axis: str) -> jax.Array:
    """Host array -> array replicated over ``axis`` via one host copy + tree.

    The host-link cost is ONE copy of the payload (to the axis-0 shard);
    replication to the remaining replicas travels over the interconnect.
    """
    n = mesh.shape[axis]
    stacked = jnp.broadcast_to(host_array, (1,) + host_array.shape)
    # place payload on slice 0; other slices start as zeros (no host traffic
    # for them beyond the zero fill, which a real runtime allocates directly)
    buf = np.zeros((n,) + host_array.shape, host_array.dtype)
    buf[0] = host_array
    sharding = NamedSharding(mesh, P(*([axis] + [None] * host_array.ndim)))
    staged = jax.device_put(buf, sharding)
    full = tree_broadcast_stacked(staged, mesh, axis)
    return full


def serial_load(host_array: np.ndarray, mesh: Mesh, axis: str) -> jax.Array:
    """eSDK-analogue: host writes every replica's copy itself (N host copies)."""
    n = mesh.shape[axis]
    buf = np.stack([host_array] * n)       # N host-link transfers
    sharding = NamedSharding(mesh, P(*([axis] + [None] * host_array.ndim)))
    return jax.device_put(buf, sharding)


def loader_cost_model(bytes_payload: int, n_replicas: int, *,
                      host_bw: float = 8e9, ici_bw: float = 50e9,
                      ) -> Dict[str, float]:
    """Derived Table-1/Fig-2 numbers for arbitrary N (e.g. 512 chips).

    serial: N transfers over the host link.
    tree:   1 host transfer + log2(N) ICI rounds (pipelined rounds would
            overlap; we charge them sequentially — conservative).
    """
    serial = n_replicas * bytes_payload / host_bw
    tree = (bytes_payload / host_bw
            + math.ceil(math.log2(max(n_replicas, 2)))
            * bytes_payload / ici_bw)
    return {"serial_s": serial, "tree_s": tree,
            "speedup": serial / tree if tree > 0 else float("inf"),
            "host_bytes_serial": float(n_replicas * bytes_payload),
            "host_bytes_tree": float(bytes_payload)}
