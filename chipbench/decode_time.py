"""Decode device time of a traced run by scope and by operation, per chip,
for the per-layer metrics of a model whose layers span several chips.

``spans.read_run`` charges each leaf operation of the ``decode`` and
``decode_horizon`` modules to its scope, averaged over the chips' device
planes; this sums the two modules and names the collective operations
(``all-reduce``, ``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, each with its ``-start`` / ``-done`` halves) by their
instruction names.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Optional, Tuple

from chipbench import spans

COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?([.-]|$)")

Seconds = Dict[Tuple[Optional[str], str], float]


def is_collective(instruction: str) -> bool:
    return COLLECTIVE.match(instruction) is not None


def decode_seconds(win) -> Optional[Seconds]:
    """{(scope, instruction): seconds} of leaf operations of the decode
    modules in the traced window, per chip; ``None`` without a profile or
    without a decode operation in it."""
    got = spans.read_run(win)
    if got is None:
        return None
    out: Seconds = defaultdict(float)
    for m in got["decode_modules"]:
        for k, v in got["scoped"].get(m, {}).items():
            out[k] += v
    return dict(out) if sum(out.values()) > 0 else None


def scope_seconds(secs: Seconds, scope: str) -> float:
    return sum(v for (sc, _), v in secs.items() if sc == scope)
