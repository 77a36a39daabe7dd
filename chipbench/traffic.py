"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix is a JSON file under ``chipbench/mixes/`` (see ``chat.json``,
``offline.json``), found by its name.  Two kinds exist:

``open_loop``
    Poisson arrivals at ``rate_per_s``.  A window of ``T`` seconds holds
    ``N = round(rate * T)`` arrivals.
``backlog``
    No arrival times: the harness keeps ``backlog_per_slot * batch``
    requests queued for the whole window, as a batch job does.  Lengths
    come in rounds of ``pool`` requests.

Lengths come from ``prompt_len`` and ``output_len``, each a lognormal
(``median``, ``sigma``) or uniform distribution clipped to ``[min, max]``.

Every seed gets the same work: the N gaps and lengths are the N
stratified quantiles ``F^-1((i + 0.5) / N)`` of their distributions, and
the seed only permutes their order (each independently) and draws the
token ids.  So two seeds differ in which request comes when and in what
it says, never in how much there is to do.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "mixes"
BACKLOG_POOL = 4096      # requests a backlog mix can hand out in one run


@dataclass(frozen=True)
class Item:
    due: float               # seconds after the window opens (0 for backlog)
    prompt: np.ndarray       # int32 token ids
    max_new: int


def load_mix(name: str, mix_dir: Path = MIX_DIR) -> dict:
    path = Path(mix_dir) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix named {name!r} at {path}")
    return json.loads(path.read_text())


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, stream])


def make_requests(mix: dict, seed: int, seconds: float, vocab: int
                  ) -> List[Item]:
    """The requests of one run, in the order they are due."""
    if mix["kind"] == "open_loop":
        n = max(1, round(mix["rate_per_s"] * seconds))
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u) / mix["rate_per_s"]
        gaps = _rng(seed, 0).permutation(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        k = n
    elif mix["kind"] == "backlog":
        n, k = BACKLOG_POOL, mix["pool"]
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown mix kind {mix['kind']!r}")
    # lengths come in rounds of k stratified quantiles, each round in an
    # order of its own: a backlog that consumes about one round per run
    # sees about the same work on every seed
    reps = -(-n // k)
    prompts = np.concatenate([_rng(seed, 10 + r).permutation(
        quantiles(mix["prompt_len"], k)) for r in range(reps)])[:n]
    outputs = np.concatenate([_rng(seed, 10 + reps + r).permutation(
        quantiles(mix["output_len"], k)) for r in range(reps)])[:n]
    ids = _rng(seed, 3)
    return [Item(float(t), ids.integers(0, vocab, int(p), dtype=np.int32),
                 int(o))
            for t, p, o in zip(due, prompts, outputs)]

