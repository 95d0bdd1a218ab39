"""The one traffic generator: a mix is a data file of parameters.

``traffic/<name>.json`` keys:

  prompt_len          tokens per prompt (one length: the pool's prefill
                      program has one prompt shape)
  output_tokens       {"dist": "lognormal", "median", "sigma", "min",
                       "max"}: per-request generation budgets
  arrivals            the arrival process, ``arrivals/<arrivals>.py``
                      (``backlog``: a standing queue, every request due
                      at t=0; ``poisson``: open loop at the cell's
                      ``rate_rps``), plus whatever keys it reads
  flush_deadline_ms   the batcher's flush deadline

Every seed gets the same work in the same order: the seed draws the
prompts' token ids (uniform over the vocabulary) and nothing else.
Budgets, and the quantiles an arrival process turns into times, are the
quantiles (i + 0.5) / ``BLOCK`` of their distributions, laid out block
by block in one fixed shuffled order.  A window takes a prefix of the
queue, and near an open-loop knee the order of the same work alone moves
the tails, so no seed reorders it.
"""

from __future__ import annotations

import dataclasses
import math
from pathlib import Path
from statistics import NormalDist
from typing import Optional

import numpy as np

import spec

BLOCK = 64                    # requests per stratified block
ARRIVALS = Path(__file__).resolve().parent / "arrivals"


@dataclasses.dataclass(frozen=True)
class Requests:
    prompts: np.ndarray           # (n, prompt_len) int32
    budgets: np.ndarray           # (n,) int64 generation budgets
    arrival_ms: np.ndarray        # (n,) float64 due times from window start


def stratified(blocks: int, order_seed: int) -> np.ndarray:
    """(blocks * BLOCK,) quantiles (i + 0.5) / BLOCK, each block in one
    fixed shuffled order of its own stream ``order_seed``."""
    q = (np.arange(BLOCK) + 0.5) / BLOCK
    fixed = np.random.RandomState(order_seed)
    return np.concatenate([fixed.permutation(q) for _ in range(blocks)])


def budgets(spec: dict, q: np.ndarray) -> np.ndarray:
    """Generation budgets at quantiles ``q``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown output_tokens dist {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def arrival_process(name: str, directory: Path = ARRIVALS):
    """The module ``arrivals/<name>.py``: ``count(mix, rate_rps,
    seconds)`` requests to make, and ``times_ms(mix, rate_rps, q)`` due
    times at the stratified quantiles ``q``."""
    return spec.load_module(directory / f"{name}.py",
                            f"arrival process {name!r}")


def generate(traffic: dict, rate_rps: Optional[float], seconds: float,
             vocab: int, seed: int, arrivals_dir: Path = ARRIVALS
             ) -> Requests:
    """The requests of one run, deterministic in ``seed``."""
    process = arrival_process(traffic["arrivals"], arrivals_dir)
    n = int(process.count(traffic, rate_rps, seconds))
    blocks = math.ceil(n / BLOCK)         # whole blocks: the same work
    n = blocks * BLOCK
    prompts = np.random.RandomState(seed).randint(
        0, vocab, size=(n, int(traffic["prompt_len"])),
        dtype=np.int64).astype(np.int32)
    return Requests(
        prompts=prompts,
        budgets=budgets(traffic["output_tokens"], stratified(blocks, 0)),
        arrival_ms=np.asarray(process.times_ms(
            traffic, rate_rps, stratified(blocks, 1)), np.float64))
