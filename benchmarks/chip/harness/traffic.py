"""The one traffic generator: every mix is a data file of parameters.

A mix file (``workloads/<traffic>.json``) gives the loop and the length
distributions (``uniform`` or ``loguniform`` between ``min`` and
``max``):

    {"loop": "closed", "slots": 20, "queue": 200,
     "prompt": {"dist": "loguniform", "min": 128, "max": 512},
     "output": {"dist": "uniform", "min": 512, "max": 768}}

    {"loop": "train", "batch": 8, "seq_len": 512, "staged_batches": 64}

Lengths are drawn *stratified*: n requests take the n mid-quantiles of
each distribution, and the seed only shuffles them and draws the token
ids.  Every seed thus offers the same work in
another order, so the spread between seeds is the system's and not the
sampler's.  The stated medians and clips hold exactly.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np


def seeded(seed: int) -> Tuple[np.random.Generator, int]:
    """A numpy generator and a 31-bit JAX seed from any whole-number seed
    (a run's seed may exceed 32 signed bits)."""
    ss = np.random.SeedSequence(int(seed))
    jax_seed = int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)
    return np.random.default_rng(ss), jax_seed


def quantile(dist: dict, u: float) -> float:
    """The ``u``-quantile of a length distribution."""
    kind, lo, hi = dist["dist"], dist["min"], dist["max"]
    if kind == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(x, lo), hi)


def mid_quantiles(dist: dict, n: int) -> np.ndarray:
    """n whole lengths at the distribution's mid-quantiles, in order."""
    return np.array([int(round(quantile(dist, (i + 0.5) / n)))
                     for i in range(n)], np.int64)


def stratified(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n whole lengths at the distribution's mid-quantiles, shuffled."""
    return rng.permutation(mid_quantiles(dist, n))


@dataclass
class Req:
    prompt: np.ndarray           # int32 token ids
    max_new: int


def _prompts(lengths, vocab: int, rng) -> List[np.ndarray]:
    return [rng.integers(0, vocab, int(n), dtype=np.int64).astype(np.int32)
            for n in lengths]


def closed_loop(mix: dict, seed: int, vocab: int) -> Tuple[List[Req],
                                                           List[Req]]:
    """(first requests, one per slot; the queue that replaces finished
    ones).  The first requests stand for a loop that has run a while: each
    is a drawn request caught at a stratified share of its output, its
    prompt followed by the part of the output already made (as tokens of
    the prompt, prefilled in set-up) and the rest still to come.  So the
    window opens on contexts spread from prompt to prompt plus output,
    and the slots finish staggered, as in steady state.  A seed changes
    the token ids, not the sizes or their order: every seed's window
    does the same work."""
    rng, _ = seeded(seed)
    slots, q = mix["slots"], mix["queue"]
    # the first requests pair prompt, output and share in one fixed way
    # and come in one fixed order: the set-up prefills them in turn, and a
    # request prefilled early decodes while the rest are prefilled, so the
    # order decides how far each has got when the window opens
    fixed = np.random.default_rng(0)
    plen = mid_quantiles(mix["prompt"], slots)
    out = mid_quantiles(mix["output"], slots)[fixed.permutation(slots)]
    share = ((np.arange(slots) + 0.5) / slots)[fixed.permutation(slots)]
    first = []
    for i in range(slots):
        left = max(1, int(math.ceil(out[i] * share[i])))
        p, made = _prompts([plen[i], out[i] - left], vocab, rng)
        first.append(Req(np.concatenate([p, made]), left))
    # the queue's sizes come in one fixed order, so the requests that a
    # window refills its slots with are the same work for every seed
    queue = [Req(p, int(o)) for p, o in
             zip(_prompts(stratified(mix["prompt"], q, fixed), vocab, rng),
                 stratified(mix["output"], q, fixed))]
    return first, queue


def longest_prompt(mix: dict) -> int:
    """The longest prompt the engine is handed: a first request's prompt
    holds the part of its output already made."""
    return mix["prompt"]["max"] + mix["output"]["max"] - 1


def longest_context(mix: dict) -> int:
    return mix["prompt"]["max"] + mix["output"]["max"]
