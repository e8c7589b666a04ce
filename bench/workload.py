"""Requests of one traffic mix, drawn from a seed.

A traffic file (``bench/traffic/<mix>.json``) is data: the loop (``closed``:
one client sends its next request when the last one finished), lognormal
prompt and output lengths (median, sigma, clipped to [min, max]), the size
of the pool of lengths, and the scheduler's settings.  This module is the
one generator that reads it.

Every seed gets the same amount of work: the pool's lengths are the
lognormal's quantiles at evenly spaced probabilities, and each round of
the pool sends every one of them once, in an order the seed shuffles,
with prompts of random tokens the seed draws.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

_STREAM = {"closed": 4, "warm": 5, "sample": 6}


@dataclasses.dataclass
class Req:
    """One request: its prompt and how many tokens it asks for."""
    rid: str
    prompt: np.ndarray
    max_new: int


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([seed, _STREAM[stream]])


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lognormal quantiles at probabilities (i + 0.5) / n, clipped."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def check(traffic: Dict) -> None:
    worst = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    if worst > traffic["max_len"]:
        raise ValueError(f"{traffic['name']}: prompt {traffic['prompt_len']}"
                         f" + output {traffic['output_len']} can exceed "
                         f"max_len {traffic['max_len']}")
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")


def closed_loop(traffic: Dict, vocab: int, seed: int) -> Iterator[Req]:
    """An endless stream for one client: rounds of ``pool`` requests, each
    round the same lengths in a new order, every prompt new."""
    r = rng(seed, "closed")
    n = int(traffic["pool"])
    plen0 = lengths(traffic["prompt_len"], n)
    out0 = lengths(traffic["output_len"], n)
    for k in range(1 << 30):
        plen, out = r.permutation(plen0), r.permutation(out0)
        for i in range(n):
            yield Req(f"c{k * n + i}",
                      r.integers(0, vocab, int(plen[i])).astype(np.int32),
                      int(out[i]))


def warmup(traffic: Dict, vocab: int) -> List[List[Req]]:
    """Rounds of requests that compile every program the window runs, the
    same for every seed: prompts of a full and a partial chunk, a few
    decode steps, then a prompt that opens with a few tokens of an earlier
    one, as random prompts now and then do: the prefix cache matches them
    and copies the partly shared block."""
    r = rng(0, "warm")
    chunk = traffic["scheduler"]["prefill_chunk"]
    n = max(2, min(traffic["scheduler"]["num_slots"], 4))
    first = [Req(f"warm{i}", r.integers(0, vocab, chunk + chunk // 2 + i)
                 .astype(np.int32), 4 + i) for i in range(n)]
    shared = np.concatenate([first[0].prompt[:3],
                             r.integers(0, vocab, chunk).astype(np.int32)])
    return [first, [Req("warm-shared", shared, 4)]]


def sample(done: List[Req], served: Dict[str, np.ndarray], seed: int,
           tokens: int) -> List[Req]:
    """Finished requests for the check: the longest (prompt + served),
    then others in an order drawn from the seed, until ``tokens`` served
    tokens are covered."""
    if not done:
        return []
    size = [len(q.prompt) + len(served[q.rid]) for q in done]
    first = int(np.argmax(size))
    order = [first] + [int(i) for i in rng(seed, "sample")
                       .permutation(len(done)) if i != first]
    out, n = [], 0
    for i in order:
        if n >= tokens:
            break
        out.append(done[i])
        n += len(served[done[i].rid])
    return out


__all__ = ["Req", "lengths", "check", "closed_loop", "warmup", "sample"]
