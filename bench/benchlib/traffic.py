"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) fixes a pool of ``pool`` request
sizes: prompt and output lengths at the midpoints of ``pool`` equal
quantile bins of clipped log-normal distributions, paired by a fixed
permutation. The pool is the same for every ``--seed``; the seed orders
it, afresh in every cycle through the pool, and draws the token ids. A
window of whole cycles (``harness.run_window``) therefore serves the same
sizes for every seed, in another order. Requests are grouped
``requests_per_call`` at a time into closed-loop ``run()`` calls. Token
ids are uniform over ``[2, vocab)``.
"""
from __future__ import annotations

import json
import math
from statistics import NormalDist
from typing import Iterator, List, Tuple

import numpy as np

# pairs the i-th prompt length with an output length; fixed, so that every
# mix's pool is a property of its file alone
PAIRING_SEED = 0


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("slots", "requests_per_call", "pool", "prompt", "output",
                "check_requests"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    if mix["pool"] % mix["requests_per_call"]:
        raise ValueError(f"{path}: pool must be a multiple of "
                         "requests_per_call")
    return mix


def lognormal_lengths(dist: dict, n: int) -> List[int]:
    """Midpoints of ``n`` equal-probability bins, clipped, ascending."""
    nd = NormalDist()
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def pool_sizes(mix: dict) -> List[Tuple[int, int]]:
    """The fixed (prompt_len, out_len) pairs, ascending in prompt length."""
    n = mix["pool"]
    prompts = lognormal_lengths(mix["prompt"], n)
    outs = lognormal_lengths(mix["output"], n)
    perm = np.random.default_rng(PAIRING_SEED).permutation(n)
    return [(prompts[i], outs[int(perm[i])]) for i in range(n)]


def calls_per_cycle(mix: dict) -> int:
    return mix["pool"] // mix["requests_per_call"]


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         stream]))


def size_stream(mix: dict, seed: int) -> Iterator[Tuple[int, int]]:
    """Pool sizes in the seed's order, a fresh order every cycle."""
    rng = _rng(seed, 0)
    sizes = pool_sizes(mix)
    while True:
        for i in rng.permutation(len(sizes)):
            yield sizes[int(i)]


def calls(mix: dict, vocab: int, seed: int
          ) -> Iterator[List[Tuple[np.ndarray, int]]]:
    """Closed-loop calls: lists of ``(prompt int32 [S], max_new_tokens)``."""
    sizes = size_stream(mix, seed)
    tok_rng = _rng(seed, 1)
    while True:
        batch = []
        for _ in range(mix["requests_per_call"]):
            p_len, o_len = next(sizes)
            prompt = tok_rng.integers(2, vocab, size=p_len).astype(np.int32)
            batch.append((prompt, o_len))
        yield batch


def max_lengths(mix: dict) -> Tuple[int, int]:
    """Longest prompt and longest output of the pool."""
    sizes = pool_sizes(mix)
    return max(p for p, _ in sizes), max(o for _, o in sizes)


def sample_rng(seed: int) -> np.random.Generator:
    """The seed's stream for choosing which finished requests to check."""
    return _rng(seed, 2)


def warmup_tokens(seed: int, n: int, vocab: int) -> np.ndarray:
    return _rng(seed, 3).integers(2, vocab, size=n).astype(np.int32)
