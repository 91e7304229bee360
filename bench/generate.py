"""The one traffic generator: requests and arrival times from a mix file.

A mix (``bench/traffic/<name>.json``) gives length distributions.  Every
seed gets the same *set* of sizes, in its own order, so that seeds change
which tokens are sent and in what order, not how much work there is:

- a block of ``block`` requests takes the ``(i + 0.5) / block`` quantiles
  of the prompt and output distributions, paired by one fixed permutation;
- the seed shuffles each block and draws the token ids, uniform over the
  vocabulary.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    prompt: np.ndarray      # [P] int32
    max_new: int


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy generator for any whole number ``seed`` (and a sub-stream)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the ``(i + 0.5) / n`` quantiles of ``dist``."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        v = dist["min"] + np.floor(q * (dist["max"] - dist["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def block_sizes(mix: dict) -> list[tuple[int, int]]:
    """The fixed ``(prompt, output)`` pairs of one block."""
    n = mix["block"]
    prompts = quantile_sizes(mix["prompt_tokens"], n)
    outputs = quantile_sizes(mix["output_tokens"], n)
    pairing = np.random.default_rng(0).permutation(n)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs[pairing])]


def requests(mix: dict, vocab: int, seed: int, n: int) -> list[Req]:
    """The first ``n`` requests of the mix for ``seed``."""
    sizes = block_sizes(mix)
    rng = rng_for(seed)
    out: list[Req] = []
    while len(out) < n:
        for j in rng.permutation(len(sizes)):
            p, o = sizes[j]
            out.append(Req(rng.integers(0, vocab, p, dtype=np.int32), o))
    return out[:n]


def prefill_buckets(mix: dict, bucket_to, floor: int) -> list[int]:
    """Every prefill extent the mix can produce under the engine's rule
    ``bucket_to(max prompt of a wave, floor)``."""
    d = mix["prompt_tokens"]
    return sorted({bucket_to(p, floor) for p in range(d["min"], d["max"] + 1)})
