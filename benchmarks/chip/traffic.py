"""The one traffic generator: waves of requests from a mix file.

A mix (``traffic/<name>.json``) is data only. Its keys:

* ``wave``: requests per wave, handed to one ``serve_continuous`` call.
* ``prompt``: the prompt length distribution (see ``quantile``). With
  ``docs`` it is the length of the question appended to a document.
* ``docs`` (optional): ``{"count": n, <distribution>}``: each wave holds
  ``n`` documents, each asked ``wave / n`` questions (document ``d`` gets
  the question lengths of strata ``d, d + n, ...``), and the ``wave``
  requests are shuffled together.
* ``output``: output tokens per request, the same in every wave (the
  engine takes one ``max_new_tokens`` per call).

Every seed gets the same sizes in the same order: the lengths of a wave
are the distribution's quantiles at ``(i + 0.5) / n``, shuffled by the
wave's index alone, and the seed draws only the token ids. Every wave
holds the same sizes, so a run's work per wave does not change with its
seed or with how many waves fit the window. (With the order drawn
from the seed too, the order moved the p90 of time per output token by
up to 15% between seeds of the long-context cell on a v5e.)
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List

import numpy as np

def quantile(dist: dict, q: float) -> int:
    """Length at quantile ``q`` (0 < q < 1) of a length distribution:
    ``uniform`` and ``loguniform`` over [min, max]; ``lognormal`` with
    ``median`` and ``sigma``, clipped to [min, max]."""
    lo, hi = dist["min"], dist["max"]
    kind = dist["dist"]
    if kind == "uniform":
        x = lo + q * (hi - lo)
    elif kind == "loguniform":
        x = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(q)
        x = dist["median"] * math.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), lo), hi))


def strata(dist: dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles ``(i + 0.5) / n``."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator keyed by the seed (any size) and a stream path."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


@dataclass
class Wave:
    prompts: List[List[int]]
    max_new_tokens: int
    doc_of: List[int]           # document index per request, -1 if none


# stream ids: the measured waves and the warm-up never share tokens
MEASURED, WARMUP, SAMPLE = 0, 1, 2


def wave(mix: dict, vocab: int, seed: int, index: int,
         stream: int = MEASURED) -> Wave:
    """Wave ``index`` of a mix for ``seed``."""
    n = mix["wave"]
    rng = rng_for(seed, stream, index)            # token ids
    order = np.random.default_rng([stream, index])  # sizes' order
    max_new = mix["output"]
    q_lens = strata(mix["prompt"], n)
    docs = mix.get("docs")
    if docs is None:
        prompts = [rng.integers(1, vocab, q_lens[i]).tolist()
                   for i in order.permutation(n)]
        return Wave(prompts, max_new, [-1] * n)
    n_docs = docs["count"]
    if n % n_docs:
        raise ValueError(f"wave {n} is not a multiple of {n_docs} documents")
    d_lens = strata(docs, n_docs)
    texts = [rng.integers(1, vocab, d).tolist() for d in d_lens]
    # document d is asked the questions of strata d, d + n_docs, ...
    pairs = [(i % n_docs, q_lens[i]) for i in range(n)]
    pairs = [pairs[i] for i in order.permutation(n)]
    prompts = [texts[d] + rng.integers(1, vocab, q).tolist() for d, q in pairs]
    return Wave(prompts, max_new, [d for d, _ in pairs])

