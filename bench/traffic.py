"""The one traffic generator: closed waves, read from a traffic file.

A wave is `slots` requests of `prompt_len` tokens, all admitted on one
tick; the next wave is submitted once the engine has drained. Every wave
holds the same multiset of output lengths: the quantiles (i + 0.5) / slots
of a lognormal with the file's median and sigma, rounded and clipped.
The seed only orders them over the lanes and draws the prompt tokens,
uniformly from the vocabulary, so two seeds give the engine the same
amount of work. Request classes cycle through the file's `classes` by lane.
"""
from __future__ import annotations

import itertools
import math
import statistics
from typing import Dict, Iterator, List

import numpy as np


def output_lengths(spec: Dict, n: int) -> List[int]:
    """The wave's new-token counts (the prefill's token not included)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    norm = statistics.NormalDist()
    out = []
    for i in range(n):
        z = norm.inv_cdf((i + 0.5) / n)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def waves(traffic: Dict, seed: int, vocab: int) -> Iterator[List[Dict]]:
    """Endless waves of request dicts: uid, prompt, new_tokens, cls."""
    n = traffic["slots"]
    lengths = np.asarray(output_lengths(traffic["output"], n))
    classes = traffic["classes"]
    uid = itertools.count()
    for w in itertools.count():
        rng = np.random.default_rng([int(seed), w])
        order = rng.permutation(n)
        prompts = rng.integers(0, vocab, (n, traffic["prompt_len"]),
                               dtype=np.int32)
        yield [{"uid": next(uid), "prompt": prompts[i],
                "new_tokens": int(lengths[order[i]]),
                "cls": classes[i % len(classes)]} for i in range(n)]
