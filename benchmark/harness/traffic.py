"""One general generator of request traffic from a data file's
parameters.  A mix gives every seed the same set of sizes, in another
order: the seed may not change the work."""
from __future__ import annotations

import statistics
import zlib

import numpy as np

from harness.weights import host_rng


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: the (i + 1/2)/n quantiles of the distribution,
    clipped to ``[min, max]`` — the same for every seed."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    lens = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(lens), spec["min"], spec["max"]).astype(int)


def request_pool(traffic: dict) -> list:
    """The mix's fixed ``(prompt_len, output_len)`` pairs: each
    distribution's quantiles, paired by a permutation that depends on
    the mix's parameters alone."""
    n = traffic["pool_requests"]
    prompts = length_quantiles(traffic["prompt_tokens"], n)
    outputs = length_quantiles(traffic["output_tokens"], n)
    fixed = np.random.default_rng(
        zlib.crc32(repr(sorted(traffic["prompt_tokens"].items())).encode()))
    return list(zip(prompts.tolist(), outputs[fixed.permutation(n)].tolist()))


class RequestStream:
    """Requests in the seed's order: the pool, shuffled anew each time it
    is used up, with token ids uniform over the vocabulary."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        self.pool = request_pool(traffic)
        self.vocab = vocab_size
        self.order_rng = host_rng(seed, "order")
        self.token_rng = host_rng(seed, "tokens")
        self._queue: list = []
        self.made = 0

    def next(self):
        """``(prompt token ids, output length)``"""
        if not self._queue:
            self._queue = [self.pool[i] for i in
                           self.order_rng.permutation(len(self.pool))]
        p_len, o_len = self._queue.pop()
        self.made += 1
        return (self.token_rng.integers(0, self.vocab, p_len,
                                        dtype=np.int32), int(o_len))
