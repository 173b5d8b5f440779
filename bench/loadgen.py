"""Closed-loop traffic from a mix file and a seed.

A mix (``bench/traffic/<name>.json``) gives the number of clients, the
lognormal prompt and output lengths (median, sigma, clip range) and the
fixed prompt lengths that a drawn prompt is rounded up to.  Round ``r``
hands the ``C`` clients one length from each of ``C`` equal-probability
strata of the distribution, at a fixed offset inside the stratum, in a
fixed shuffled order.  Every seed gets the same lengths in the same
order, so every run offers the same work: the seed draws the token ids
(and the weights), which the engine's schedule does not depend on.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, Iterator, List, Sequence

import numpy as np

GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0
ORDER_SEED = 20231118          # the fixed shuffle of lengths to clients


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    clients: int
    requests_per_client: int
    prompt: Dict
    output: Dict

    @classmethod
    def load(cls, path: Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        if d.get("loop") != "closed":
            raise ValueError(f"{path}: only closed-loop mixes are "
                             f"generated (loop={d.get('loop')!r})")
        return cls(name=d["name"], clients=int(d["clients"]),
                   requests_per_client=int(d["requests_per_client"]),
                   prompt=d["prompt"], output=d["output"])

    @property
    def prompt_lengths(self) -> List[int]:
        """Every prompt length a request of this mix can have."""
        return sorted(self.prompt["round_up_to"])

    @property
    def max_output(self) -> int:
        return int(self.output["max"])


def lognormal_quantile(dist: Dict, p: float) -> float:
    """Quantile ``p`` of the clipped lognormal of ``dist``."""
    z = NormalDist().inv_cdf(p)
    x = dist["median"] * math.exp(dist["sigma"] * z)
    return min(max(x, dist["min"]), dist["max"])


def round_up(n: float, allowed: Sequence[int]) -> int:
    """The smallest allowed length that is at least ``n``."""
    for a in sorted(allowed):
        if a >= n:
            return int(a)
    raise ValueError(f"length {n} exceeds every allowed length "
                     f"{sorted(allowed)}")


def stratified_lengths(dist: Dict, n: int, offset: float) -> List[int]:
    """``n`` lengths, one from each equal-probability stratum."""
    out = []
    for i in range(n):
        x = lognormal_quantile(dist, (i + offset) / n)
        out.append(round_up(x, dist["round_up_to"])
                   if "round_up_to" in dist else int(math.ceil(x)))
    return out


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    client: int
    index: int            # the client's n-th request
    prompt_len: int
    max_new_tokens: int


def schedule(mix: Mix) -> List[List[RequestSpec]]:
    """Per client, the lengths of its requests in the order it sends
    them."""
    rng = np.random.default_rng(ORDER_SEED)
    C = mix.clients
    per_client: List[List[RequestSpec]] = [[] for _ in range(C)]
    for r in range(mix.requests_per_client):
        # fixed offsets inside the strata: the same for every seed
        prompts = stratified_lengths(mix.prompt, C,
                                     (0.5 + r * GOLDEN) % 1.0)
        outputs = stratified_lengths(mix.output, C,
                                     (0.25 + r * GOLDEN * GOLDEN) % 1.0)
        pp = rng.permutation(C)
        po = rng.permutation(C)
        for c in range(C):
            per_client[c].append(RequestSpec(c, r, prompts[pp[c]],
                                             outputs[po[c]]))
    return per_client


class ClosedLoop:
    """Each client sends its next request when its last one finishes."""

    def __init__(self, mix: Mix, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.plan = schedule(mix)
        self._next = [0] * mix.clients
        # one token stream per client, so a client's prompts do not
        # depend on the order in which other clients' requests finish
        self._rngs = [np.random.default_rng([seed, c])
                      for c in range(mix.clients)]

    def next_request(self, client: int):
        """(spec, prompt token ids) of ``client``'s next request."""
        i = self._next[client]
        if i >= len(self.plan[client]):
            raise RuntimeError(
                f"client {client} ran out of its {i} requests; raise "
                f"requests_per_client in the {self.mix.name} mix")
        self._next[client] = i + 1
        spec = self.plan[client][i]
        ids = self._rngs[client].integers(0, self.vocab, spec.prompt_len,
                                          dtype=np.int32)
        return spec, ids


def warmup_prompts(mix: Mix, seed: int, vocab: int) -> Iterator:
    """One prompt of every length the mix can send."""
    rng = np.random.default_rng([seed, 1 << 20])
    for n in mix.prompt_lengths:
        yield rng.integers(0, vocab, n, dtype=np.int32)
