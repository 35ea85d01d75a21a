"""One general traffic generator; a traffic mix is a data file it reads
(`benchmark/traffic/<traffic>.json`).

Serving (`"kind": "serve"`). The lengths of a mix are a FIXED set — the
quantiles of the stated distribution, `block` of them — shuffled inside
each block of `block` requests, so any prefix of a run holds nearly the
same multiset of lengths. The shuffle is the mix's own (`shape_seed`);
`--seed` draws the token ids and the weights (and, in an open loop, the
order of the gaps). In a closed loop with greedy decoding and fixed
reply lengths, which request sits in which slot at which step then does
not depend on the seed, and a tail over some tens of requests is a tail
of the same requests in every run: with the order drawn from the seed,
`ttft_p95_ms` of ~100 requests read 185.7, 292.4 and 186.5 ms in three
runs (my chip runs, PR 25), so the order is not the seed's. Arrivals:

  closed   `clients` callers, each sends its next request `think_s`
           after its last one finished; the first ones start staggered
           over `stagger_s`, and caller i's first reply is cut to
           (i + 1) / clients of its length, so that replies end at all
           phases from the start, as they do in a loop that has run for
           long
  poisson  exponential gaps at `rate_rps`          } open loop: gaps are
  gamma    gamma gaps, squared CV = `burstiness`   } a fixed block too,
                                                     shuffled by the seed

(the gap arithmetic and the shared-prefix pools are copied from
`paddle_tpu/serving/loadgen.py`, whose `LoadSpec`/`build_requests` stay
the program's own; its `mmpp` mode keeps a hidden state from one arrival
to the next and cannot be given as a shuffled set, so it is not here).
A request is timed from when it was DUE, not from when the loop got
round to submitting it, and the lateness of the loop is reported.

Training (`"kind": "train"`): a stream of token sequences, each drawn
zipf(`zipf`) over a seeded permutation of the vocabulary, so that there
is something to learn and the loss can fall; sample `i` depends on
(seed, i) alone, so workers may make them in any order.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import List, Optional, Tuple

import numpy as np


# -- lengths -------------------------------------------------------------------

def length_set(spec: dict, n: int) -> np.ndarray:
    """`n` lengths: the (i + 0.5) / n quantiles of the distribution,
    clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec.get("min", 1),
                   spec.get("max", np.inf)).astype(np.int64)


def gap_set(arrival: dict, n: int) -> np.ndarray:
    """`n` inter-arrival gaps (s) with mean 1/rate: a fixed sample drawn
    from the mix's own `shape_seed`, rescaled to the exact mean."""
    rng = np.random.default_rng(arrival.get("shape_seed", 0))
    mean = 1.0 / arrival["rate_rps"]
    if arrival["mode"] == "poisson" or arrival.get("burstiness", 1.0) == 1.0:
        g = rng.exponential(mean, n)
    elif arrival["mode"] == "gamma":
        shape = 1.0 / float(arrival["burstiness"])
        g = rng.gamma(shape, mean / shape, n)
    else:
        raise ValueError(f"unknown open-loop arrival {arrival['mode']!r}")
    return g * (mean / g.mean())


def _block_order(rng, block: int, k: int) -> np.ndarray:
    """Indices into a block-sized set for requests 0..k-1: a fresh
    permutation of the block for every `block` requests."""
    n_blocks = -(-k // block)
    return np.concatenate([rng.permutation(block)
                           for _ in range(n_blocks)])[:k]


class ServeTraffic:
    """The requests of one run: `prompt(k)`, `max_new(k)`, and for an
    open loop `due(k)` seconds after the start."""

    def __init__(self, mix: dict, vocab_size: int, seed: int,
                 scale: Optional[dict] = None):
        self.mix = mix
        self.vocab = int(vocab_size)
        self.seed = int(seed)
        block = int(mix["block"])
        n = int(mix["num_requests"])
        plen = length_set(mix["prompt_len"], block)
        olen = length_set(mix["output_len"], block)
        if scale:                       # --rehearse: shrink to a tiny model
            plen = np.clip(plen // scale["prompt_div"], 1, scale["prompt_max"])
            olen = np.clip(olen // scale["output_div"], 1, scale["output_max"])
        # which output length goes with which prompt length, and the
        # order of the requests, are part of the mix, not of the seed
        shape_seed = mix.get("shape_seed", 0)
        pair = np.random.default_rng(shape_seed).permutation(block)
        order = _block_order(np.random.default_rng([shape_seed, 1]), block, n)
        self.prompt_len = plen[order]
        self.max_new = olen[pair][order]
        arrival = mix["arrival"]
        self.closed = arrival["mode"] == "closed"
        if self.closed:
            c = int(arrival["clients"])
            self.max_new[:c] = np.maximum(
                1, -(-self.max_new[:c] * (np.arange(c) + 1) // c))
        self.dues = None
        if not self.closed:
            gaps = gap_set(arrival, block)[_block_order(
                np.random.default_rng([self.seed, 2]), block, n)]
            self.dues = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        sp = mix.get("shared_prefix") or {}
        self.prefixes = self.prefix_of = None
        if sp.get("len", 0) > 0:
            # pool from the mix's shape_seed; which request takes which
            # prefix: bounded zipf, rank == index (copied arithmetic)
            prng = np.random.default_rng([shape_seed, 0x5A5A])
            self.prefixes = prng.integers(
                0, self.vocab, (max(1, sp["pool"]), sp["len"])).astype(np.int32)
            w = 1.0 / np.power(np.arange(1, len(self.prefixes) + 1.0),
                               float(sp["zipf"]))
            cdf = np.cumsum(w / w.sum())
            draws = np.random.default_rng([self.seed, 3]).random(n)
            self.prefix_of = np.minimum(np.searchsorted(cdf, draws),
                                        len(self.prefixes) - 1)

    def __len__(self) -> int:
        return len(self.prompt_len)

    def prompt(self, k: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 4, k])
        body = rng.integers(0, self.vocab,
                            (int(self.prompt_len[k]),)).astype(np.int32)
        if self.prefixes is None:
            return body
        return np.concatenate([self.prefixes[self.prefix_of[k]], body])


# -- training stream -----------------------------------------------------------

class TokenStream:
    """Map-style dataset of (ids[S], labels[S]) int32 pairs, labels the
    ids shifted by one. `paddle.io.DataLoader` forks its workers with a
    copy of this object."""

    def __init__(self, mix: dict, vocab_size: int, seq: int, seed: int):
        self.seq = int(seq)
        self.seed = int(seed)
        self.length = int(mix["samples"])
        rng = np.random.default_rng([self.seed, 7])
        self.perm = rng.permutation(int(vocab_size)).astype(np.int32)
        w = 1.0 / np.power(np.arange(1, vocab_size + 1.0), float(mix["zipf"]))
        self.cdf = np.cumsum(w / w.sum())

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng([self.seed, 8, int(i)])
        rank = np.minimum(np.searchsorted(self.cdf, rng.random(self.seq + 1)),
                          len(self.perm) - 1)
        toks = self.perm[rank]
        return toks[:-1], toks[1:]


# -- reduction of what the client saw ------------------------------------------

def percentile(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None
