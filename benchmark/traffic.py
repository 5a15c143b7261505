"""The one general traffic generator.  A traffic mix is a JSON file of
parameters under ``benchmark/traffic/``; this module turns one, plus a
seed, into the work a run offers.  A new mix is a new file, no code.

What the FILE fixes and the seed may not change: the multiset of
(prompt length, output length) pairs and the batch shapes.  What the
SEED decides: the order of the pairs, the token ids, which client gets
which request.  Two seeds therefore offer the same work in another
order, and a difference between two runs is the system's, not the
sample's.

Kinds: ``stream`` (training batches) and ``closed_loop`` (N clients,
each sending its next request when the last completes).  An open loop
(a fixed number of arrivals at sorted uniform instants) was built and
run in PR 24 and left out with its cell: PERF.md, Open questions.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, so that drawing more of one
    thing never shifts another.  ``seed`` may exceed 32 bits."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed), tag])


# ---------------------------------------------------------------------------
# lengths: evenly spaced quantiles of a stated distribution
# ---------------------------------------------------------------------------

def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: the quantiles at (i + 0.5) / n of ``spec``'s
    distribution, rounded and clipped to [min, max].  Deterministic: the
    seed has no part in which lengths exist."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def stride_pairing(n: int, stride: int) -> np.ndarray:
    """A fixed permutation of range(n) — ``i -> (i * stride) % n`` with
    ``stride`` coprime to ``n`` — that pairs the i-th quantile of one
    distribution with a far-away quantile of another.  Part of the file,
    not of the seed: the multiset of PAIRS is fixed."""
    if math.gcd(n, stride) != 1:
        raise ValueError(f"pair_stride {stride} is not coprime to {n}")
    return (np.arange(n) * stride) % n


# ---------------------------------------------------------------------------
# stream: training batches
# ---------------------------------------------------------------------------

def stream_batches(traffic: dict, cfg: dict, seed: int
                   ) -> List[Dict[str, np.ndarray]]:
    """``distinct_batches`` seeded BERT pretraining batches of the
    file's global batch and sequence length (the feed layout of
    ``models/bert.py::build_pretrain_network``): full-length sequences,
    ``num_masks`` masked positions each."""
    rng = rng_for(seed, "stream")
    b, s, m = traffic["global_batch"], traffic["seq_len"], \
        traffic["num_masks"]
    out = []
    for _ in range(traffic["distinct_batches"]):
        out.append({
            "src_ids": rng.integers(0, cfg["vocab_size"], (b, s),
                                    dtype=np.int64),
            "pos_ids": np.tile(np.arange(s, dtype=np.int64), (b, 1)),
            "sent_ids": rng.integers(0, cfg["type_vocab_size"], (b, s),
                                     dtype=np.int64),
            "input_mask": np.ones((b, s, 1), np.float32),
            "mask_label": rng.integers(0, cfg["vocab_size"], (b * m, 1),
                                       dtype=np.int64),
            "mask_pos": rng.integers(0, s, (b, m), dtype=np.int64),
            "labels": rng.integers(0, 2, (b, 1), dtype=np.int64),
        })
    return out


# ---------------------------------------------------------------------------
# serving requests
# ---------------------------------------------------------------------------

class Request:
    """One generation request: its prompt and how many tokens to ask
    for."""

    __slots__ = ("prompt", "max_new")

    def __init__(self, prompt, max_new):
        self.prompt = prompt
        self.max_new = int(max_new)


def closed_loop_requests(traffic: dict, cfg: dict, seed: int, count: int
                         ) -> List[Request]:
    """``count`` requests for a closed loop: whole seeded permutations of
    the file's fixed multiset of (prompt, output) pairs, one after the
    other; clients take them in this order as they come free."""
    n = traffic["pairs"]
    prompts, outputs = _closed_loop_pairs(traffic)
    rng = rng_for(seed, "order")
    tok = rng_for(seed, "tokens")
    out: List[Request] = []
    while len(out) < count:
        for i in rng.permutation(n):
            out.append(Request(
                tok.integers(0, cfg["vocab_size"], int(prompts[i]),
                             dtype=np.int64), outputs[i]))
    return out[:count]


def _closed_loop_pairs(traffic: dict):
    """The file's fixed (prompt, output) pairs: quantile i of the prompt
    lengths with quantile ``i * pair_stride % pairs`` of the outputs."""
    n = traffic["pairs"]
    return (quantile_lengths(traffic["prompt"], n),
            quantile_lengths(traffic["output"], n)[
                stride_pairing(n, traffic["pair_stride"])])


def closed_loop_multiset(traffic: dict) -> List[tuple]:
    prompts, outputs = _closed_loop_pairs(traffic)
    return sorted(zip(prompts.tolist(), outputs.tolist()))


def length_multiset(requests: List[Request]) -> List[tuple]:
    return sorted((int(r.prompt.size), r.max_new) for r in requests)
