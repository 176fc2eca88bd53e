"""The one traffic generator.  A traffic mix is a data file under
``benchmark/traffic/`` (arrival process and rate, length distributions,
batch shape); this module turns such a file and a seed into the inputs a
cell runs.  The program under test sees only those inputs.

Steadiness across seeds: every seed gets the SAME schedule — the
distributions' quantiles at evenly spaced levels, in one fixed shuffled order
— and draws from the seed what the timing does not depend on: the token ids
of every prompt (and, in the drivers, the weights that answer them).  Measured
on the chip in PR 23: with the order drawn afresh per seed, and then with one
cycle entered at a point the seed picked, ``serve_tokens_per_s`` differed by
3 % and ``tpot_p95_ms`` by 6 % BETWEEN seeds (which requests straddle the end
of the window, which meet at the seam) while two runs of ONE seed agreed
within 0.5 %: the seed was changing the work.  So a serve cell replays ONE
fixed trace of arrivals and lengths, and ``--seed`` varies what is sent and
what answers, not when or how much.
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
SET_SEED = 20260927  # fixes the one shuffled order every seed replays


def load(name: str) -> dict:
    with open(os.path.join(_HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generators per purpose from one ``--seed`` (any size)."""
    return np.random.default_rng([int(seed), sum(map(ord, stream))])


def quantile_set(dist: dict, n: int) -> np.ndarray:
    """``n`` values at the levels (i + 0.5) / n of ``dist``: the same set
    for every seed.  ``dist`` is ``{"dist": name, ...parameters}``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "exponential":
        x = -np.log1p(-u) * dist["mean"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist and kind != "uniform":
        x = np.maximum(x, dist["min"])
    if "max" in dist and kind != "uniform":
        x = np.minimum(x, dist["max"])
    return x


def int_lengths(dist: dict, n: int) -> np.ndarray:
    return np.clip(np.rint(quantile_set(dist, n)).astype(np.int64),
                   int(dist.get("min", 1)), int(dist.get("max", 1 << 30)))


def _fixed_order(traffic: dict, n: int, span: float):
    """``n`` (gap, prompt length, output length) triples filling ``span``
    seconds: the distributions' quantile sets in one fixed shuffled order."""
    if traffic["arrivals"]["process"] != "poisson":
        raise ValueError("the only arrival process so far is 'poisson'")
    gaps = quantile_set({"dist": "exponential", "mean": 1.0}, n)
    gaps = gaps * (span / gaps.sum())  # the set offers exactly n in span
    columns = [gaps, int_lengths(traffic["prompt_len"], n),
               int_lengths(traffic["output_len"], n)]
    for stream, values in zip(("gaps", "prompt_len", "output_len"), columns):
        rng_for(SET_SEED, stream).shuffle(values)
    return columns


def serve_schedule(traffic: dict, seed: int, seconds: float,
                   vocab: int) -> dict:
    """An open-loop schedule: ``due`` times in seconds relative to the start
    of the measured window (negative ones are the pre-roll that brings the
    system to its steady state and is not judged), one prompt and one
    output length per request.  Times and lengths are the same for every
    seed; the prompts' token ids are the seed's."""
    rate = traffic["arrivals"]["rate_per_s"]
    preroll = float(traffic.get("preroll_s", 0.0))
    gaps, prompt_len, output_len = _fixed_order(
        traffic, max(1, int(round(rate * seconds))), float(seconds))
    due = np.cumsum(gaps) - gaps[0]
    n_pre = int(round(rate * preroll))
    if n_pre:
        g, p, o = _fixed_order(traffic, n_pre, preroll)
        due = np.concatenate([np.cumsum(g) - g[0] - preroll, due])
        prompt_len = np.concatenate([p, prompt_len])
        output_len = np.concatenate([o, output_len])
    tok = rng_for(seed, "tokens")
    prompts = [tok.integers(0, vocab, int(k)).tolist() for k in prompt_len]
    return {"due": due.tolist(), "prompts": prompts,
            "max_new": output_len.tolist()}


class HostBatches:
    """Training batches made on the host, a fresh one per step: ``T + 1``
    tokens per row from a Zipf law over the vocabulary (so that there is
    something to learn and the loss falls), split into inputs and next-token
    targets."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.batch = int(traffic["batch_size"])
        self.seq = int(traffic["seq_len"])
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        weights = ranks ** -float(traffic["tokens"]["zipf_exponent"])
        self._cdf = np.cumsum(weights / weights.sum())
        self._rng = rng_for(seed, "batches")
        # which token id has which rank is the seed's
        self._ids = rng_for(seed, "vocab").permutation(vocab).astype(np.int32)

    def next(self) -> dict:
        u = self._rng.random((self.batch, self.seq + 1))
        toks = self._ids[np.minimum(np.searchsorted(self._cdf, u),
                                    len(self._ids) - 1)]
        return {"inputs": np.ascontiguousarray(toks[:, :-1]),
                "targets": np.ascontiguousarray(toks[:, 1:])}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics; the one definition every metric here uses."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)
