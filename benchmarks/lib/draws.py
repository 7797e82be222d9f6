"""The one general generator: every traffic mix is parameters in a data file
that these functions read.

Steadiness comes from here.  The multiset of lengths of a run is the
distribution's evenly spaced quantiles, so ``--seed`` decides what meets what
and never how much work there is; every second of an open-loop window holds
the same number of arrivals (to rounding); the cohort that fills a backlog
cell's slots during set-up draws its remaining outputs from the residual-life
distribution, which is what a server met at a random moment of a long run
holds.  Token ids and orders come from ``numpy.random.default_rng(seed)``.
"""

import math
from statistics import NormalDist

import numpy as np


def quantile_fn(spec):
    """``u in (0, 1) -> value`` for a length distribution given as data:
    ``{"dist": "uniform", "min": a, "max": b}`` or
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    (clipped), or ``{"dist": "fixed", "value": v}``."""
    kind = spec["dist"]
    if kind == "fixed":
        return lambda u: float(spec["value"])
    lo, hi = float(spec["min"]), float(spec["max"])
    if kind == "uniform":
        return lambda u: lo + (hi - lo) * u
    if kind == "lognormal":
        med, sigma = float(spec["median"]), float(spec["sigma"])
        inv = NormalDist().inv_cdf
        return lambda u: min(hi, max(lo, med * math.exp(sigma * inv(u))))
    raise ValueError(f"unknown length distribution {kind!r}")


def quantiles(spec, n):
    """The ``n`` evenly spaced quantiles of ``spec``, as whole numbers >= 1,
    ascending: the same multiset whatever the seed."""
    q = quantile_fn(spec)
    return np.maximum(1, np.rint([q((i + 0.5) / n) for i in range(n)])).astype(np.int64)


def residual_quantiles(spec, n, grid=4096):
    """The ``n`` evenly spaced quantiles of the residual life of ``spec``:
    the remaining length of a request met at a random moment of a long run
    (length-biased choice, uniform age).  Its distribution function is
    ``G(r) = E[min(r, L)] / E[L]``, inverted here on whole numbers."""
    lens = quantiles(spec, grid).astype(np.float64)
    r = np.arange(0, int(lens.max()) + 1, dtype=np.float64)
    G = np.minimum(r[:, None], lens[None, :]).sum(1) / lens.sum()
    u = (np.arange(n) + 0.5) / n
    return np.maximum(1, np.ceil(np.interp(u, G, r))).astype(np.int64)


def spread_order(values, rng, block=16):
    """``values`` (ascending) in a seeded order in which every run of
    ``block`` consecutive items spans the whole range: item ``j`` of each
    block comes from the ``j``-th of ``block`` equal strata.  A server that
    gets through only part of a queue then still meets the distribution, not
    a lucky or unlucky sample of it."""
    values = np.asarray(values)
    n = len(values)
    nblocks = -(-n // block)
    # stratum s holds values[s*nblocks:(s+1)*nblocks]; each block takes one
    # item of each stratum, which one and in which place from the seed
    out = np.empty(nblocks * block, values.dtype)
    take = np.full(nblocks * block, False)
    for s in range(block):
        idx = np.arange(s * nblocks, (s + 1) * nblocks)
        ok = idx < n
        pick = rng.permutation(nblocks)
        out[pick * block + s] = values[np.minimum(idx, n - 1)]
        take[pick * block + s] = ok
    out, take = out.reshape(nblocks, block), take.reshape(nblocks, block)
    for b in range(nblocks):
        p = rng.permutation(block)
        out[b], take[b] = out[b][p], take[b][p]
    return out.reshape(-1)[take.reshape(-1)]


def lengths(spec, n, rng, block=16):
    return spread_order(quantiles(spec, n), rng, block)


def arrivals(rate, seconds, rng):
    """Due times in ``[0, seconds)`` at ``rate`` a second: second ``k`` holds
    ``floor(rate*(k+1)) - floor(rate*k)`` arrivals, placed inside it
    uniformly from ``rng``."""
    out = []
    for k in range(int(math.ceil(seconds))):
        c = int(math.floor(rate * (k + 1) + 1e-9) - math.floor(rate * k + 1e-9))
        out.extend(k + np.sort(rng.random(c)))
    return np.asarray([t for t in out if t < seconds])


def blocks(rate, block_seconds, n_blocks, prompt_spec, output_spec, rng):
    """``n_blocks`` stretches of traffic of ``block_seconds`` each, as
    ``[[(offset, prompt tokens, output tokens), ...], ...]``.  The lengths of
    ALL blocks together are the distributions' evenly spaced quantiles, dealt
    so that every block spans the whole range (one item of each of its
    strata); each block has the same number of arrivals every second."""
    per = arrivals(rate, block_seconds, rng)
    n = len(per) * n_blocks
    p = spread_order(quantiles(prompt_spec, n), rng, block=len(per))
    o = spread_order(quantiles(output_spec, n), rng, block=len(per))
    out = []
    for b in range(n_blocks):
        at = arrivals(rate, block_seconds, rng)
        sl = slice(b * len(per), (b + 1) * len(per))
        out.append(list(zip(at.tolist(), p[sl].tolist(), o[sl].tolist())))
    return out


def prompt_tokens(n, vocab, rng):
    return rng.integers(0, vocab, (int(n),)).astype(np.int32)


class ZipfBatches:
    """Next-token batches ``(inputs, labels)`` of shape ``[1, batch, seq]``,
    a new one each call.  Tokens are Zipf-distributed over a seeded
    permutation of the vocabulary (after ``chip_smoke.zipf_batches``): unlike
    uniform noise there is something to learn, so a falling loss means the
    step trains."""

    def __init__(self, seed, vocab, batch, seq):
        self.rng = np.random.default_rng(seed)
        p = 1.0 / np.arange(1, vocab + 1)
        self.cdf = np.cumsum(p / p.sum())
        self.perm = self.rng.permutation(vocab).astype(np.int32)
        self.shape = (1, batch, seq + 1)
        self.vocab = vocab

    def __call__(self):
        ranks = np.searchsorted(self.cdf, self.rng.random(self.shape))
        ids = self.perm[np.minimum(ranks, self.vocab - 1)]
        return ids[..., :-1], ids[..., 1:]
