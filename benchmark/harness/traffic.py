"""The one general traffic generator. A mix is a data file of parameters
under ``benchmark/traffic/``; this reads it and makes the schedule. The
schedule is a fixed replay: lengths, gaps between arrivals and their order
are the mix's alone, the same for every seed, and ``--seed`` draws the
token ids (and the weights). The parent and the change of a later PR then
serve the same requests in the same order (common random numbers): the
windows hold a few tens of requests, and the order of so few alone moved
every latency statistic by 3-14 % on the chip while two runs of one order
agreed to 0.2 % (PERF.md). So a cell is judged on what a replay supports, a
rate over the whole window, and never on a tail of a handful of requests."""
from __future__ import annotations

import math
import statistics

import numpy as np

#: lengths and gaps are the stratified quantiles of their distribution over
#: a cycle of this many draws (a mix may state its own ``cycle``); each
#: cycle is newly permuted, by the replay's own generator
CYCLE = 16
#: the words every replay's order is drawn from
REPLAY = [71, 0]


def _quantiles(spec, n):
    """``n`` stratified quantiles of a length distribution, as whole
    numbers clipped to [min, max]."""
    qs = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        vals = lo + qs * (hi - lo)
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        vals = np.asarray([float(spec["median"])
                           * math.exp(float(spec["sigma"]) * nd.inv_cdf(q))
                           for q in qs])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def _stream(rng, values):
    """Endless draws: each cycle is the whole multiset, newly permuted."""
    while True:
        for i in rng.permutation(len(values)):
            yield values[i]


def _words(seed):
    return [int(seed) & 0xFFFFFFFF, int(seed) >> 32]


class Request:
    __slots__ = ("index", "due", "prompt", "max_new", "client")

    def __init__(self, index, due, prompt, max_new, client=None):
        self.index, self.due = index, due
        self.prompt, self.max_new, self.client = prompt, max_new, client


class Lengths:
    """The stream of (prompt ids, output budget) of one mix: the lengths
    replayed, the ids from the seed. ``stream`` tells apart the streams of
    one run (the window's, the warm-up's)."""

    def __init__(self, mix, seed, vocab, stream=0):
        rng = np.random.default_rng(REPLAY + [int(stream), 1])
        self._tok = np.random.default_rng(_words(seed) + [int(stream), 2])
        n = int(mix.get("cycle", CYCLE))
        self._p = _stream(rng, _quantiles(mix["prompt"], n))
        self._o = _stream(rng, _quantiles(mix["output"], n))
        self._vocab = int(vocab)
        self._n = 0

    def next(self, due, client=None):
        n_p, n_o = int(next(self._p)), int(next(self._o))
        prompt = self._tok.integers(1, self._vocab, size=n_p) \
            .astype(np.int32)
        self._n += 1
        return Request(self._n - 1, due, prompt, n_o, client)


def open_schedule(mix, seed, vocab, warm_s, seconds):
    """Open loop: arrivals at the mix's fixed rate, due from 0 to
    ``warm_s + seconds``. The gaps are the stratified quantiles of the
    exponential distribution over a cycle of n arrivals, permuted, so a
    cycle takes exactly n / rate seconds. The cycles start where the
    window opens, so a window of a whole number of cycles holds whole
    multisets of lengths and of gaps. The warm-up's arrivals run backwards
    from there, from streams of their own."""
    rate = float(mix["rate_rps"])
    n = int(mix.get("cycle", CYCLE))
    qs = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-qs)
    gaps = gaps / gaps.mean() / rate
    shift = float(gaps.min()) / 2     # keeps a cycle's last arrival inside
    out = []
    lengths = Lengths(mix, seed, vocab, stream=1)
    t = float(warm_s)
    for gap in _stream(np.random.default_rng(REPLAY + [4]), gaps):
        t -= float(gap)
        if t <= 0:
            break
        out.append(lengths.next(t))
    out.reverse()
    lengths = Lengths(mix, seed, vocab, stream=0)
    t = float(warm_s) - shift
    for gap in _stream(np.random.default_rng(REPLAY + [3]), gaps):
        t += float(gap)
        if t >= warm_s + seconds:
            break
        out.append(lengths.next(t))
    for i, req in enumerate(out):
        req.index = i
    return out
