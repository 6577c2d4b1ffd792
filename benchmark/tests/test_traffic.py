"""The generator: a fixed replay. Every seed gets the same lengths and
arrivals in the same order and draws only the token ids; a whole number of
cycles holds whole multisets; large seeds are fine."""
import copy

import pytest

from benchmark.harness import loader, traffic

SEEDS = (0, 7, 2 ** 31 + 12345, 2 ** 32 + 3)
#: no shipped cell runs an open loop yet: the chat mix PERF.md keeps for
#: later, as a later PR's data file would state it
OPEN = {"kind": "open_poisson", "rate_rps": 0.4, "cycle": 20, "warm_s": 8,
        "drain_s": 30,
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                   "min": 64, "max": 2048},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 16, "max": 512}}


def in_window(mix, seed, seconds):
    w0 = float(mix["warm_s"])
    return [r for r in traffic.open_schedule(mix, seed, 32768, w0, seconds)
            if w0 <= r.due < w0 + seconds]


def test_same_seed_same_inputs():
    a, b = (traffic.open_schedule(OPEN, 2 ** 31 + 5, 32768, 8, 50)
            for _ in range(2))
    assert [r.due for r in a] == [r.due for r in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_every_seed_replays_one_schedule_and_draws_its_own_ids():
    runs = [traffic.open_schedule(OPEN, s, 32768, 8, 50) for s in SEEDS]
    shape = [[(r.due, len(r.prompt), r.max_new) for r in run]
             for run in runs]
    assert all(s == shape[0] for s in shape)
    for other in runs[1:]:
        assert any((x.prompt != y.prompt).any()
                   for x, y in zip(runs[0], other))


def test_a_window_of_whole_cycles_holds_whole_multisets():
    seconds = 2 * OPEN["cycle"] / OPEN["rate_rps"]
    reqs = in_window(OPEN, 3, seconds)
    assert len(reqs) == 2 * OPEN["cycle"]
    assert [r.index for r in reqs] == sorted(r.index for r in reqs)
    one = sorted(traffic._quantiles(OPEN["prompt"], OPEN["cycle"]))
    assert sorted(len(r.prompt) for r in reqs) == sorted(one + one)
    # a cycle is permuted anew each time: the replay is no short loop
    first, second = reqs[:OPEN["cycle"]], reqs[OPEN["cycle"]:]
    assert [len(r.prompt) for r in first] != [len(r.prompt) for r in second]


def test_lengths_stay_inside_the_mix():
    for r in traffic.open_schedule(OPEN, 3, 32768, 8, 50):
        assert OPEN["prompt"]["min"] <= len(r.prompt) \
            <= OPEN["prompt"]["max"]
        assert OPEN["output"]["min"] <= r.max_new <= OPEN["output"]["max"]
        assert r.prompt.min() >= 1 and r.prompt.max() < 32768


def test_closed_loop_lengths_cycle_through_one_multiset():
    mix = copy.deepcopy(loader.Cell("doc_batch").traffic)
    n = int(mix.get("cycle", traffic.CYCLE))
    per_seed = []
    for seed in SEEDS:
        lengths = traffic.Lengths(mix, seed, 32768)
        per_seed.append([len(lengths.next(0.0).prompt) for _ in range(n)])
    assert all(p == per_seed[0] for p in per_seed)
    assert sorted(per_seed[0]) == sorted(
        traffic._quantiles(mix["prompt"], n))


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError, match="distribution"):
        traffic._quantiles({"dist": "zipf", "min": 1, "max": 2}, 4)
