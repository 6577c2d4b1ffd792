"""The two metrics of the KDA kernel on made-up runs: what each reads, and
that a program without the counters, the kernel or the span ids (the
parent of the PR that brought them) gives None and raises nothing."""
from types import SimpleNamespace

import pytest

from benchmark.harness import loader
from benchmark.harness.trace import TraceError

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CONFIG = {"linear_attn_config": {"num_heads": 32, "head_dim": 128},
          "engine": {"chunk_size": 256}}
#: a mixed step of kimi_long_docs: a chunk of 256 rows, 7 decode rows
MIXED = {"step_id": 1, "kind": 1, "prefill_rows": 256, "decode_rows": 7}


class Kernel:
    def __init__(self, secs, calls):
        self.secs, self.calls = secs, calls

    def op_seconds(self, pattern):
        assert pattern == "kda_chunk_walk"
        if not self.calls:
            raise TraceError(f"no event matching {pattern!r}")
        return self.secs, self.calls


class Dispatches:
    def __init__(self, ids):
        self.ids = ids

    def named(self, prefix):
        assert prefix == "pt:engine.dispatch"
        return [SimpleNamespace(ids=i) for i in self.ids]


def ctx(config=CONFIG, **kw):
    base = {"cell": SimpleNamespace(config=config), "peaks": PEAKS,
            "chips": 1}
    base.update(kw)
    return base


def test_the_share_of_the_grid_that_held_a_live_row():
    read = loader.module("metrics", "kda_grid_live_pct.batch").read
    s0 = {"kda_grid_steps": 320, "kda_grid_live": 100}
    s1 = {"kda_grid_steps": 320 + 10 * 6 * 32,
          "kda_grid_live": 100 + 10 * 6 * 11}
    assert read(ctx(stats0=s0, stats1=s1)) == pytest.approx(100 * 11 / 32)
    assert read(ctx(stats0={"steps": 1}, stats1={"steps": 9})) is None
    assert read(ctx(stats0=s0, stats1=s0)) is None      # no mixed step ran
    assert read(ctx()) is None


def test_the_least_counts_every_live_row_and_each_slots_state_twice():
    k = loader.module("kernels", "kda_chunk")
    flops, nbytes = k.least(263, 8, 32, 128, 128)
    assert flops == 2 * 3 * 263 * 32 * 128 * 128
    rows = 263 * 32 * (3 * 128 + 2 * 128 + 1) * 4
    states = 2 * 8 * 32 * 128 * 128 * 4
    assert nbytes == rows + states == 21_578_624 + 33_554_432
    # the bytes bind: 0.067 ms a layer at the chip's bandwidth
    assert nbytes / PEAKS["bytes_per_s"] > flops / PEAKS["flops_per_s"]
    assert nbytes / PEAKS["bytes_per_s"] == pytest.approx(67.3e-6, rel=0.01)


def test_the_roofline_reads_the_stretchs_own_mixed_steps():
    read = loader.module("metrics", "kda_chunk_roofline").read
    k = loader.module("kernels", "kda_chunk")
    _, nbytes = k.least(263, 8, 32, 128, 128)
    least = nbytes / PEAKS["bytes_per_s"]
    # 68 steps' calls of 6 layers at 1 ms, against 66 dispatch spans
    got = read(ctx(trace=Kernel(68 * 6 * 0.001, 68 * 6),
                   inside=Dispatches([MIXED] * 66)))
    assert got == pytest.approx(100 * least / 0.001)
    assert 5 < got < 10
    # an all-decode scan's dispatch (kind 0) runs no kernel: left out
    scan = dict(MIXED, kind=0, prefill_rows=0, decode_rows=8)
    assert read(ctx(trace=Kernel(68 * 6 * 0.001, 68 * 6),
                    inside=Dispatches([MIXED] * 40 + [scan] * 26))) \
        == pytest.approx(got)
    # a step without a prompt chunk: 8 rows in 8 slots, less to do
    thin = dict(MIXED, prefill_rows=0, decode_rows=8)
    less = read(ctx(trace=Kernel(68 * 6 * 0.001, 68 * 6),
                    inside=Dispatches([MIXED] * 33 + [thin] * 33)))
    assert 0.75 * got < less < got
    # a chunk and a half of prompt rows lie in two slots at least
    two = dict(MIXED, prefill_rows=384, decode_rows=6)
    more = read(ctx(trace=Kernel(6 * 0.001, 6), inside=Dispatches([two])))
    f, b = k.least(390, 8, 32, 128, 128)
    assert more == pytest.approx(100 * b / PEAKS["bytes_per_s"] / 0.001)


def test_a_program_without_the_kernel_or_the_ids_gives_none():
    read = loader.module("metrics", "kda_chunk_roofline").read
    old = {"step_id": 1, "kind": 1, "rows": 272}        # before PR 37
    assert read(ctx()) is None                          # no trace
    assert read(ctx(trace=Kernel(0.0, 0),
                    inside=Dispatches([MIXED]))) is None
    assert read(ctx(trace=Kernel(0.1, 6), inside=Dispatches([old]))) is None
    assert read(ctx(trace=Kernel(0.1, 6), inside=None)) is None
    # a configuration without recurrent layers has no such kernel
    assert read(ctx(config={"engine": {"chunk_size": 256}},
                    trace=Kernel(0.1, 6),
                    inside=Dispatches([MIXED]))) is None
