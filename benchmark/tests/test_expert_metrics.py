"""The two metrics of the grouped expert product on made-up runs: what
each reads, and that a program without the counters or the span ids (the
parent of the PR that brought them) gives None and raises nothing."""
from types import SimpleNamespace

import pytest

from benchmark.harness import loader
from benchmark.harness.trace import TraceError

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CONFIG = {"num_experts": 64, "hidden_size": 2304,
          "moe_intermediate_size": 1024}


class Kernel:
    def __init__(self, secs, calls):
        self.secs, self.calls = secs, calls

    def op_seconds(self, pattern):
        if not self.calls:
            raise TraceError(f"no event matching {pattern!r}")
        return self.secs, self.calls


class Emits:
    def __init__(self, ids):
        self.ids = ids

    def named(self, prefix):
        assert prefix == "pt:engine.emit"
        return [SimpleNamespace(ids=i) for i in self.ids]


def ctx(**kw):
    base = {"cell": SimpleNamespace(config=CONFIG), "peaks": PEAKS,
            "chips": 1}
    base.update(kw)
    return base


def test_the_share_of_the_held_experts_that_got_a_row():
    read = loader.module("metrics", "expert_weights_read_pct.batch").read
    s0 = {"moe_experts_nonempty": 100, "moe_experts_held": 640}
    s1 = {"moe_experts_nonempty": 100 + 7 * 48, "moe_experts_held": 640 + 7 * 64}
    assert read(ctx(stats0=s0, stats1=s1)) == pytest.approx(75.0)
    assert read(ctx(stats0={"steps": 1}, stats1={"steps": 9})) is None
    assert read(ctx(stats0=s0, stats1=s0)) is None      # no expert layer ran


def test_the_roofline_reads_the_stretchs_own_steps():
    read = loader.module("metrics", "expert_matmul_roofline").read
    k = loader.module("kernels", "grouped_expert_matmul")
    # 10 steps of 7 expert layers: every expert non-empty, 526 rows a layer
    step = {"step_id": 1, "experts_read": 7 * 64, "experts_held": 7 * 64,
            "held_rows": 7 * 526}
    flops, nbytes = k.least(526, 64, 2304, 1024)
    least = max(flops / PEAKS["flops_per_s"], nbytes / PEAKS["bytes_per_s"])
    assert least == nbytes / PEAKS["bytes_per_s"]       # bandwidth binds
    # the kernel's calls speak for the layers (2 a layer), the emits for
    # what a layer held: 12 steps' calls against 10 emits' means
    got = read(ctx(trace=Kernel(12 * 7 * 2 * 0.0007, 12 * 7 * 2),
                   inside=Emits([step] * 10)))
    assert got == pytest.approx(100 * least / 0.0014)
    assert 75 < got < 100
    # a scan's steps read a few experts: the least work follows them
    scan = dict(step, experts_read=7 * 8, held_rows=7 * 12)
    less = read(ctx(trace=Kernel(12 * 7 * 2 * 0.0007, 12 * 7 * 2),
                    inside=Emits([step] * 5 + [scan] * 5)))
    assert less < 0.6 * got


def test_a_program_without_the_kernel_or_the_ids_gives_none():
    read = loader.module("metrics", "expert_matmul_roofline").read
    step = {"step_id": 1, "held_rows": 3000}            # the parent's emit
    assert read(ctx()) is None                          # no trace
    assert read(ctx(trace=Kernel(0.0, 0), inside=Emits([step]))) is None
    assert read(ctx(trace=Kernel(0.1, 14), inside=Emits([step]))) is None
    assert read(ctx(trace=Kernel(0.1, 14), inside=None)) is None
