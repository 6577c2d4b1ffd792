"""The three metrics of the power-retention core on made-up runs: what each
reads, that the roofline's least work is a floor, and that a program
without the counters, the component table or the span ids (the parent of the
PR that brought them) gives None and raises nothing."""
from types import SimpleNamespace

import pytest

from benchmark.harness import loader

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CONFIG = {"num_attention_heads": 40, "num_key_value_heads": 8,
          "head_dim": 128}
STATE = 34_080_768


class Table:
    """A component table that holds ``secs`` of ``mixer.core``."""

    def __init__(self, secs, programs=100):
        self.secs, self.n = secs, programs

    def seconds(self, pick):
        return sum(s for comp, s in (("mixer.core", self.secs),
                                     ("ffn", 1.0)) if pick(comp, False))

    def programs(self):
        return self.n


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


def test_the_counters_shares():
    live = loader.module("metrics", "retention_state_live_pct.batch").read
    rows = loader.module("metrics", "retention_chunk_rows_pct.batch").read
    s0 = {"ret_state_walked": 10, "ret_state_live": 10,
          "ret_rows_chunk": 0, "ret_rows_step": 10}
    s1 = {"ret_state_walked": 10 + 128 + 16, "ret_state_live": 10 + 96 + 16,
          "ret_rows_chunk": 8 * 512, "ret_rows_step": 10 + 8 * 27}
    assert live(ctx(stats0=s0, stats1=s1)) == pytest.approx(100 * 112 / 144)
    assert rows(ctx(stats0=s0, stats1=s1)) == pytest.approx(
        100 * 4096 / (4096 + 216))
    for read in (live, rows):
        assert read(ctx(stats0={"steps": 1}, stats1={"steps": 9})) is None
        assert read(ctx(stats0=s0, stats1=s0)) is None
        assert read(ctx()) is None


def test_the_least_work_is_a_floor():
    k = loader.module("kernels", "power_retention")
    assert k.feature_dim(128) == 8256
    assert k.state_bytes(8, 128, 128) == STATE
    # an all-decode iteration of 16 live slots in 8 layers: every state
    # once in and once out, 8.72 GB, 10.7 ms at the chip's bandwidth
    flops, nbytes = k.least(16 * 8, 16 * 8, 40, 8, 128, 128)
    assert nbytes == pytest.approx(2 * 128 * STATE, rel=1e-3)
    assert nbytes / PEAKS["bytes_per_s"] == pytest.approx(10.65e-3, rel=0.01)
    assert flops == 2 * 128 * 8256 * 129 * 48
    assert flops / PEAKS["flops_per_s"] < 0.1 * nbytes / PEAKS["bytes_per_s"]
    # a 512-row chunk in 8 layers: ONE state pass a layer (the chunk form
    # as written pays one a 64-row sub-chunk) and the rows' two products
    flops, nbytes = k.least(8, 512 * 8, 40, 8, 128, 128)
    assert nbytes < 2 * 8 * STATE * 1.2
    assert flops == pytest.approx(0.419e12, rel=0.01)


def test_the_roofline_reads_the_emits_ids_against_the_core_component():
    read = loader.module("metrics", "retention_core_roofline").read
    k = loader.module("kernels", "power_retention")
    scan = {"step_id": 1, "live_states": 4 * 128, "ret_rows": 4 * 128}
    mixed = {"step_id": 2, "live_states": 128, "ret_rows": 8 * 527}
    least = 0.0
    for ids in (scan, mixed):
        f, b = k.least(ids["live_states"], ids["ret_rows"], 40, 8, 128, 128)
        least += max(f / PEAKS["flops_per_s"], b / PEAKS["bytes_per_s"])
    got = read(ctx(trace=object(), inside=Emits([scan, mixed] * 20),
                   components=Table(20 * 0.150)))
    assert got == pytest.approx(100 * 20 * least / 3.0)
    assert 20 < got < 60
    # nothing to read: no trace, no ids on the emits, no table, no core
    assert read(ctx()) is None
    assert read(ctx(trace=object(), inside=Emits([{"step_id": 1}]),
                    components=Table(1.0))) is None
    assert read(ctx(trace=object(), inside=None,
                    components=Table(1.0))) is None
    assert read(ctx(trace=object(), inside=Emits([scan]),
                    components=None)) is None
    assert read(ctx(trace=object(), inside=Emits([scan]),
                    components=Table(0.0))) is None
