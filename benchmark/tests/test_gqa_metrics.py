"""The two metrics of the paged K/V kernels in a model whose K/V layers
are the few (``gqa_append_roofline``, ``gqa_core_device_ms.batch``) on
made-up runs: known seconds and chunks give the known share, the layer
count follows the depth, and a configuration without ``gqa_layers``, a
trace without the kernels or a run without a stretch gives None and
raises nothing."""
from types import SimpleNamespace

import pytest

from benchmark.harness import loader
from benchmark.harness.readers import SERVE_MODULES
from benchmark.harness.trace import MODULES_LINE, TraceError

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CONFIG = {"num_attention_heads": 64, "num_key_value_heads": 8,
          "head_dim": 128, "hidden_size": 4096, "num_hidden_layers": 4,
          "gqa_layers": [0, 4, 8, 12], "engine": {"chunk_size": 512}}


class Trace:
    """``events``: {pattern: (seconds, count)}; any other pattern has no
    event inside the window."""

    def __init__(self, events):
        self.events = events

    def op_seconds(self, pattern, line=None):
        if pattern == SERVE_MODULES:
            assert line == MODULES_LINE
        if pattern not in self.events:
            raise TraceError(f"no event matching {pattern!r}")
        return self.events[pattern]


def record(rid, n_prompt, t_first=None):
    return SimpleNamespace(handle=SimpleNamespace(request_id=rid),
                           n_prompt=n_prompt, t_first=t_first)


def stretch(snap0, snap1, t0=10.0, t1=14.0):
    return SimpleNamespace(snap0=snap0, snap1=snap1, t0=t0, t1=t1)


def ctx(config=CONFIG, **kw):
    base = {"cell": SimpleNamespace(config=config), "peaks": PEAKS,
            "chips": 1, "records": []}
    base.update(kw)
    return base


def test_known_seconds_and_chunks_give_the_known_share():
    read = loader.module("metrics", "gqa_append_roofline").read
    k = loader.module("kernels", "paged_attention_append")
    # one request of 20,000 tokens went from position 8,192 to 9,216
    # inside the stretch: two chunks of 512, in the ONE K/V layer
    pairs = [k.least(8192, 8704, 64, 8, 128, 1),
             k.least(8704, 9216, 64, 8, 128, 1)]
    least = sum(max(f / PEAKS["flops_per_s"], b / PEAKS["bytes_per_s"])
                for f, b in pairs)
    got = read(ctx(trace=Trace({"paged_attention_append": (0.020, 2)}),
                   stretch=stretch({7: 8192}, {7: 9216}),
                   records=[record(7, 20000)]))
    assert got == pytest.approx(100 * least / 0.020)
    # the flops bind at this context: 4 x 64 x 128 x pairs over the peak
    flops = sum(f for f, _ in pairs)
    assert flops == 4.0 * 64 * 128 * (512 * (8192 + 8704 + 1) / 2
                                      + 512 * (8704 + 9216 + 1) / 2)
    assert least == pytest.approx(flops / PEAKS["flops_per_s"])
    assert 0 < got < 100
    # a request that finished its prompt before the stretch, and one
    # admitted after it, add no chunk
    same = read(ctx(trace=Trace({"paged_attention_append": (0.020, 2)}),
                    stretch=stretch({7: 8192}, {7: 9216}),
                    records=[record(7, 20000), record(8, 300, t_first=9.0),
                             record(9, 400)]))
    assert same == pytest.approx(got)
    # a prompt whose prefill began and ended inside the stretch: whole
    whole = read(ctx(trace=Trace({"paged_attention_append": (0.020, 2)}),
                     stretch=stretch({}, {}),
                     records=[record(5, 700, t_first=12.0)]))
    p = [k.least(0, 512, 64, 8, 128, 1), k.least(512, 700, 64, 8, 128, 1)]
    assert whole == pytest.approx(100 * sum(
        max(f / PEAKS["flops_per_s"], b / PEAKS["bytes_per_s"])
        for f, b in p) / 0.020)


@pytest.mark.parametrize("depth,layers", [(4, 1), (8, 2), (48, 12), (1, 1)])
def test_the_layer_count_follows_the_depth(depth, layers):
    mod = loader.module("metrics", "gqa_append_roofline")
    config = dict(CONFIG, num_hidden_layers=depth,
                  gqa_layers=list(range(0, 48, 4)))
    assert mod.gqa_depth(config) == layers
    one = mod.read(ctx(config=dict(config, num_hidden_layers=4),
                       trace=Trace({"paged_attention_append": (0.5, 9)}),
                       stretch=stretch({1: 0}, {1: 512}),
                       records=[record(1, 4000)]))
    got = mod.read(ctx(config=config,
                       trace=Trace({"paged_attention_append": (0.5, 9)}),
                       stretch=stretch({1: 0}, {1: 512}),
                       records=[record(1, 4000)]))
    assert got == pytest.approx(layers * one)


def test_nothing_to_read_gives_none():
    read = loader.module("metrics", "gqa_append_roofline").read
    tr = Trace({"paged_attention_append": (0.020, 2)})
    st = stretch({7: 8192}, {7: 9216})
    recs = [record(7, 20000)]
    no_key = {k: v for k, v in CONFIG.items() if k != "gqa_layers"}
    assert read(ctx(config=no_key, trace=tr, stretch=st,
                    records=recs)) is None          # every layer is K/V
    assert read(ctx(config=dict(CONFIG, gqa_layers=[4, 8]), trace=tr,
                    stretch=st, records=recs)) is None   # none in the depth
    assert read(ctx(stretch=st, records=recs)) is None           # no trace
    assert read(ctx(trace=tr, records=recs)) is None             # no stretch
    assert read(ctx(trace=tr, stretch=stretch(None, None),
                    records=recs)) is None          # the probe saw nothing
    assert read(ctx(trace=Trace({}), stretch=st,
                    records=recs)) is None          # no such kernel
    assert read(ctx(trace=tr, stretch=stretch({7: 9216}, {7: 9216}),
                    records=recs)) is None          # no chunk prefilled


def test_the_core_ms_a_step_program_adds_both_kernels():
    read = loader.module("metrics", "gqa_core_device_ms.batch").read
    # 100 step programs: 90 mixed steps' appends and 10 scans' decodes
    tr = Trace({SERVE_MODULES: (3.0, 100),
                "paged_attention_append": (0.90, 90),
                "paged_attention_decode": (0.04, 40)})
    assert read(ctx(trace=tr)) == pytest.approx(1e3 * 0.94 / 100)
    # a stretch that never left prefill has no decode kernel, and back
    only = Trace({SERVE_MODULES: (3.0, 100),
                  "paged_attention_append": (0.90, 90)})
    assert read(ctx(trace=only)) == pytest.approx(9.0)
    scans = Trace({SERVE_MODULES: (1.0, 50),
                   "paged_attention_decode": (0.05, 200)})
    assert read(ctx(trace=scans)) == pytest.approx(1.0)


def test_the_core_ms_gives_none_where_there_is_nothing_to_read():
    read = loader.module("metrics", "gqa_core_device_ms.batch").read
    tr = Trace({SERVE_MODULES: (3.0, 100),
                "paged_attention_append": (0.90, 90)})
    no_key = {k: v for k, v in CONFIG.items() if k != "gqa_layers"}
    assert read(ctx()) is None                                  # no trace
    assert read(ctx(config=no_key, trace=tr)) is None
    assert read(ctx(trace=Trace({SERVE_MODULES: (3.0, 100)}))) is None
    assert read(ctx(trace=Trace(
        {"paged_attention_append": (0.9, 90)}))) is None   # no step program


def test_both_readers_agree_with_benchmark_json():
    listed = {m["name"]: m for m in loader.benchmark_json()["per_layer"]}
    for name in ("gqa_append_roofline", "gqa_core_device_ms.batch"):
        mod, entry = loader.module("metrics", name), listed[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER, mod.SOURCE) == (
            entry["unit"], entry["layer"], entry["moves"], entry["better"],
            entry["source"])
        assert entry["workloads"] == ["solar_long_reports"]
