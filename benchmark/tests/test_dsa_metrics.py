"""The five metrics of the learned-sparse and windowed layers on made-up
runs: known seconds, counters and span ids give the known numbers, the
least works are floors, and a program without the scopes, the counters or
the span ids (the parent of the PR that brought them) gives None and
raises nothing."""
from types import SimpleNamespace

import pytest

from benchmark.harness import loader
from benchmark.harness.trace import TraceError

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CONFIG = loader.data("configs", "dots3-note-prev-ep8-d5")
CELL = "dots3_long_answers"
NAMES = ("dsa_index_roofline", "dsa_attend_roofline",
         "dsa_select_device_ms.batch", "window_append_roofline",
         "dsa_context_read_pct.batch")


class Table:
    """A component table whose step programs hold ``leaves`` {leaf:
    seconds} under the mixer, beside an expert layer."""

    def __init__(self, leaves, programs=100):
        rows = {("mixer.other" if leaf != "pt.sparse" else "mixer.core",
                 leaf, False): [secs, 7, {}] for leaf, secs in leaves.items()}
        rows[("ffn.experts", "pt.experts", False)] = [1.0, 3, {}]
        self.kinds = {"jit_fused_step": SimpleNamespace(rows=rows)}
        self.n = programs

    def step_kinds(self):
        return self.kinds

    def programs(self):
        return self.n


class Emits:
    def __init__(self, ids):
        self.ids = ids

    def named(self, prefix):
        assert prefix == "pt:engine.emit"
        return [SimpleNamespace(ids=i) for i in self.ids]


class Trace:
    def __init__(self, events):
        self.events = events

    def op_seconds(self, pattern, line=None):
        if pattern not in self.events:
            raise TraceError(f"no event matching {pattern!r}")
        return self.events[pattern]


def ctx(config=CONFIG, **kw):
    base = {"cell": SimpleNamespace(config=config), "peaks": PEAKS,
            "chips": 1}
    base.update(kw)
    return base


def _least(pairs):
    return sum(max(f / PEAKS["flops_per_s"], b / PEAKS["bytes_per_s"])
               for f, b in pairs)


#: a mixed step of one 512-row chunk at 24,576 and 7 decode rows at
#: 20,000, in 2 full and 3 window layers; and an all-decode iteration
MIXED = {"step_id": 1,
         "indexed_rows": 2 * 519,
         "scored_keys": 2 * (512 * 24576 + 512 * 513 // 2 + 7 * 20001),
         "selected_keys": 2 * 519 * 2048,
         "window_keys": 3 * 519 * 513}
SCAN = {"step_id": 2, "indexed_rows": 2 * 8, "scored_keys": 2 * 8 * 20001,
        "selected_keys": 2 * 8 * 2048, "window_keys": 3 * 8 * 513}


def test_the_least_works_are_floors():
    idx = loader.module("kernels", "dsa_index")
    att = loader.module("kernels", "dsa_attend")
    win = loader.module("kernels", "window_latent_append")
    # the index scores of the mixed step: 16,384 flops a pair bind
    f, b = idx.least(MIXED["scored_keys"], MIXED["indexed_rows"], 64, 128,
                     512)
    assert f == 2.0 * 64 * 128 * MIXED["scored_keys"]
    assert f / PEAKS["flops_per_s"] == pytest.approx(2.13e-3, rel=0.02)
    assert b / PEAKS["bytes_per_s"] < 0.05 * f / PEAKS["flops_per_s"]
    # the keys read are counted from below: a chunk's 24,576 of history
    # is at least its pairs over 512
    assert (b / 2 - MIXED["indexed_rows"] * 64 * 128) / 128 <= \
        2 * (24576 + 512 + 7 * 20001)
    # gather-and-attend: a selected latent is 1,152 B a row, which binds
    f, b = att.least(MIXED["selected_keys"], MIXED["indexed_rows"], 128,
                     576, 512)
    assert f == 2.0 * 128 * (576 + 512) * MIXED["selected_keys"]
    assert b >= 1152.0 * MIXED["selected_keys"]
    assert b / PEAKS["bytes_per_s"] > f / PEAKS["flops_per_s"]
    assert b / PEAKS["bytes_per_s"] == pytest.approx(3.34e-3, rel=0.02)
    # the window layers: 513 positions a row, not the context
    f, b = win.least(MIXED["window_keys"], 3 * 519, 64, 1088, 1024, 512)
    assert f == 2.0 * 64 * 2112 * 3 * 519 * 513
    assert f / PEAKS["flops_per_s"] == pytest.approx(1.1e-3, rel=0.02)


def test_known_seconds_and_ids_give_the_known_shares():
    emits = Emits([MIXED, SCAN] * 10)
    # the scores' time is the kernel's row AND the scope's own (what a
    # plain form leaves there), not the projections inside the scope
    table = Table({"dsa_index_scores": 0.110, "pt.index": 0.010,
                   "dsa_sparse_attend": 0.170, "pt.sparse": 0.030,
                   "pt.select": 0.5, "index_proj": 9.0})
    idx = loader.module("kernels", "dsa_index")
    att = loader.module("kernels", "dsa_attend")
    run = dict(trace=object(), inside=emits, components=table)
    got = loader.module("metrics", "dsa_index_roofline").read(ctx(**run))
    want = 10 * sum(_least([idx.least(i["scored_keys"], i["indexed_rows"],
                                      64, 128, 512)]) for i in (MIXED, SCAN))
    assert got == pytest.approx(100 * want / 0.120)
    assert 0 < got < 100
    plain = Table({"pt.index": 0.120})              # no kernel: plain XLA
    assert loader.module("metrics", "dsa_index_roofline").read(
        ctx(**dict(run, components=plain))) == pytest.approx(got)
    got = loader.module("metrics", "dsa_attend_roofline").read(ctx(**run))
    want = 10 * sum(_least([att.least(i["selected_keys"], i["indexed_rows"],
                                      128, 576, 512)]) for i in (MIXED, SCAN))
    assert got == pytest.approx(100 * want / 0.200)
    assert 0 < got < 100
    plain = Table({"pt.sparse": 0.200})             # the plain gather
    assert loader.module("metrics", "dsa_attend_roofline").read(
        ctx(**dict(run, components=plain))) == pytest.approx(got)
    # the top-k: ms a step program, whatever the ids say
    sel = loader.module("metrics", "dsa_select_device_ms.batch").read
    assert sel(ctx(**run)) == pytest.approx(1e3 * 0.5 / 100)


def test_the_window_roofline_reads_the_kernels_time_and_the_window_ids():
    read = loader.module("metrics", "window_append_roofline").read
    win = loader.module("kernels", "window_latent_append")
    emits = Emits([MIXED] * 20)
    tr = Trace({"latent_attention_append": (0.090, 60)})
    got = read(ctx(trace=tr, inside=emits))
    want = 20 * _least([win.least(MIXED["window_keys"], 3 * 519, 64, 1088,
                                  1024, 512)])
    assert got == pytest.approx(100 * want / 0.090)
    assert 0 < got < 100
    mod = loader.module("metrics", "window_append_roofline")
    assert mod.layer_counts(CONFIG) == (2, 3)
    assert mod.layer_counts(dict(CONFIG, num_hidden_layers=46)) == (13, 33)
    assert mod.layer_counts({"num_hidden_layers": 4}) is None


def test_the_context_read_share_is_the_counters_ratio():
    read = loader.module("metrics", "dsa_context_read_pct.batch").read
    s0 = {"dsa_keys_selected": 100, "dsa_keys_scored": 1000}
    s1 = {"dsa_keys_selected": 100 + 2048 * 50,
          "dsa_keys_scored": 1000 + 24576 * 50}
    assert read(ctx(stats0=s0, stats1=s1)) == pytest.approx(100 * 2048 / 24576)
    assert read(ctx(stats0=s0, stats1=s0)) is None
    assert read(ctx(stats0={"steps": 1}, stats1={"steps": 9})) is None
    assert read(ctx()) is None


def test_nothing_to_read_gives_none():
    emits = Emits([MIXED] * 4)
    table = Table({"pt.index": 0.1, "pt.sparse": 0.1, "pt.select": 0.1})
    bare = Table({})                    # a program from before the scopes
    old = Emits([{"step_id": 1, "held_rows": 5}])   # ... and before the ids
    tr = Trace({"latent_attention_append": (0.09, 60)})
    for name in ("dsa_index_roofline", "dsa_attend_roofline"):
        read = loader.module("metrics", name).read
        assert read(ctx(inside=emits, components=table)) is None  # no trace
        assert read(ctx(trace=object(), inside=None,
                        components=table)) is None
        assert read(ctx(trace=object(), inside=old,
                        components=table)) is None
        assert read(ctx(trace=object(), inside=emits,
                        components=None)) is None
        assert read(ctx(trace=object(), inside=emits,
                        components=bare)) is None
    sel = loader.module("metrics", "dsa_select_device_ms.batch").read
    assert sel(ctx(components=None)) is None
    assert sel(ctx(components=bare)) is None
    win = loader.module("metrics", "window_append_roofline").read
    assert win(ctx(inside=emits)) is None                        # no trace
    assert win(ctx(trace=Trace({}), inside=emits)) is None   # no such kernel
    assert win(ctx(trace=tr, inside=old)) is None
    assert win(ctx(trace=tr, inside=None)) is None
    no_types = {k: v for k, v in CONFIG.items() if k != "layer_types"}
    assert win(ctx(config=no_types, trace=tr, inside=emits)) is None
    assert win(ctx(config=dict(CONFIG, num_hidden_layers=2), trace=tr,
                   inside=emits)) is None           # no window layer inside


def test_the_readers_agree_with_benchmark_json():
    listed = {m["name"]: m for m in loader.benchmark_json()["per_layer"]}
    for name in NAMES:
        mod, entry = loader.module("metrics", name), listed[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER, mod.SOURCE) == (
            entry["unit"], entry["layer"], entry["moves"], entry["better"],
            entry["source"])
        assert entry["workloads"] == [CELL]
    # the cell reports what it lists, and not the other latent cells'
    # rooflines, whose counts are another model's
    declared = loader.Cell(CELL).declared(True)
    assert set(NAMES) <= set(declared)
    assert "mla_append_roofline" not in declared
    assert "latent_append_roofline" not in declared
    assert {"expert_matmul_roofline", "routed_rows_held_pct.batch",
            "kv_grid_live_pct.batch"} <= set(declared)
