"""The arithmetic of ``benchmark/harness/inside.py`` on a small profile
written out by hand (as ``test_trace.py`` does): a span's own time, and the
chip's idle time split into what lies under a host work span, under a wait
span and under none. Then every reader of the module on each cell's CPU
rehearsal, with the cells' lists extended in memory."""
import json
import time

import pytest
from jax.profiler import ProfileData

from benchmark.harness import common, inside, loader, main, output
from benchmark.harness import trace as T
from benchmark.tests import toy
from benchmark.tests.test_trace import NS, plane

#: window 1000..5000 ns; the chip idles in [2000,2300), [3000,3400) and
#: [4200,4600)
OPS = [("%fusion.1 = f32[8] fusion(%p)", 1000, 1000),
       ("%fusion.2 = f32[8] fusion(%p)", 2300, 700),
       ("%fusion.3 = f32[8] fusion(%p)", 3400, 800),
       ("%fusion.4 = f32[8] fusion(%p)", 4600, 600)]
SERVE = [
    ("pt:server.pass", 900, 2600, {}),
    ("pt:server.sweep", 900, 1000, {}),
    ("pt:server.begin", 1000, 1900, {}),
    ("pt:engine.schedule", 1000, 1100, {}),
    ("pt:engine.dispatch", 1100, 1800, {"step_id": 7, "rows": 2048}),
    ("pt:server.finish", 1900, 2600, {}),
    ("pt:engine.sync", 1900, 2400, {"step_id": 7}),   # over gap 1: a wait
    ("pt:engine.emit", 2400, 2500, {"step_id": 7}),
    ("pt:server.pass", 2600, 4000, {}),
    ("pt:server.admit_queue", 2600, 2700, {}),
    ("pt:server.begin", 2700, 3300, {}),              # over gap 2: work
    ("pt:engine.dispatch", 2800, 3250, {"step_id": 8, "rows": 32}),
    ("pt:server.idle", 3500, 3900, {}),
    # nothing over gap 3
    ("pt:server.pass", 4700, 5200, {}),               # straddles the end
    ("pt:server.sweep", 4700, 4800, {}),
]
TRAIN = [
    ("pt:train.step", 1900, 2500, {"step": 4}),       # over gap 1: work
    ("pt:train.prepare", 1900, 2100, {}),
    ("pt:train.dispatch", 2100, 2400, {}),
    ("pt:train.commit", 2400, 2500, {}),
    ("pt:train.step", 3300, 3700, {"step": 5}),       # over 100 of gap 2
    ("pt:train.step", 4900, 5300, {"step": 6}),       # straddles the end
]


def host(window, spans):
    """A host plane with one thread: the window and ``spans``
    [(name, start, end, {stat: int})]."""
    names, stats = {T.WINDOW: 1}, {}
    rows = [f"events {{ metadata_id: 1 offset_ps: {window[0] * NS} "
            f"duration_ps: {(window[1] - window[0]) * NS} }}"]
    for name, s, e, ids in spans:
        mid = names.setdefault(name, len(names) + 1)
        st = " ".join(
            f"stats {{ metadata_id: {stats.setdefault(k, len(stats) + 1)} "
            f"int64_value: {v} }}" for k, v in ids.items())
        rows.append(f"events {{ metadata_id: {mid} offset_ps: {s * NS} "
                    f"duration_ps: {(e - s) * NS} {st} }}")
    metas = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in names.items())
    smetas = "\n".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in stats.items())
    return (f'planes {{ id: 99 name: "/host:CPU"\n{metas}\n{smetas}\n'
            f'lines {{ id: 5 name: "python3" {" ".join(rows)} }} }}')


def load(spans):
    data = ProfileData.from_text_proto(
        plane(0, OPS, []) + "\n" + host((1000, 5000), spans))
    tr = T.Trace.from_profile(data)
    ins = inside.Inside(inside.read_lines(data),
                        [(s, e) for s, e, _ in tr.chips[0][T.OPS_LINE]],
                        tr.window)
    return {"trace": tr, "inside": ins}, ins


def test_spans_nest_and_carry_their_ids():
    _, ins = load(SERVE)
    by = {(s.name, s.start): s for s in ins.spans}
    d = by["pt:engine.dispatch", 1100]
    assert d.ids == {"step_id": 7, "rows": 2048}
    assert d.parent is by["pt:server.begin", 1000]
    assert d.parent.parent is by["pt:server.pass", 900]
    assert [c.name for c in by["pt:server.pass", 2600].children] == [
        "pt:server.admit_queue", "pt:server.begin", "pt:server.idle"]
    # a span's own time: its length inside the window less its children's
    assert by["pt:server.pass", 900].self_ns(1000, 5000) == 0
    assert by["pt:server.begin", 1000].self_ns(1000, 5000) == 100
    assert by["pt:server.pass", 2600].self_ns(1000, 5000) == 300
    assert by["pt:server.pass", 4700].self_ns(1000, 5000) == 200


def test_idle_time_is_split_by_what_the_host_was_doing():
    ctx, ins = load(SERVE)
    assert ins.gaps() == [(2000, 2300), (3000, 3400), (4200, 4600)]
    work, wait, none = ins.idle_split()
    # gap 1 under pt:engine.sync; gap 2 under the dispatch, then the
    # server's begin, then the pass itself; gap 3 under nothing
    assert (work, wait, none) == (pytest.approx(400e-9),
                                  pytest.approx(300e-9),
                                  pytest.approx(400e-9))
    assert inside.idle_by_program_pct(ctx) == pytest.approx(10.0)
    tr = ctx["trace"]
    assert inside.idle_by_program_pct(ctx) <= \
        100.0 * (1.0 - tr.busy_s() / tr.window_s)
    assert ins.longest_gaps() == [
        (pytest.approx(400e-9), "pt:engine.dispatch", 8),
        (pytest.approx(400e-9), None, None),
        (pytest.approx(300e-9), "pt:engine.sync", 7)]


def test_the_server_loops_own_time_a_step():
    ctx, _ = load(SERVE)
    # passes 0 + 300 + 200, begins 100 + 150, finish 100, admit_queue 100,
    # the sweep inside the window 100 (the one before it 0); idle left
    # out; over the two dispatch spans inside the window
    assert inside.server_self_ms_per_step(ctx) == pytest.approx(
        1050 / 2 / 1e6)
    assert inside.dispatch_host_ms(ctx) is None     # no pt:train.step


def test_a_train_steps_host_time_and_the_idle_under_it():
    ctx, ins = load(TRAIN)
    # the steps wholly inside the window: 600 and 400 ns
    assert inside.dispatch_host_ms(ctx) == pytest.approx(500 / 1e6)
    work, wait, none = ins.idle_split()
    assert (work, wait) == (pytest.approx(400e-9), 0.0)
    assert none == pytest.approx(700e-9)
    assert ins.longest_gaps(1) == [(pytest.approx(400e-9), None, None)]
    assert inside.server_self_ms_per_step(ctx) is None  # no dispatch span


def test_the_counter_readers_take_the_windows_deltas():
    s0 = {"prefill_tokens": 1000, "tokens_generated": 50,
          "rows_computed": 8000, "slot_wait_time_s": 2.0, "first_grants": 1,
          "kv_live_blocks": 100, "kv_grid_blocks": 500,
          "program_build_time_s": 3.5, "programs_built": 5}
    s1 = {"prefill_tokens": 1256, "tokens_generated": 57,
          "rows_computed": 8000 + 2048, "slot_wait_time_s": 14.0,
          "first_grants": 3, "kv_live_blocks": 400, "kv_grid_blocks": 1100,
          "program_build_time_s": 3.5, "programs_built": 5}
    ctx = {"stats0": s0, "stats1": s1}
    assert inside.row_occupancy_pct(ctx) == pytest.approx(100 * 263 / 2048)
    assert inside.slot_wait_ms(ctx) == pytest.approx(6000.0)
    assert inside.kv_grid_live_pct(ctx) == pytest.approx(50.0)
    assert inside.program_build_s(ctx) == 3.5


def test_a_program_without_spans_or_counters_gives_nothing(tmp_path):
    """The parent of the PR that brought them: every reader returns None
    and none raises, so the line leaves the metrics out."""
    old = {"steps": 3, "prefill_tokens": 10, "tokens_generated": 4}
    ctx = {"stats0": old, "stats1": dict(old), "kind": "serve"}
    for read in (inside.row_occupancy_pct, inside.slot_wait_ms,
                 inside.kv_grid_live_pct, inside.program_build_s):
        assert read(ctx) is None
    for read in (inside.idle_by_program_pct, inside.dispatch_host_ms,
                 inside.server_self_ms_per_step):
        assert read({"trace": None}) is None        # an untraced run
    assert inside.row_occupancy_pct({"kind": "train"}) is None
    data = ProfileData.from_text_proto(
        plane(0, OPS, []) + "\n" + host((1000, 5000), []))
    assert inside.read_lines(data) == {}            # a trace with no pt:


@pytest.mark.parametrize("metric", sorted(
    n for names in inside.METRICS.values() for n in names))
def test_each_metrics_file_is_ready_for_its_benchmark_json_entry(metric):
    mod = loader.module("metrics", metric)
    assert callable(mod.read)
    bj = loader.benchmark_json()
    e2e = {m["name"]: m for m in bj["end_to_end"]}
    layers = {m["layer"] for m in bj["per_layer"]}
    assert mod.MOVES in e2e and mod.LAYER in layers
    assert mod.BETTER in ("lower", "higher")
    assert mod.SOURCE in ("program_counter", "device_trace")
    cell = next(c for c, names in inside.METRICS.items() if metric in names)
    assert cell in e2e[mod.MOVES].get("workloads", [cell])


@pytest.mark.parametrize("name,variant", [
    ("pretrain_2k", None), ("doc_batch", None), ("doc_batch", "open"),
    ("doc_batch", "four_chips")])
def test_every_reader_gives_a_number_in_the_cpu_rehearsals(
        name, variant, monkeypatch):
    monkeypatch.setattr(common, "memory_peak_bytes", lambda devices: 1)
    monkeypatch.setattr(main, "log", lambda msg: None)
    cell = inside.extend(toy.cell(name, variant))
    cell.name = f"{name}.inside.{variant}"   # a trace directory of its own
    obj, declared = main.run_cell(
        cell, 2 ** 31 + 78, 1.5, 1, time.perf_counter(), require_chip=False,
        peaks=toy.PEAKS, load_trace=toy.cpu_trace)
    line = json.loads(output.dumps(obj, declared, True, cell.chips))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(cell.file["per_layer"]) | set(
        inside.METRICS[name])
    assert got["idle_by_program_pct." + ("train" if name == "pretrain_2k"
                                         else "batch")] \
        <= got["device_idle_pct." + ("train" if name == "pretrain_2k"
                                     else "batch")] + 1e-9
    if name == "pretrain_2k":
        assert got["dispatch_host_ms.train"] > 0
        return
    # the new numbers are consistent with the old ones of the same run
    rows_a_step = got["tokens_per_step.batch"] / (
        got["row_occupancy_pct.batch"] / 100)
    eng = cell.config["engine"]
    assert eng["max_batch"] <= rows_a_step <= \
        eng["max_batch"] * eng["chunk_size"]
    assert 0 < got["kv_grid_live_pct.batch"] <= 100
    assert got["slot_wait_ms.batch"] >= 0
    assert got["server_self_ms_per_step.batch"] > 0
    assert got["program_build_s.batch"] > 0
