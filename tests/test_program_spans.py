"""The program's own spans in a profiler trace (``paddle_tpu.profiler.span``,
names ``pt:<layer>.<phase>``) and the counters beside them in
``engine.stats``.

A ``jax.profiler`` trace is taken on the CPU backend around a few
``AsyncLLMServer`` requests and around three ``TrainStep`` calls, and read
back from the ``.xplane.pb``: every name of the contract is there, children
lie inside their parents, only the two wait spans wait, a dispatch span
carries its StepRecord's id and maps the recorder's ``perf_counter`` stamps
onto the trace's clock, and ``engine.stats``'s wall sums are the spans'
summed lengths — both come from the same enter/exit."""
import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.llm_engine import DISPATCH_KINDS, PROGRAMS
from paddle_tpu.jit.api import TrainStep
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import span
from paddle_tpu.serving import AsyncLLMServer

V = 96
SERVER = {"pt:server." + n for n in ("pass", "idle", "admit_queue", "begin",
                                     "finish", "sweep")}
ENGINE = {"pt:engine." + n for n in ("admit", "schedule", "dispatch", "sync",
                                     "emit", "build")}
TRAIN = {"pt:train." + n for n in ("step", "prepare", "build", "dispatch",
                                   "commit")}
WAITS = {"pt:server.idle", "pt:engine.sync"}


def tiny_model(train=False):
    paddle.seed(7)
    cfg = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.train() if train else m.eval()
    return m


def prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, size=(n,)).astype(np.int32) for n in sizes]


class Span:
    def __init__(self, start, end, name, ids):
        self.start, self.end, self.name, self.ids = start, end, name, ids
        self.parent = None

    def ancestors(self):
        a = self.parent
        while a is not None:
            yield a
            a = a.parent


def traced(tmp_path, body, gc_spans=None):
    """Run ``body()`` under a profile; returns the ``pt:`` spans of each
    host thread, nested (a thread's spans never cross). The collector's
    ``pt:host.gc`` spans come when it pleases, on whichever thread tripped
    it: they are set aside, into ``gc_spans`` where one is given."""
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level, o.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=o)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[-1]
    threads = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            rows = sorted(
                (Span(int(e.start_ns), int(e.start_ns + e.duration_ns),
                      e.name, dict(e.stats))
                 for e in line.events if e.name.startswith("pt:")),
                key=lambda s: (s.start, -s.end))
            if gc_spans is not None:
                gc_spans += [s for s in rows if s.name == "pt:host.gc"]
            rows = [s for s in rows if s.name != "pt:host.gc"]
            stack = []
            for s in rows:
                while stack and stack[-1].end <= s.start:
                    stack.pop()
                if stack:
                    assert s.end <= stack[-1].end, \
                        f"{s.name} crosses the end of {stack[-1].name}"
                    s.parent = stack[-1]
                stack.append(s)
            if rows:
                threads.append(rows)
    return threads


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A fused, paged server with a flight recorder, its whole life inside
    one profile: (spans of the engine thread, engine.stats at the end, the
    recorder's StepRecords)."""
    eng = LLMEngine(tiny_model(), cache_impl="paged", scheduler="fused",
                    max_batch=2, max_seq_len=64, chunk_size=16, block_size=8,
                    readout_stride=4)
    srv = AsyncLLMServer(eng, flight_recorder=True, pipeline_depth=2)
    out = {}

    def body():
        srv.start()
        hs = [srv.submit(p, max_new_tokens=6, temperature=0.0)
              for p in prompts(0, (20, 37, 9))]
        for h in hs:
            assert h.result(timeout=300).finish_reason == "length"
        time.sleep(0.05)            # a pass or two of idle
        srv.stop(timeout=60)
        out["stats"] = dict(eng.stats)
        out["records"] = srv.flight_recorder.records()
    threads = traced(tmp_path_factory.mktemp("serve"), body)
    assert len(threads) == 1, "the engine thread alone writes pt: spans"
    return threads[0], out["stats"], out["records"]


def test_every_server_and_engine_span_of_the_contract_is_in_the_trace(served):
    spans, _, _ = served
    assert {s.name for s in spans} == SERVER | ENGINE


def test_children_lie_inside_their_parents(served):
    spans, _, _ = served
    for s in spans:
        up = [a.name for a in s.ancestors()]
        if s.name == "pt:server.pass":
            assert not up
        elif s.name in SERVER:
            assert up == ["pt:server.pass"]
        elif s.name == "pt:engine.build":
            assert up[0] in ("pt:engine.dispatch", "pt:engine.admit")
        else:
            # an engine phase: directly under the server's call into the
            # engine, never under another phase
            assert up == [{"pt:engine.sync": "pt:server.finish",
                           "pt:engine.emit": "pt:server.finish"}.get(
                               s.name, "pt:server.begin"), "pt:server.pass"]
        assert all(a.start <= s.start and s.end <= a.end
                   for a in s.ancestors())


def test_only_the_two_wait_spans_wait(served):
    spans, _, _ = served
    # a wait is a leaf (the host does nothing of its own inside it) ...
    assert not [s.name for s in spans
                if any(a.name in WAITS for a in s.ancestors())]
    # ... and the device's work is waited for in pt:engine.sync alone: the
    # dispatches return at once (async), the sync spans hold the steps
    by = {}
    for s in spans:
        by[s.name] = by.get(s.name, 0) + s.end - s.start
    assert WAITS <= set(by)


def test_a_dispatch_span_joins_the_flight_recorder_by_step_id(served):
    spans, stats, records = served
    disp = {s.ids["step_id"]: s for s in spans
            if s.name == "pt:engine.dispatch"}
    recs = {r.step_id: r for r in records if r.kind != "drain"}
    assert set(disp) == set(recs) and len(disp) == stats["steps"]
    for sid, s in disp.items():
        r = recs[sid]
        assert DISPATCH_KINDS[s.ids["kind"]] == r.kind
        assert s.ids["live_tokens"] == r.tokens_scheduled
        assert s.ids["rows"] >= s.ids["live_tokens"] > 0
        # a mixed step (the engine is paged) carries its attn_tile_steps
        assert ("live_tiles" in s.ids) == (r.kind == "mixed")
        # pc_ns lays the recorder's perf_counter stamps on the trace's
        # clock with one subtraction: the step's entry falls inside the
        # pass that holds its dispatch, before the dispatch
        to_trace = s.start - s.ids["pc_ns"]
        t_begin = r.t_begin * 1e9 + to_trace
        loop_pass = list(s.ancestors())[-1]
        assert loop_pass.name == "pt:server.pass"
        assert loop_pass.start - 2000 <= t_begin <= s.start + 2000
    assert sum(s.ids.get("live_tiles", 0) for s in disp.values()) == \
        stats["attn_tile_steps"] > 0
    # the step's shape: its prefill and decode rows are its grants', and
    # its context is what its granted requests held before it (no prefix
    # cache, nothing preempted: every token a request holds was granted)
    held = {}
    for sid in sorted(disp):
        s, r = disp[sid], recs[sid]
        by_kind = {"prefill": 0, "decode": 0}
        for _, _, kind, n in r.grants:
            by_kind[kind] += n
        assert (s.ids["prefill_rows"], s.ids["decode_rows"]) == \
            (by_kind["prefill"], by_kind["decode"])
        assert s.ids["ctx_tokens"] == sum(held.get(rid, 0)
                                          for _, rid, _, _ in r.grants)
        for _, rid, _, n in r.grants:
            held[rid] = held.get(rid, 0) + n
    assert sum(s.ids["prefill_rows"] for s in disp.values()) == \
        stats["prefill_tokens"] > 0
    assert sum(s.ids["decode_rows"] for s in disp.values()) >= \
        stats["tokens_generated"] > 0
    # the sync and the emit of a step carry its id too
    for name in ("pt:engine.sync", "pt:engine.emit"):
        assert {s.ids["step_id"] for s in spans if s.name == name} \
            == set(recs)


def test_a_build_span_names_its_program(served):
    spans, stats, _ = served
    built = [PROGRAMS[s.ids["program"]] for s in spans
             if s.name == "pt:engine.build"]
    assert len(built) == stats["programs_built"]
    assert {"fused_step", "multi_step", "set_len"} <= set(built)
    assert sum(s.end - s.start for s in spans
               if s.name == "pt:engine.build") / 1e9 == \
        pytest.approx(stats["program_build_time_s"], rel=0.05)


@pytest.mark.parametrize("phase,keys", [
    ("admit", ("admit_time_s",)), ("schedule", ("schedule_time_s",)),
    ("dispatch", ("dispatch_time_s",)), ("sync", ("host_sync_time_s",)),
    ("emit", ("emit_time_s",))])
def test_a_stats_wall_sum_is_its_phases_summed_spans(served, phase, keys):
    spans, stats, _ = served
    total = sum(s.end - s.start for s in spans
                if s.name == "pt:engine." + phase) / 1e9
    for key in keys:
        assert stats[key] == pytest.approx(total, rel=0.05, abs=2e-4)
    assert stats["decode_time_s"] == pytest.approx(
        stats["dispatch_time_s"] + stats["host_sync_time_s"])


def test_train_step_spans(tmp_path):
    m = tiny_model(train=True)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = TrainStep(m, lambda mm, ids, lbl: mm(ids, labels=lbl)[0], o)
    ids = paddle.to_tensor(np.stack(prompts(1, (16, 16))), dtype="int32")

    def body():
        for _ in range(3):
            step(ids, ids)
    threads = traced(tmp_path, body)
    assert len(threads) == 1
    spans = threads[0]
    assert {s.name for s in spans} == TRAIN
    steps = [s for s in spans if s.name == "pt:train.step"]
    assert [s.ids["step"] for s in steps] == [1, 2, 3]
    for s in spans:
        up = [a.name for a in s.ancestors()]
        if s.name == "pt:train.step":
            assert not up
        elif s.name == "pt:train.dispatch":
            assert up in (["pt:train.step"],
                          ["pt:train.build", "pt:train.step"])
        else:
            assert up == ["pt:train.step"]
    # built once, at the first call, with that call's dispatch inside it
    builds = [s for s in spans if s.name == "pt:train.build"]
    assert len(builds) == 1 and builds[0].parent is steps[0]
    names = [[c.name for c in spans if c.parent is s] for s in steps]
    assert names[0] == ["pt:train.prepare", "pt:train.build",
                        "pt:train.commit"]
    assert names[1] == names[2] == ["pt:train.prepare", "pt:train.dispatch",
                                    "pt:train.commit"]


def test_span_reads_no_clock_when_no_profile_is_taken(monkeypatch):
    """The twin of ``test_record_event_disabled_fast_path``: an idle span
    is a TraceMe that finds tracing off — no clock of ours is read."""
    def boom(*a, **k):
        raise AssertionError("span() read a clock with no profile running")
    for clock in ("perf_counter", "perf_counter_ns", "monotonic",
                  "monotonic_ns", "time", "time_ns"):
        monkeypatch.setattr(time, clock, boom)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with span("pt:engine.dispatch", step_id=3, rows=2048):
        with span("pt:engine.build", program=2):
            pass


# -- the counters -------------------------------------------------------------
def run_counted(cache, stride):
    """A scripted schedule on a fused engine driven by hand; returns the
    engine, its stats and the rows its step programs were asked for (read
    off the programs' own calls)."""
    kw = dict(block_size=8) if cache == "paged" else {}
    eng = LLMEngine(tiny_model(), cache_impl=cache, scheduler="fused",
                    max_batch=2, max_seq_len=64, chunk_size=16,
                    readout_stride=stride, **kw)
    eng._programs()
    rows, mixed = [], []
    fused = eng._fused_fn

    def fused_spy(*a, **k):
        rows.append(a[6].size)          # ids: [B, chunk]
        # the device's own lens going into the step, and the grants
        mixed.append((np.asarray(a[4]), np.asarray(a[7])))
        return fused(*a, **k)
    eng._fused_fn = fused_spy
    for rid, p in enumerate(prompts(3, (20, 9, 33))):
        eng.add_request(p, max_new_tokens=7, temperature=0.0)
    while eng.has_unfinished():
        before = len(rows)
        pending = eng.step_begin()
        done = eng.step_finish(pending)
        assert all(o.finish_reason == "length" for o in done)
        if len(rows) == before and pending.toks is not None:
            # an all-decode dispatch: the iterations that ran are those
            # with any row active
            rows.append(eng.B * max(int(np.asarray(
                pending.was_active).any(axis=1).sum()), 1))
    eng.mixed_steps = mixed
    return eng, dict(eng.stats), rows


@pytest.fixture(scope="module", params=[("dense", 1), ("dense", 4),
                                        ("paged", 1), ("paged", 4)],
                ids=lambda p: f"{p[0]}-stride{p[1]}")
def counted(request):
    return request.param + run_counted(*request.param)


def test_rows_computed_is_the_programs_rows(counted):
    cache, stride, eng, stats, rows = counted
    assert stats["rows_computed"] == sum(rows) > 0
    mixed = stats["fused_steps"] * eng.B * eng.chunk
    assert stats["rows_computed"] >= mixed
    if stride == 1:
        assert stats["rows_computed"] == mixed + \
            (stats["steps"] - stats["fused_steps"]) * eng.B
    tokens = stats["prefill_tokens"] + stats["tokens_generated"]
    assert tokens == 20 + 9 + 33 + 3 * 7 <= stats["rows_computed"]


def test_the_attention_grid_holds_at_least_its_live_blocks(counted):
    cache, stride, eng, stats, _ = counted
    if cache == "dense":
        assert stats["kv_grid_blocks"] == stats["kv_live_blocks"] == 0
        return
    assert 0 < stats["kv_live_blocks"] <= stats["kv_grid_blocks"]
    # the grid walks every table entry of every slot each iteration
    assert stats["kv_grid_blocks"] % eng._tables.size == 0


def test_attn_tile_steps_grow_by_the_kernels_own_count(counted):
    """Each mixed paged dispatch books what ``append_tile_steps`` says of
    the lens and grants the step program was really called with (the
    device's lens, not the host's mirror of them), on the packed row axis
    the kernel's tiles lie on: a slot's first row is the grants before
    it."""
    from paddle_tpu.ops.kernels.paged_attention import append_tile_steps
    cache, _, eng, stats, _ = counted
    if cache == "dense":
        assert stats["attn_tile_steps"] == stats["attn_tile_steps_grid"] == 0
        return
    cfg = eng.model.config
    want = np.sum([append_tile_steps(
        lens, q_lens, cfg.num_attention_heads // cfg.num_key_value_heads,
        eng.chunk, eng.block_size, eng._tables.shape[1],
        np.cumsum(q_lens) - np.asarray(q_lens))
        for lens, q_lens in eng.mixed_steps], axis=0)
    assert len(eng.mixed_steps) == stats["fused_steps"] > 0
    assert (stats["attn_tile_steps"], stats["attn_tile_steps_grid"]) == \
        tuple(want)
    assert 0 < stats["attn_tile_steps"] <= stats["attn_tile_steps_grid"]


def test_first_grants_count_the_requests_prefilled(counted):
    _, _, _, stats, _ = counted
    assert stats["first_grants"] == 3
    assert stats["slot_wait_time_s"] >= 0.0


def test_programs_are_built_once(counted):
    _, _, eng, stats, _ = counted
    assert stats["programs_built"] >= 2
    assert stats["program_build_time_s"] > 0
    eng.generate(prompts(3, (20, 9, 33)), max_new_tokens=7, temperature=0.0)
    assert eng.stats["programs_built"] == stats["programs_built"]
    assert eng.stats["program_build_time_s"] == stats["program_build_time_s"]


def test_a_build_is_a_call_that_grew_jits_own_cache(counted):
    """After the first call a build is told from jit itself, whatever the
    arguments' nesting: the same shapes again build nothing, a new shape
    inside a list builds once."""
    _, _, eng, _, _ = counted
    prog = eng._program("cow", jax.jit(lambda pools, i: [p[i] for p in pools]))
    n0 = eng.stats["programs_built"]
    for size, grown in ((3, 1), (3, 1), (5, 2), (5, 2), (3, 2)):
        prog([np.zeros(size), np.zeros(size)], 1)
        assert eng.stats["programs_built"] == n0 + grown

