"""Start-up accounted from inside (``paddle_tpu.profiler.build`` /
``builds`` / ``startup``, ``watch_gc``): a build leaves ONE record whose
parts jax itself timed, a steady call leaves none and reads no clock, a
retrace is named, a compile under no build reaches no record, the
collector's pauses are a span and three counters, and the operator's
exporters carry all of it."""
import gc
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu import profiler
from paddle_tpu.inference import LLMEngine
from paddle_tpu.jit.api import TrainStep
from paddle_tpu.profiler import ServingTelemetry, collect_bundle
from paddle_tpu.profiler._build import _UNCLAIMED
from paddle_tpu.serving import AsyncLLMServer

from test_program_spans import prompts, tiny_model, traced

PARTS = ("trace_s", "lower_s", "compile_or_load_s")
CLOCKS = ("perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns",
          "time", "time_ns")


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(tiny_model(), cache_impl="paged", scheduler="fused",
                     max_batch=2, max_seq_len=64, chunk_size=16,
                     block_size=8, readout_stride=4)


def new_records(n0):
    return profiler.builds()[n0:]


def test_a_builds_parts_sum_to_at_most_its_wall():
    """A program that calls a jitted function inside its own trace fires a
    trace event for the inner one too, inside the outer's seconds (and
    ``jnp``'s own jitted functions fire by the dozen): a plain sum of the
    events passes the wall, the record does not."""
    summed = []

    def every_event(event, secs, **_):
        summed.append(secs)
    jax.monitoring.register_event_duration_secs_listener(every_event)

    @jax.jit
    def inner(x):
        for _ in range(40):
            x = jnp.sin(x) * 2.0 + jnp.cos(x)
        return x

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1.0)

    n0 = len(profiler.builds())
    try:
        with profiler.build("pt:engine.build", "nested", 7, program=2):
            outer(jnp.ones((5,))).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(every_event)
    (rec,) = new_records(n0)
    assert rec["program"] == "nested" and rec["owner"] == "engine"
    assert rec["step_id"] == 7 and rec["retrace"] is False
    assert all(rec[p] > 0 for p in PARTS)
    assert sum(rec[p] for p in PARTS) <= rec["wall_s"]
    assert sum(summed) > sum(rec[p] for p in PARTS)     # the trap
    assert rec["pc_ns"] <= time.perf_counter_ns()
    assert rec["cache_hits"] == 0           # tier-1 runs without the cache


def test_a_second_call_leaves_no_record_and_reads_one_clock(engine,
                                                            monkeypatch):
    """The steady path of ``_program.call``: ``_cache_size()`` and ONE
    ``perf_counter()``, as before the records came."""
    prog = engine._program("cow", jax.jit(lambda x: x * 3))
    n0, built = len(profiler.builds()), engine.stats["programs_built"]
    prog(np.ones(4))
    (rec,) = new_records(n0)
    assert rec["program"] == "cow" and rec["owner"] == "engine"
    assert engine.stats["programs_built"] == built + 1
    reads, real = [], time.perf_counter

    def boom(*a, **k):
        raise AssertionError("a steady call read another clock")
    for clock in CLOCKS:
        monkeypatch.setattr(time, clock, boom)
    monkeypatch.setattr(time, "perf_counter",
                        lambda: reads.append(1) or real())
    prog(np.ones(4))
    monkeypatch.undo()
    assert len(reads) == 1
    assert new_records(n0) == [rec]
    assert engine.stats["programs_built"] == built + 1


def test_a_retrace_leaves_one_record_that_names_the_program(engine, caplog):
    prog = engine._program("kv_gather", jax.jit(
        lambda pools, i: [p[i] for p in pools]))
    prog([np.zeros(3), np.zeros(3)], 1)
    n0, built = len(profiler.builds()), engine.stats["programs_built"]
    wall0 = engine.stats["program_build_time_s"]
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.profiler"):
        prog([np.zeros(3)], 0)               # a new argument structure
    (rec,) = new_records(n0)
    assert rec["program"] == "kv_gather" and rec["retrace"] is True
    assert rec["compile_or_load_s"] > 0 and rec["trace_s"] > 0
    assert sum(rec[p] for p in PARTS) <= rec["wall_s"]
    assert engine.stats["programs_built"] == built + 1
    assert engine.stats["program_build_time_s"] - wall0 == rec["wall_s"]
    said = [r.getMessage() for r in caplog.records
            if r.name == "paddle_tpu.profiler"]
    assert len(said) == 1 and "'kv_gather'" in said[0]
    s = profiler.startup()
    assert s["retraces"] >= 1 and s["builds"] >= 2


def test_a_compile_under_no_build_reaches_no_record():
    """The benchmark's float32 reference compiles its own programs in the
    same process, after the window, under no build: they are seen by the
    listeners, kept a while for a retrace to claim, and dropped."""
    with profiler.build("pt:train.build", "first"):
        pass                                # the listeners are on
    n0, totals = len(profiler.builds()), profiler.startup()
    for n in range(2, _UNCLAIMED + 8):      # more than a thread keeps
        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones((n,))).block_until_ready()
    assert new_records(n0) == [] and profiler.startup() == totals
    with profiler.build("pt:train.build", "empty"):
        pass
    (rec,) = new_records(n0)
    assert rec["owner"] == "train" and "step_id" not in rec
    assert [rec[p] for p in PARTS] == [0.0, 0.0, 0.0]


def test_train_steps_first_call_is_one_record_under_its_build_span(tmp_path):
    m = tiny_model(train=True)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = TrainStep(m, lambda mm, ids, lbl: mm(ids, labels=lbl)[0], o)
    ids = paddle.to_tensor(np.stack(prompts(1, (16, 16))), dtype="int32")
    n0 = len(profiler.builds())
    (spans,) = traced(tmp_path, lambda: step(ids, ids))
    (rec,) = new_records(n0)
    assert rec["owner"] == "train" and rec["retrace"] is False
    assert all(rec[p] > 0 for p in PARTS)
    (span,) = [s for s in spans if s.name == "pt:train.build"]
    # the record's wall is the span's: one enter, one exit
    assert rec["wall_s"] == pytest.approx((span.end - span.start) / 1e9,
                                          rel=0.05)
    step(ids, ids)
    assert new_records(n0) == [rec]


def test_the_engines_construction_and_the_packages_import_have_a_wall(engine):
    assert 0 < engine.stats["engine_init_time_s"] < 60
    s = profiler.startup()
    assert 0 < s["import_s"] < 60
    assert s["jax_preimported"] is True     # conftest imports jax first
    assert set(s) == {"trace_s", "lower_s", "compile_or_load_s", "wall_s",
                      "builds", "retraces", "cache_hits", "cache_misses",
                      "import_s", "jax_preimported"}


def test_a_collection_is_a_span_and_three_counters(engine, tmp_path):
    other = LLMEngine(tiny_model(), max_batch=1, max_seq_len=32)
    spans = []
    gc.collect()                            # nothing left to find inside
    gc.disable()                            # ... and none but the forced
    before = [dict(e.stats) for e in (engine, other)]
    try:
        t0 = time.perf_counter_ns()
        traced(tmp_path, lambda: gc.collect(), gc_spans=spans)
        t1 = time.perf_counter_ns()
    finally:
        gc.enable()
    (span,) = spans
    assert span.ids["generation"] == 2 and span.ids["collected"] >= 0
    assert t0 <= span.ids["pc_ns"] <= t1
    pause = (span.end - span.start) / 1e9
    for eng, was in zip((engine, other), before):
        assert eng.stats["gc_pauses"] == was["gc_pauses"] + 1
        assert eng.stats["gc_pause_time_s"] - was["gc_pause_time_s"] == \
            pytest.approx(pause, rel=0.1, abs=2e-4)
        assert eng.stats["gc_pause_max_s"] >= pause * 0.9
    assert gc.callbacks.count(profiler._host_gc._on_gc) == 1


def test_the_exporters_hold_the_new_names(engine):
    srv = AsyncLLMServer(engine, black_box=False)
    srv.start()
    try:
        snap = srv.telemetry.snapshot()
        text = srv.telemetry.prometheus_text()
        bundle = collect_bundle(server=srv)
    finally:
        srv.stop(timeout=60)
    assert snap["gauges"]["engine_init_time_s"] == pytest.approx(
        engine.stats["engine_init_time_s"], abs=1e-6)
    assert snap["startup"]["builds"] >= 1 and snap["startup"]["import_s"] > 0
    for name in ("engine_init_time_s", "startup_trace_s", "startup_lower_s",
                 "startup_compile_or_load_s", "startup_wall_s",
                 "startup_builds", "startup_retraces", "startup_cache_hits",
                 "startup_cache_misses", "startup_import_s"):
        assert f"\npaddle_tpu_serving_{name} " in text
    assert ServingTelemetry(replica=3).prometheus_text().count(
        'startup_builds{replica="3"}') == 1
    assert bundle["builds"] and bundle["builds"] == profiler.builds()[-64:]
    assert {"program", "owner", "wall_s", "retrace"} <= set(
        bundle["builds"][-1])
