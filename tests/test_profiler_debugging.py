"""Profiler scheduler/events/export + amp.debugging numeric tools
(reference: test/legacy_test/test_profiler*.py, test_nan_inf*.py)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import (
    Profiler, ProfilerState, RecordEvent, make_scheduler, export_chrome_tracing,
    load_profiler_result, benchmark,
)
from paddle_tpu.amp.debugging import (
    check_numerics, collect_operator_stats, TensorCheckerConfig,
    enable_tensor_checker, disable_tensor_checker,
)


def test_make_scheduler_states():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(5)]
    assert states[0] == ProfilerState.CLOSED
    assert states[1] == ProfilerState.READY
    assert states[2] == ProfilerState.RECORD
    assert states[3] == ProfilerState.RECORD_AND_RETURN
    assert states[4] == ProfilerState.CLOSED


def test_profiler_records_and_exports(tmp_path):
    got = []

    def ready(prof):
        got.append(len(prof._events_snapshot))
        path = str(tmp_path / "trace.json")
        prof._export_chrome(path)
        got.append(path)

    p = Profiler(scheduler=make_scheduler(closed=0, ready=0, record=2, repeat=1),
                 on_trace_ready=ready, timer_only=True)
    p.start()
    for _ in range(3):
        with RecordEvent("train_step"):
            x = paddle.ones([4, 4])
            (x @ x).sum()
        p.step()
    p.stop()
    assert got and got[0] >= 2
    events = load_profiler_result(got[1])
    assert any(e["name"] == "train_step" for e in events)


def test_record_event_disabled_fast_path():
    """With no profiler recording, RecordEvent must neither timestamp nor
    enter a jax named_scope — always-on instrumentation costs ~nothing —
    and must not record a span; enabling a profiler re-arms it."""
    from paddle_tpu.profiler import _BUFFER

    assert not _BUFFER.enabled
    ev = RecordEvent("hot_path")
    with ev:
        assert ev._t0 is None and ev._scope is None
    assert not _BUFFER.events
    p = Profiler(timer_only=True)
    p.start()
    with RecordEvent("hot_path") as ev2:
        assert ev2._t0 is not None
    with _BUFFER.lock:
        assert any(e["name"] == "hot_path" for e in _BUFFER.events)
    p.stop()


def test_profiler_summary(capsys):
    p = Profiler(timer_only=True)
    p.start()
    with RecordEvent("fwd"):
        pass
    with RecordEvent("fwd"):
        pass
    p.stop()
    p._events_snapshot = p._events_snapshot or []
    # stop() snapshots remaining events via _finish_record only in RECORD state;
    # default scheduler is always RECORD so snapshot happened
    table = p.summary()
    assert "fwd" in table


def test_step_timer():
    b = benchmark()
    b.reset()
    b.begin()
    for _ in range(3):
        b.step(num_samples=8)
    info = b.step_info()
    assert "ips" in info and b.step_time.count == 3


def test_check_numerics():
    x = paddle.to_tensor(np.asarray([1.0, np.nan, np.inf, 0.0], np.float32))
    stats, values = check_numerics(x)
    assert list(np.asarray(stats._value)) == [1, 1, 1]
    vals = np.asarray(values._value)
    assert vals[0] == 1.0 and vals[1] == 0.0


def test_operator_stats_collection(capsys):
    with collect_operator_stats():
        a = paddle.ones([2, 2])
        b = a + a
        c = b * b
    out = capsys.readouterr().out
    assert "calls" in out
    assert any(k in out for k in ("add", "multiply", "mul"))


def test_tensor_checker_flags():
    enable_tensor_checker(TensorCheckerConfig(enable=True))
    x = paddle.to_tensor(np.asarray([1.0, 0.0], np.float32))
    with pytest.raises(FloatingPointError):
        x / paddle.zeros([2])
    disable_tensor_checker()
    y = x / paddle.zeros([2])  # no raise once disabled
    assert not np.isfinite(np.asarray(y._value)).all()


def test_merge_profile_cross_rank(tmp_path):
    import json
    from paddle_tpu.profiler import merge_profile

    # fabricate two per-rank traces with different clock bases
    for rank, base in ((0, 1_000_000), (1, 5_000_000)):
        events = [
            {"ph": "M", "pid": 1234, "name": "process_name",
             "args": {"name": "host"}},
            {"ph": "X", "pid": 1234, "tid": 1, "name": f"step{rank}",
             "ts": base + 10, "dur": 100},
            {"ph": "X", "pid": 1234, "tid": 1, "name": "allreduce",
             "ts": base + 150, "dur": 50},
        ]
        with open(tmp_path / f"rank{rank}.json", "w") as f:
            json.dump({"traceEvents": events}, f)

    out = merge_profile([str(tmp_path / "rank0.json"),
                         str(tmp_path / "rank1.json")],
                        str(tmp_path / "merged.json"))
    merged = json.load(open(out))["traceEvents"]
    lanes = {e["args"]["name"] for e in merged
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert lanes == {"rank0:rank0", "rank1:rank1"}
    xs = [e for e in merged if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    # clocks aligned: each rank's earliest event shifts to ts=0, and the
    # relative in-rank spacing survives
    starts = sorted(e["ts"] for e in xs if e["name"].startswith("step"))
    assert starts == [0, 0]
    gaps = sorted(e["ts"] for e in xs if e["name"] == "allreduce")
    assert gaps == [140, 140]


def test_merge_profile_from_dir(tmp_path):
    import json
    from paddle_tpu.profiler import merge_profile

    d = tmp_path / "traces"
    d.mkdir()
    for i in range(2):
        with open(d / f"w{i}.json", "w") as f:
            json.dump({"traceEvents": [
                {"ph": "X", "pid": 9, "tid": 0, "name": "op", "ts": 5,
                 "dur": 1}]}, f)
    out = merge_profile([str(d)], str(tmp_path / "m.json"))
    merged = json.load(open(out))["traceEvents"]
    assert len([e for e in merged if e.get("ph") == "X"]) == 2
