"""Every cell end to end at a toy size on the CPU (and the open loop and
the four-chip engine, on four virtual devices), untraced and traced, with the last line parsed by the
same validator that guards the real runs. Then the comparison behind
``correct`` shown to fail: with the timed path broken underneath, and with
the lower-precision control in the program's place."""
import json
import time

import numpy as np
import pytest

from benchmark.harness import common, loader, main, output
from benchmark.tests import toy

SEED = 2 ** 31 + 77
CELLS = [w["name"] for w in loader.benchmark_json()["workloads"]]


@pytest.fixture(autouse=True)
def cpu_has_no_memory_stats(monkeypatch):
    monkeypatch.setattr(common, "memory_peak_bytes", lambda devices: 1)
    monkeypatch.setattr(main, "log", lambda msg: None)


#: every shipped cell, and the harness's paths that no shipped cell takes
CASES = [(name, None) for name in CELLS] + [("doc_batch", "open"),
                                            ("doc_batch", "four_chips")]


def run(name, trace, variant=None, **kw):
    cell = toy.cell(name, variant)
    obj, declared = main.run_cell(
        cell, SEED, 1.5, trace, time.perf_counter(), require_chip=False,
        peaks=toy.PEAKS, load_trace=toy.cpu_trace, **kw)
    return cell, obj, declared


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name,variant", CASES)
def test_cell_runs_to_a_valid_last_line(name, variant, trace):
    cell, obj, declared = run(name, trace, variant)
    line = json.loads(output.dumps(obj, declared, bool(trace), cell.chips))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"      # never a measurement
    assert line["device"]["count"] == cell.chips
    assert set(line["metrics"]) == set(cell.file[
        "per_layer" if trace else "end_to_end"])
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"]
    else:
        assert line["metrics"]["setup_s"]["value"] > 0


def test_the_measurement_path_refuses_a_cpu(capsys):
    with pytest.raises(common.NoChip):
        main.run_cell(toy.cell("pretrain_2k"), SEED, 1.0, 0,
                      time.perf_counter())


def test_a_served_token_altered_where_it_is_produced_is_not_correct():
    def tamper(sample):
        prompt, served = sample[-1]
        served = np.array(served)
        served[len(served) // 2] = (served[len(served) // 2] + 1) % 1024
        return sample[:-1] + [(prompt, served)]
    _, obj, _ = run("doc_batch", 0, tamper=tamper)
    assert obj["correct"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    def tamper(step):
        step.donate = False      # the old state has to outlive the call

        def frozen(ids, labels):
            """The loss of the real step, the state put back."""
            opt, params = step.optimizer, step.params
            before = [p._value for p in params]
            slots = {k: dict(v) for k, v in opt._slots.items()}
            loss = step(ids, labels)
            for p, v in zip(params, before):
                p._value = v
            opt._slots.update(slots)
            return loss
        return frozen
    _, obj, _ = run("pretrain_2k", 0, tamper=tamper)
    assert obj["correct"] is False


def test_the_lower_precision_control_fails_the_served_comparison():
    """The fp8 control in the program's place: at each position of the same
    prompts and tokens, the token the lower precision puts first, under
    the float32 reference. It has to pass a limit."""
    from benchmark.reference import dense_decoder as R
    cell = toy.cell("doc_batch")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 1024, size=n) for n in (90, 40, 120)]
    limits = cell.file["check"]["limits"]
    for seed in (5, 99, 2 ** 31 + 77):
        # greedy tokens of the reference itself stand for a sound program
        sample = []
        for p in prompts:
            seq = list(p)
            for _ in range(8):
                logits = R.served_logits(seed, cell.config, [seq],
                                         [[len(seq) - 1]], pad_to=64)
                seq.append(int(np.argmax(logits[0][0])))
            sample.append((p, np.asarray(seq[len(p):])))
        out = R.served_gaps(seed, cell.config, sample, "fp8", pad_to=64)
        sound = np.concatenate(out["gaps"])
        low = np.concatenate(out["control_gaps"])
        assert sound.max() <= limits["gap_max"]
        assert sound.mean() <= limits["gap_mean"]
        assert low.max() > limits["gap_max"] \
            or low.mean() > limits["gap_mean"]


def test_the_lower_precision_control_fails_the_training_comparison():
    """The int8 control in the program's place, against the limits the cell
    ships: it has to fail one of the numbers (on the chip at the cell's own
    size it fails the first gradient's norm and the parameters' change)."""
    from benchmark.harness import train
    from benchmark.reference import dense_decoder as R
    cell = toy.cell("pretrain_2k")
    limits = loader.Cell("pretrain_2k").file["check"]["limits"]
    hp = train.hyper(cell.config)
    for seed in (5, 99, 2 ** 31 + 77):
        first = [train.batch_for(seed, k, 4, 64, 1024) for k in range(3)]
        ref = R.train_steps(seed, cell.config, first, hp)
        same, ok = train.compare(ref, ref, limits, lambda s: None)
        assert ok and max(same.values()) == 0
        low = R.train_steps(seed, cell.config, first, hp, "int8")
        _, ok = train.compare(low, ref, limits, lambda s: None)
        assert not ok


def test_a_prompt_is_credited_over_its_prefill_and_every_token_once():
    """Two windows back to back count every token once between them, and a
    document that crosses the edge moves neither by its whole length."""
    from benchmark.harness import serve

    class R:
        n_prompt = 3000

        def __init__(self, t_submit, t_first, events):
            self.t_submit, self.t_first, self.events = \
                t_submit, t_first, events
    recs = [R(8.0, 12.0, [(12.0, 0, 1), (13.0, 1, 4)]),     # crosses 10
            R(2.0, 3.0, [(3.0, 0, 1), (9.5, 1, 4)]),
            R(19.0, None, [])]                              # never answered
    a, a_first, seen = serve._window_tokens(recs, 0.0, 10.0)
    b, b_first, _ = serve._window_tokens(recs, 10.0, 20.0)
    assert a == pytest.approx(3000 + 5 + 1500)
    assert b == pytest.approx(1500 + 5)
    assert a + b == a_first + b_first == 2 * 3005
    assert (a_first, b_first) == (3005, 3005) and seen == [3.0, 9.5]
