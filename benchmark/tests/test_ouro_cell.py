"""``ouro_worked_answers`` beside ``test_cells.py`` (which rehearses the
cell end to end with every other, by its name in ``BENCHMARK.json``): the
lower-precision control at a test's size against the toy cell's limit, and
the two readers this cell brought, on made-up counters and a made-up
trace, with the program that lacks the counters (the parent) and the trace
that lacks the kernel reading nothing."""
import numpy as np
import pytest

from benchmark.harness import loader
from benchmark.harness.trace import TraceError
from benchmark.tests import toy


def test_the_lower_precision_control_fails_the_served_comparison():
    """The int8 control in the program's place: at each position of the
    same prompts and tokens, the token the lower precision puts first,
    under the float32 reference. It has to pass the limit; the reference's
    own greedy tokens, which stand for a sound program, stay under it."""
    cell = toy.cell("ouro_worked_answers")
    R = cell.reference()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cell.config["vocab_size"], size=n)
               for n in (90, 40, 120)]
    limit = cell.file["check"]["limits"]["gap_mean"]
    for seed in (5, 99, 2 ** 31 + 77):
        sample = []
        for p in prompts:
            seq = list(p)
            for _ in range(8):
                logits = R.served_logits(seed, cell.config, [seq],
                                         [[len(seq) - 1]], pad_to=64)
                seq.append(int(np.argmax(logits[0][0])))
            sample.append((p, np.asarray(seq[len(p):])))
        out = R.served_gaps(seed, cell.config, sample, "int8", pad_to=64)
        assert np.concatenate(out["gaps"]).mean() <= limit
        assert np.concatenate(out["control_gaps"]).mean() > limit


class _Trace:
    def __init__(self, secs, calls):
        self.secs, self.calls = secs, calls

    def op_seconds(self, pattern):
        if not self.calls:
            raise TraceError(f"no event matching {pattern!r}")
        return self.secs, self.calls


class _Rec:
    n_prompt = 100
    events = [(1.0, 0, 1), (2.0, 1, 4), (9.0, 5, 4)]


class _Stretch:
    t0, t1 = 0.5, 3.0


def _ctx(stats0, stats1, trace, stretch=_Stretch):
    return {"cell": loader.Cell("ouro_worked_answers"), "stats0": stats0,
            "stats1": stats1, "trace": trace, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "records": [_Rec()], "stretch": stretch()}


def test_looped_decode_roofline_is_calls_times_the_stretchs_own_context(
        capsys):
    read = loader.module("metrics", "looped_decode_roofline").read
    k = loader.module("kernels", "paged_attention_decode")
    zero = {"decode_ctx_tokens": 0, "decode_rows": 0,
            "decode_iterations": 0}
    # the window: 10 iterations of 8 live slots holding 2,400 tokens
    # between them, 300 a row
    one = {"decode_ctx_tokens": 24000, "decode_rows": 80,
           "decode_iterations": 10}
    calls, secs = 5 * 192, 0.5
    got = read(_ctx(zero, one, _Trace(secs, calls)))
    # the stretch: tokens 1..5 of a 100-token prompt were seen in it, so a
    # row held 101..105 = 103 tokens, not the window's 300. A call reads
    # 8 x 103 tokens' K and V of 16 heads x 128 x 2 B, and q, out and the
    # new K/V of 8 rows: the bandwidth roof binds
    flops, nbytes = k.least(8 * 103, 16, 16, 128, calls, seqs=8)
    assert nbytes == calls * (824 * 2 * 16 + 8 * 64) * 128 * 2
    assert flops / 197e12 < nbytes / 819e9
    assert got == pytest.approx(100 * (nbytes / 819e9) / secs)
    assert 0 < got < 100
    # both printed, the stretch's used
    said = capsys.readouterr().out
    assert "103.0 tokens inside the traced stretch" in said
    assert "300.0 over the window" in said
    # nothing to read: a program without the counters, a trace without
    # the kernel, a window without a decode iteration, no trace at all, a
    # stretch in which no token was seen
    assert read(_ctx({}, {}, _Trace(secs, calls))) is None
    assert read(_ctx(zero, one, _Trace(0.0, 0))) is None
    assert read(_ctx(zero, zero, _Trace(secs, calls))) is None
    assert read(_ctx(zero, one, None)) is None

    class _Elsewhere:
        t0, t1 = 20.0, 24.0
    assert read(_ctx(zero, one, _Trace(secs, calls), _Elsewhere)) is None


def test_kv_pool_used_pct_is_used_over_total_across_the_window(capsys):
    read = loader.module("metrics", "kv_pool_used_pct.batch").read
    s0 = {"pool_blocks_used": 100, "pool_blocks_total": 800}
    s1 = {"pool_blocks_used": 100 + 47 * 90, "pool_blocks_total":
          800 + 80 * 90}
    assert read({"stats0": s0, "stats1": s1}) == pytest.approx(58.75)
    assert read({"stats0": {}, "stats1": {}}) is None        # the parent
    assert read({"stats0": s0, "stats1": s0}) is None
    # the exit masses are booked in fixed point: printed as shares
    capsys.readouterr()
    read({"stats0": dict(s0, loop_exit_mass_1=0, loop_exit_mass_2=0),
          "stats1": dict(s1, loop_exit_mass_1=3 << 16,
                         loop_exit_mass_2=1 << 16)})
    assert "'loop_exit_mass_pct': [75.0, 25.0]" in capsys.readouterr().out
