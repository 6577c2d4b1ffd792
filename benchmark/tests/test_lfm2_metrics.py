"""What ``lfm2_agent_turns`` added to the benchmark, without a chip: the
two readers (``short_conv_roofline``, ``gqa_d64_roofline``) on made-up
runs, where known seconds and ids give the known share and a program
without the ids, the table or the kernels gives None and raises nothing;
the cell's files found by the loader under the names ``BENCHMARK.json``
gives; and the five families that share ``SparseMoE``, ``StateCausalLM``
and ``moe_dropless.route`` with this one tracing to the programs they
traced at the parent commit."""
import hashlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import loader
from benchmark.harness.trace import TraceError
from benchmark.tests import toy

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CELL = "lfm2_agent_turns"
CONFIG = loader.data("configs", "lfm2-24b-a2b-pp4-d10")


class Table:
    """A component table whose step programs hold ``rows``: {(component,
    innermost scope): seconds}."""

    def __init__(self, rows, programs=100):
        self.rows = {(comp, leaf, False): (secs, 1, {})
                     for (comp, leaf), secs in rows.items()}
        self.n = programs

    def step_kinds(self):
        return {"jit_fused_step": SimpleNamespace(rows=self.rows)}

    def programs(self):
        return self.n


class Emits:
    def __init__(self, ids):
        self.ids = ids

    def named(self, prefix):
        assert prefix == "pt:engine.emit"
        return [SimpleNamespace(ids=i) for i in self.ids]


class Trace:
    """``events``: {pattern: (seconds, count)}; any other pattern has no
    event inside the window."""

    def __init__(self, events):
        self.events = events

    def op_seconds(self, pattern, line=None):
        if pattern not in self.events:
            raise TraceError(f"no event matching {pattern!r}")
        return self.events[pattern]


def ctx(**kw):
    base = {"cell": SimpleNamespace(config=CONFIG), "peaks": PEAKS,
            "chips": 1}
    base.update(kw)
    return base


def test_the_conv_cores_least_work():
    k = loader.module("kernels", "short_conv")
    # 128 decode rows in 8 conv layers: W_in's output, the result and 128
    # tails of [2, 2048] in and out, 3.1 us a layer at the chip's bandwidth
    flops, nbytes = k.least(128 * 8, 128 * 8, 2048, 3)
    assert flops == 8 * 1024 * 2048
    assert nbytes == 2 * (4 * 1024 * 2048 + 2 * 1024 * 2 * 2048)
    assert nbytes / PEAKS["bytes_per_s"] == pytest.approx(41e-6, rel=0.01)
    assert flops / PEAKS["flops_per_s"] < 0.01 * nbytes / PEAKS["bytes_per_s"]


def test_the_conv_roofline_reads_the_emits_ids_against_its_scopes_time():
    read = loader.module("metrics", "short_conv_roofline").read
    k = loader.module("kernels", "short_conv")
    mixed = {"step_id": 1, "conv_rows": 8 * 639, "conv_tails": 8 * 128}
    scan = {"step_id": 2, "conv_rows": 8 * 4 * 128, "conv_tails": 8 * 4 * 128}
    least = 0.0
    for ids in (mixed, scan):
        f, b = k.least(ids["conv_rows"], ids["conv_tails"], 2048, 3)
        least += max(f / PEAKS["flops_per_s"], b / PEAKS["bytes_per_s"])
    # the scope's own rows count, whatever component they fall under, and
    # a kernel's under its own name; the projections beside them do not
    table = Table({("mixer.other", "pt.conv"): 0.010,
                   ("mixer.other", "lfm2_short_conv"): 0.002,
                   ("mixer.proj", "in_proj"): 0.5,
                   ("mixer.other", "pt.rope"): 0.3})
    got = read(ctx(trace=object(), inside=Emits([mixed, scan] * 20),
                   components=table))
    assert got == pytest.approx(100 * 20 * least / 0.012)
    assert 0 < got < 100
    # nothing to read: no trace, no ids on the emits (the parent's program),
    # no span, no table, no such scope
    assert read(ctx()) is None
    assert read(ctx(trace=object(), inside=Emits([{"step_id": 1}]),
                    components=table)) is None
    assert read(ctx(trace=object(), inside=None, components=table)) is None
    assert read(ctx(trace=object(), inside=Emits([scan]),
                    components=None)) is None
    assert read(ctx(trace=object(), inside=Emits([scan]), components=Table(
        {("mixer.proj", "in_proj"): 0.5}))) is None


def test_the_paged_kernels_least_work_counts_a_slot_once_and_64_a_head():
    k = loader.module("kernels", "gqa_paged_d64")
    # 128 decode rows at a context of 1,200 in 2 layers: 0.63 GB of K and
    # V at 64 values a head, 0.77 ms; the flops are a tenth of it
    rows, ctx_tokens = 2 * 128, 2 * 128 * 1200
    flops, nbytes = k.least(rows, ctx_tokens, ctx_tokens, 32, 8, 64)
    assert nbytes == 2 * 64 * (2 * 8 * ctx_tokens + rows * (64 + 16))
    assert nbytes / PEAKS["bytes_per_s"] == pytest.approx(0.77e-3, rel=0.01)
    assert flops == 4.0 * 32 * 64 * ctx_tokens
    assert flops / PEAKS["flops_per_s"] < 0.1 * nbytes / PEAKS["bytes_per_s"]
    # a chunk of 512 rows on a slot of 1,024: its rows attend 393,472
    # positions between them, and the slot's keys are read ONCE
    chunk = sum(range(513, 1025))
    flops, nbytes = k.least(512, chunk, 1024, 32, 8, 64)
    assert nbytes == 2 * 64 * (2 * 8 * 1024 + 512 * 80)
    assert flops == 4.0 * 32 * 64 * chunk


def test_the_d64_roofline_reads_both_kernels_against_the_emits_ids():
    read = loader.module("metrics", "gqa_d64_roofline").read
    k = loader.module("kernels", "gqa_paged_d64")
    mixed = {"step_id": 1, "kv_rows": 2 * 639, "kv_slot_tokens": 2 * 160000,
             "kv_ctx_tokens": 2 * 300000}
    scan = {"step_id": 2, "kv_rows": 2 * 4 * 128,
            "kv_ctx_tokens": 2 * 4 * 150000, "kv_slot_tokens": 2 * 4 * 150000}
    least = 0.0
    for ids in (mixed, scan):
        f, b = k.least(ids["kv_rows"], ids["kv_ctx_tokens"],
                       ids["kv_slot_tokens"], 32, 8, 64)
        least += max(f / PEAKS["flops_per_s"], b / PEAKS["bytes_per_s"])
    tr = Trace({"paged_attention_append": (0.060, 40),
                "paged_attention_decode": (0.200, 160)})
    got = read(ctx(trace=tr, inside=Emits([mixed, scan] * 20)))
    assert got == pytest.approx(100 * 20 * least / 0.260)
    assert 0 < got < 50
    # a stretch of scans alone has no append kernel, and back
    only = read(ctx(trace=Trace({"paged_attention_decode": (0.2, 160)}),
                    inside=Emits([scan] * 20)))
    f, b = k.least(scan["kv_rows"], scan["kv_ctx_tokens"],
                   scan["kv_slot_tokens"], 32, 8, 64)
    assert only == pytest.approx(
        100 * 20 * (b / PEAKS["bytes_per_s"]) / 0.2)
    # nothing to read: no trace, no ids (the parent's program), no span,
    # neither kernel
    assert read(ctx()) is None
    assert read(ctx(trace=tr, inside=Emits([{"step_id": 1}]))) is None
    assert read(ctx(trace=tr, inside=None)) is None
    assert read(ctx(trace=Trace({}), inside=Emits([scan]))) is None


def test_both_readers_agree_with_benchmark_json():
    listed = {m["name"]: m for m in loader.benchmark_json()["per_layer"]}
    for name in ("short_conv_roofline", "gqa_d64_roofline"):
        mod, entry = loader.module("metrics", name), listed[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER, mod.SOURCE) == (
            entry["unit"], entry["layer"], entry["moves"], entry["better"],
            entry["source"])
        assert entry["workloads"] == [CELL]


def test_the_cells_files_are_found_by_the_names_benchmark_json_gives():
    cell = loader.Cell(CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == "agent_turns"
    assert cell.traffic["kind"] == "closed_clients"
    assert cell.traffic["clients"] == 128 == cell.config["engine"]["max_batch"]
    assert (cell.traffic["prompt"]["min"], cell.traffic["prompt"]["max"],
            cell.traffic["output"]["min"], cell.traffic["output"]["max"]) \
        == (256, 1024, 256, 768)
    # the longest request fits a slot: 1,024 + 768 of 2,048
    assert cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"] \
        <= cell.config["engine"]["max_seq_len"]
    assert cell.program().__name__.endswith("lfm2_moe")
    assert cell.reference().__name__.endswith("lfm2_moe_plain")
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert cell.config["num_experts"] == 64 and \
        cell.config["vocab_size"] == 65536
    plain, traced = cell.declared(False), cell.declared(True)
    assert set(plain) == {"serve_tok_s", "setup_s"}
    for name in ("short_conv_roofline", "gqa_d64_roofline",
                 "expert_matmul_roofline", "expert_weights_read_pct.batch",
                 "device_idle_pct.batch", "host_ms_per_step.batch"):
        assert name in traced
    assert len(cell.entry["why"]) <= 200
    # the toy cut is found too, and is this cell's
    small = toy.cell(CELL)
    assert small.config["hidden_size"] == 64
    assert small.config["layer_types"] == cell.config["layer_types"]


#: the digest and the line count of a family's plain forward at its toy
#: cut, as jax 0.9.0 prints its jaxpr, read at the parent of PR 48 (commit
#: 585c0a6) with the script these lines repeat
PARENTS = {
    "kimi_long_docs": ("12eba0cad6ae38fb", 2179),
    "dsv2_rag_answers": ("c5b3d65933a6f571", 1940),
    "brumby_doc_reports": ("6f5b2382540c341f", 32022),
    "solar_long_reports": ("30e541773a927dae", 1976),
    "dots3_long_answers": ("33aac6adf328a0aa", 4520),
}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digests are of jax 0.9.0's jaxprs")
@pytest.mark.parametrize("name", list(PARENTS))
def test_a_family_that_shares_the_layers_traces_to_what_it_traced(name):
    """``SparseMoE`` gained a shared width of 0 and an epsilon under the
    renormalised weights, ``StateCausalLM`` a tied head, ``route`` the
    epsilon: arguments the five families there leave at what they are, so
    that each traces the program it traced."""
    import paddle_tpu as paddle
    cell = toy.cell(name)
    with paddle.LazyGuard():
        model = cell.program().build(cell.config)
    model.eval()
    leaves = [p for _, p in model.named_parameters()]
    shapes = [jax.ShapeDtypeStruct(p._value.shape, jnp.float32)
              for p in leaves]

    def forward(vals, ids):
        for p, v in zip(leaves, vals):
            p._value = v
        with paddle.no_grad():
            return model(paddle.to_tensor(ids))._value
    # under the precision the digests were read at, whatever a suite that
    # runs beside this one has set (``tests/conftest.py``: "highest")
    with jax.default_matmul_precision(None):
        text = str(jax.make_jaxpr(forward)(
            shapes, jax.ShapeDtypeStruct((2, 40), jnp.int32)))
    assert (hashlib.sha256(text.encode()).hexdigest()[:16],
            len(text.splitlines())) == PARENTS[name]
