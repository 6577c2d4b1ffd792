"""The reader of device time by component
(``benchmark/harness/components.py``): the table adds up to the
operations' total on a synthetic set of events; the wire-format reader finds
a compiled program's scopes, names a fusion by its root and what XLA made
for another operation by that one; and a toy cell's CPU profile carries the
HLO the reader needs, so the rehearsal reads a value for every new metric
(never a measurement: the platform is the CPU)."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import common, components as C, main, output
from benchmark.harness.readers import SERVE_MODULES, TRAIN_MODULES
from benchmark.harness.trace import MODULES_LINE, OPS_LINE, Trace
from benchmark.tests import toy

MS = 1_000_000          # ns


def own(op):
    return (op, True, "fusion", True)


#: two serving program kinds and a small program beside them
SCOPES = {
    "jit_fused_step(11)": {
        "paged_attention_append.3": own(
            "jit(fused_step)/llama/3/self_attn/pt.core/"
            "paged_attention_append/pallas_call"),
        "fusion.7": own("jit(fused_step)/llama/3/self_attn/q_proj/"
                        "dot_general"),
        "fusion.8": own("jit(fused_step)/llama/3/self_attn/pt.rope/mul"),
        "fusion.9": own("jit(fused_step)/llama/3/mlp/down_proj/dot_general"),
        "sort.1": own("jit(fused_step)/pt.sample/vmap(jit(sort))/sort"),
        "fusion.10": own("jit(fused_step)/llama/3/input_layernorm/mul"),
        # what XLA made for the projection: its path is the user's
        "copy.4": ("jit(fused_step)/llama/3/self_attn/q_proj/dot_general",
                   False, "copy", True),
        "copy.5": ("", False, "copy", True),
        "while.2": ("jit(fused_step)/llama/while", True, "while", True),
    },
    "jit_multi_step(12)": {
        "paged_attention_decode.1": own(
            "jit(multi_step)/while/body/llama/0/self_attn/pt.core/"
            "paged_attention_decode/pallas_call"),
        "fusion.7": own("jit(multi_step)/while/body/llama/0/mlp/gate_proj/"
                        "dot_general"),
    },
}


def synthetic():
    """A window of 100 ms: two mixed steps and one scan, a program that is
    no step program, a container around an operation, an operation that
    hangs out of the window."""
    mods = [(0, 20 * MS, "jit_fused_step(11)"),
            (20 * MS, 40 * MS, "jit_fused_step(11)"),
            (40 * MS, 70 * MS, "jit_multi_step(12)"),
            (70 * MS, 71 * MS, "jit_set_len(13)")]
    ops = []
    for at in (0, 20 * MS):
        ops += [(at, at + 8 * MS, "paged_attention_append.3"),
                (at + 8 * MS, at + 10 * MS, "fusion.7"),
                (at + 10 * MS, at + 11 * MS, "fusion.8"),
                (at + 11 * MS, at + 15 * MS, "fusion.9"),
                (at + 15 * MS, at + 16 * MS, "sort.1"),
                (at + 16 * MS, at + 17 * MS, "fusion.10"),
                (at + 17 * MS, at + 18 * MS, "copy.4"),
                (at + 18 * MS, at + 19 * MS, "copy.5"),
                # a container: its body's operations are on the line too
                (at, at + 19 * MS, "while.2")]
    ops += [(40 * MS, 60 * MS, "paged_attention_decode.1"),
            (60 * MS, 70 * MS, "fusion.7"),
            (70 * MS, 71 * MS, "dynamic-update-slice.1"),
            (99 * MS, 101 * MS, "fusion.7")]
    return Trace({0: {OPS_LINE: ops, MODULES_LINE: mods}}, [],
                 (0, 100 * MS))


def test_the_table_adds_up_to_the_operations_total():
    table = C.build(synthetic(), SCOPES, SERVE_MODULES)
    assert set(table.kinds) == {"jit_fused_step", "jit_multi_step",
                                "jit_set_len"}
    assert table.programs() == 3
    fused = table.kinds["jit_fused_step"]
    assert fused.calls == 2 and fused.op_secs == pytest.approx(0.038)
    parts = {name: table.ms_a_program(*comps) for name, comps in (
        ("core", ("mixer.core",)), ("proj", ("mixer.proj",)),
        ("other", ("mixer.other",)), ("ffn", ("ffn",)),
        ("step", C.STEP_OTHER), ("unnamed", ("unnamed",)))}
    assert parts == pytest.approx({
        "core": (16 + 20) / 3, "proj": (4 + 2) / 3, "other": 2 / 3,
        "ffn": (8 + 10) / 3, "step": (2 + 2) / 3, "unnamed": 2 / 3})
    # ... which is every operation of the step programs, the container,
    # the small program and what hangs out of the window left out
    assert sum(parts.values()) == pytest.approx(
        1e3 * table.seconds() / table.programs()) == pytest.approx(68 / 3)
    assert table.named_pct() == pytest.approx(100 * 66 / 68)
    # the copy that serves the projection counts there, and as inherited
    assert fused.inherited == pytest.approx(0.002)
    assert fused.by_component()["mixer.proj"][1] == 4


def test_a_backward_operation_is_told_apart():
    scopes = {"jit_step_fn(5)": {
        "fusion.1": own("jit(step_fn)/jvp(LlamaForCausalLM)/lm_head/"
                        "dot_general"),
        "fusion.2": own("jit(step_fn)/transpose(jvp(LlamaForCausalLM))/"
                        "lm_head/dot_general"),
        "fusion.3": own("jit(step_fn)/transpose(jvp(LlamaForCausalLM))/"
                        "pt.loss/mul"),
        "fused_adamw.1": own("jit(step_fn)/pt.optimizer/fused_adamw/"
                             "pallas_call")}}
    ops = [(0, 3 * MS, "fusion.1"), (3 * MS, 9 * MS, "fusion.2"),
           (9 * MS, 10 * MS, "fusion.3"), (10 * MS, 12 * MS,
                                           "fused_adamw.1")]
    trace = Trace({0: {OPS_LINE: ops,
                       MODULES_LINE: [(0, 12 * MS, "jit_step_fn(5)")]}},
                  [], (0, 20 * MS))
    table = C.build(trace, scopes, TRAIN_MODULES)
    assert table.ms_a_program("head", "loss") == pytest.approx(10.0)
    assert table.seconds(lambda comp, bw: bw) == pytest.approx(0.007)
    rows = table.kinds["jit_step_fn"].rows
    assert ("head", "lm_head", True) in rows and \
        ("head", "lm_head", False) in rows


def test_a_program_without_scopes_gives_none_and_says_why(capsys):
    scopes = {"jit_fused_step(11)": {
        name: ("jit(fused_step)/jit(main)/mul", True, "fusion", True)
        for name in SCOPES["jit_fused_step(11)"]}}
    table = C.build(synthetic(), scopes, SERVE_MODULES)
    assert table.seconds(lambda c, _: c not in ("unnamed", "other")) == 0

    class Cell:
        trace_dir = "unused"
    ctx = {"trace": synthetic(), "kind": "serve", "cell": Cell()}
    orig_scopes, orig_path = C.hlo_scopes, C.newest_xplane
    C.hlo_scopes, C.newest_xplane = lambda path: scopes, lambda d: d
    try:
        assert C.components(ctx) is None
        assert C.named_pct(ctx) is None
        assert C.device_ms("ffn")(ctx) is None
    finally:
        C.hlo_scopes, C.newest_xplane = orig_scopes, orig_path
    assert "carries a scope the table knows" in capsys.readouterr().out


def test_a_compiled_programs_scopes_are_read_from_its_hlo():
    from paddle_tpu.profiler import scope

    def f(x, w):
        def body(c, _):
            with scope("self_attn"), scope("pt.core"):
                y = jnp.tanh(c @ w)
            with scope("mlp"):
                y = jnp.sin(y) @ w.T
            return y, None
        y, _ = jax.lax.scan(body, x, None, length=3)
        with scope("pt.loss"):
            return (y ** 2).mean()
    x = jnp.ones((64, 64))
    compiled = jax.jit(jax.value_and_grad(f, argnums=1)).lower(x, x) \
        .compile()
    proto = compiled.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    names = C.module_scopes(proto)
    seen = {(C.component(C.parse(op)[0]), C.parse(op)[1])
            for op, _, opcode, event in names.values()
            if event and opcode not in ("parameter", "constant", "tuple",
                                        "get-tuple-element")}
    # forward and backward of both, inside the loops' bodies
    assert {("mixer.core", False), ("mixer.core", True), ("ffn", False),
            ("ffn", True), ("loss", False)} <= seen
    # a fusion is named (by its own metadata or its root's), and the
    # loop's own counter, which no scope holds, stays without a path
    fusions = [v for v in names.values() if v[2] == "fusion" and v[3]]
    assert any(own and C.component(C.parse(op)[0]) == "mixer.core"
               for op, own, *_ in fusions)
    assert any(not C.parse(op)[0] for op, *_ in names.values())


def test_the_toy_cells_cpu_profile_gives_every_new_metric(monkeypatch):
    """Source (b) holds on the CPU too: the profile carries the modules'
    HLO, so the rehearsal reads the seven metrics, and in the serving
    cell they add up to the operations' total with the projections and
    the unnamed."""
    monkeypatch.setattr(common, "memory_peak_bytes", lambda devices: 1)
    new = {"serve": {"device_named_pct.batch", "mixer_core_device_ms.batch",
                     "mixer_other_device_ms.batch", "ffn_device_ms.batch",
                     "step_other_device_ms.batch"},
           "train": {"device_named_pct.train", "head_loss_device_ms.train"}}
    for name, kind in (("doc_batch", "serve"), ("pretrain_2k", "train")):
        cell = toy.cell(name)
        obj, declared = main.run_cell(
            cell, 2 ** 31 + 91, 1.5, 1, time.perf_counter(),
            require_chip=False, peaks=toy.PEAKS, load_trace=toy.cpu_trace)
        line = json.loads(output.dumps(obj, declared, True, cell.chips))
        assert line["device"]["platform"] == "cpu"
        got = {n: m["value"] for n, m in line["metrics"].items()}
        assert new[kind] <= set(got), sorted(new[kind] - set(got))
        assert 0 < got[f"device_named_pct.{'batch' if kind == 'serve' else 'train'}"] <= 100
