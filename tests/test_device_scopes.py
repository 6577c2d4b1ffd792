"""Device scopes (``paddle_tpu.profiler.scope``): the model tree, written by
``Layer.__call__``, and the ``pt.<part>`` vocabulary ride on every XLA
operation of the step programs as its ``op_name``, and the benchmark's
reader (``benchmark/harness/components.py``) turns such a path into a
component.

No profile is taken here: each family's tiny step programs and a tiny
``TrainStep`` are compiled on the CPU and their HLO is read as the reader
reads a profile's (``components.module_scopes``), so what is held is what
a trace would show: at least 95 % of the operations carry a scope, and each
component the table maps for the family is there."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from benchmark.harness import components as C
from paddle_tpu.jit.api import TrainStep
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import scope

import test_deepseek_v2 as DSV2
import test_kimi_linear as KIMI
import test_ouro as OURO

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: what is no operation of its own in a trace
TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


# ---- the path parser, on literal strings ----------------------------------

@pytest.mark.parametrize("op_name,tokens,backward", [
    ("jit(f)/transpose(jvp(self_attn))/pt.core/mul",
     ("self_attn", "pt.core"), True),
    ("jit(f)/jvp(self_attn)/pt.core/tanh", ("self_attn", "pt.core"), False),
    ("jit(multi_step)/while/body/llama/1/input_layernorm/mul",
     ("llama", "#", "input_layernorm"), False),
    ("jit(step_fn)/jvp(LlamaForCausalLM)/llama/checkpoint/3/mlp/down_proj/"
     "dot_general", ("LlamaForCausalLM", "llama", "#", "mlp", "down_proj"),
     False),
    ("jit(fused_step)/model/layers/12/mlp/pt.experts/jit(_ffn_call)/"
     "pallas_call", ("model", "layers", "#", "mlp", "pt.experts"), False),
    ("jit(fused_step)/jit(_where)/select_n", (), False),
    ("jit(fused_step)/cond/branch_1_fun/pt.sample/sort", ("pt.sample",),
     False),
    ("jit(step_fn)/transpose(jvp(LlamaForCausalLM))/pt.loss/vmap(mul)",
     ("LlamaForCausalLM", "pt.loss"), True),
    ("select_n", (), False),
    ("", (), False),
])
def test_a_path_parses_to_its_scope_tokens(op_name, tokens, backward):
    assert C.parse(op_name) == (tokens, backward)


@pytest.mark.parametrize("tokens,want", [
    ((), "unnamed"),
    (("llama", "#", "self_attn", "pt.core"), "mixer.core"),
    (("llama", "#", "self_attn", "pt.core", "pt.view"), "mixer.core"),
    (("llama", "#", "self_attn", "q_proj"), "mixer.proj"),
    (("model", "#", "self_attn", "kv_b_proj"), "mixer.proj"),
    (("llama", "#", "self_attn", "pt.rope"), "mixer.other"),
    (("model", "#", "self_attn", "pt.conv"), "mixer.other"),
    (("llama", "#", "mlp", "down_proj"), "ffn"),
    (("model", "#", "mlp", "pt.experts"), "ffn.experts"),
    (("model", "#", "mlp", "pt.route"), "ffn.route"),
    (("llama", "#", "input_layernorm"), "block"),
    (("llama", "#"), "block"),
    (("llama", "embed_tokens"), "embed"),
    (("llama", "norm"), "head"),
    (("lm_head",), "head"),
    (("pt.sample",), "sample"),
    (("pt.pack",), "pack"),
    (("pt.readout",), "readout"),
    (("model", "pt.exit"), "exit"),
    (("LlamaForCausalLM", "pt.loss"), "loss"),
    (("pt.optimizer",), "optimizer"),
    (("pt.optimizer", "pt.clip"), "clip"),
    (("model",), "other"),
])
def test_the_one_table_maps_tokens_to_a_component(tokens, want):
    assert C.component(tokens) == want


# ---- Layer.__call__ ---------------------------------------------------------

class Block(nn.Layer):
    def __init__(self, shared=None):
        super().__init__()
        self.q_proj = shared if shared is not None else nn.Linear(4, 4)
        self.towers = nn.LayerList([nn.Linear(4, 4), nn.Linear(4, 4)])

    def forward(self, x):
        x = self.q_proj(x)
        for layer in self.towers:
            x = layer(x)
        return x


def _paths(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _call(layer):
    def fn(v):
        with paddle.no_grad():
            return layer(paddle.Tensor(v))._value
    return fn


def test_a_layer_runs_under_the_name_its_parent_registered_it_under():
    paddle.seed(0)
    block = Block()
    assert (block._scope_name, block.q_proj._scope_name) == (None, "q_proj")
    paths = _paths(_call(block), jnp.ones((2, 4)))
    # the root reads as its class, a child as its attribute's name
    assert any(p.startswith("jit(fn)/Block/q_proj/") for p in paths), paths


def test_a_layer_lists_child_runs_under_its_index():
    paddle.seed(0)
    block = Block()
    assert [t._scope_name for t in block.towers] == ["0", "1"]
    paths = _paths(_call(block), jnp.ones((2, 4)))
    # the list itself is never called: the index follows the parent
    assert any(p.startswith("jit(fn)/Block/1/") for p in paths), paths
    assert C.parse("jit(fn)/Block/1/dot_general")[0] == ("Block", "#")


def test_a_layer_shared_by_two_parents_keeps_its_first_name():
    paddle.seed(0)
    first = Block()
    second = nn.Sequential(("again", first.q_proj))
    second.renamed = first.q_proj
    assert first.q_proj._scope_name == "q_proj"
    paths = _paths(_call(second), jnp.ones((2, 4)))
    assert any("/q_proj/" in p for p in paths) and \
        not any("/again/" in p or "/renamed/" in p for p in paths)


def test_hooks_still_run_inside_the_layers_scope():
    paddle.seed(0)
    lin, seen = nn.Linear(4, 4), []
    lin.register_forward_pre_hook(lambda l, a: seen.append("pre"))
    lin.register_forward_post_hook(
        lambda l, a, out: seen.append("post") or out * 2)
    x = paddle.ones([2, 4])
    doubled = lin(x)
    assert seen == ["pre", "post"]
    np.testing.assert_array_equal(np.asarray(doubled._value),
                                  2 * np.asarray(lin.forward(x)._value))


def test_an_eager_call_is_bit_equal_under_an_enclosing_scope():
    paddle.seed(0)
    block, x = Block(), paddle.ones([3, 4])
    plain = np.asarray(block(x)._value)
    with scope("outer"), scope("pt.core"):
        scoped = np.asarray(block(x)._value)
    np.testing.assert_array_equal(plain, scoped)


def test_static_name_scope_goes_through_the_one_function():
    import paddle_tpu.static as static

    def fn(v):
        with static.name_scope("tower"):
            return jnp.tanh(v) * 2
    assert any("/tower/" in p for p in _paths(fn, jnp.ones((4,))))


def test_named_scope_is_called_through_profiler_scope_alone():
    hits = []
    for base, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as fh:
                    if "named_scope(" in fh.read():
                        hits.append(os.path.relpath(path, ROOT))
    assert hits == ["paddle_tpu/profiler/_span.py"]


def test_the_compile_caches_key_takes_the_scopes_in():
    """An executable carries the metadata it was compiled with, so one
    cached by a tree without scopes must not be loaded by one with."""
    assert jax.config.jax_compilation_cache_include_metadata_in_key


# ---- the step programs ------------------------------------------------------

def table_of(compiled):
    """(share of the operations that carry a scope, {component: count},
    {leaf scope}, {component with a backward operation}) of a compiled
    program, read as the benchmark reads a profile's HLO. A copy without a
    path is XLA:CPU's own (a donated buffer into a result) and is left
    out with the parameters."""
    proto = compiled.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    comps, leaves, backward = {}, set(), set()
    for op, _, opcode, event in C.module_scopes(proto).values():
        tokens, bw = C.parse(op)
        if not event or opcode in TRIVIAL or \
                (opcode == "copy" and not tokens):
            continue
        comp = C.component(tokens)
        comps[comp] = comps.get(comp, 0) + 1
        leaves.add(C.leaf(tokens))
        if bw:
            backward.add(comp)
    n = sum(comps.values())
    return (n - comps.get("unnamed", 0)) / n, comps, leaves, backward


def _llama():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2))
    model.eval()
    return model, dict(max_seq_len=128)


STEP = {"sample", "readout", "embed", "block", "head"}
MIXER = {"mixer.core", "mixer.proj", "mixer.other"}
STAGES = {"ffn.route", "ffn.dispatch", "ffn.experts", "ffn.shared",
          "ffn.combine"}
FAMILIES = {
    "llama": (_llama, MIXER | {"ffn"}, {"pt.core", "pt.rope", "pt.view"}),
    "kimi_linear": (lambda: (KIMI.build(KIMI.TOY, 1)[0], {}),
                    MIXER | STAGES | {"ffn"},
                    {"pt.core", "pt.conv", "pt.gate", "pt.view", "qkv_proj",
                     "kv_b_proj"}),
    "deepseek_v2": (lambda: (DSV2.build(DSV2.TOY, 1)[0], {}),
                    MIXER | STAGES | {"ffn"},
                    {"pt.core", "pt.view", "q_proj", "kv_a_proj", "kv_b_proj"}),
    "ouro": (lambda: (OURO.build(OURO.TOY, 1)[0], {}),
             MIXER | {"ffn", "exit"}, {"pt.core", "pt.rope"}),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def programs(request):
    make, comps, leaves = FAMILIES[request.param]
    model, over = make()
    raw, args = OURO._raw_programs(model, **over)
    return raw, args, comps, leaves


@pytest.mark.parametrize("name", ["fused_step", "multi_step"])
def test_a_step_programs_operations_carry_their_component(programs, name):
    """The mixed step and the decode scan of each family: the operations
    carry a scope and every component of the family's table is there (in
    the scan, the mixed step's packing is not)."""
    raw, args, comps, leaves = programs
    share, seen, seen_leaves, backward = table_of(
        raw[name].lower(*args[name]).compile())
    assert share >= 0.95, (share, seen)
    want = comps | STEP | ({"pack"} if name == "fused_step" else set())
    assert want <= set(seen), sorted(want - set(seen))
    assert leaves <= seen_leaves, sorted(leaves - seen_leaves)
    assert not backward


def test_a_train_steps_operations_carry_forward_and_backward():
    paddle.seed(7)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128))
    model.train()
    step = TrainStep(
        model, lambda m, ids, lbl: m(ids, labels=lbl)[0],
        opt.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                  grad_clip=nn.ClipGradByGlobalNorm(1.0)))
    ids = paddle.Tensor(jnp.ones((2, 16), jnp.int32))
    share, seen, leaves, backward = table_of(step.aot_compile(ids, ids))
    assert share >= 0.95, (share, seen)
    assert MIXER | {"ffn", "embed", "block", "head", "loss", "optimizer",
                    "clip"} <= set(seen), seen
    # the backward of a component reads ``transpose(jvp(...))``
    assert {"mixer.core", "mixer.proj", "ffn", "block", "head",
            "loss"} <= backward, backward
    assert not {"optimizer", "clip"} & backward


# ---- the sampling prologue's conditional ------------------------------------

_CALLED = re.compile(r"(?:to_apply|calls|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
#: what only the filtered categorical does: the sort over the vocabulary,
#: the nucleus' cumulative sum, the keys and their bits
_DRAWS = re.compile(r"sort|cumsum|threefry|random_|rng-bit")


def sampling_branches(text):
    """(the greedy branch's lines, the sampling branch's lines, every
    computation's lines) of the ONE ``conditional`` under ``pt.sample`` in
    an HLO module's text (metadata printed): a branch is its computation
    with everything that computation calls."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)

    def names(group):
        return [c.strip().lstrip("%") for c in group.split(",")]

    def reach(name):
        seen, todo = set(), [name]
        while todo:
            c = todo.pop()
            if c not in seen:
                seen.add(c)
                for line in comps[c]:
                    todo.extend(_CALLED.findall(line))
                    for group in _BRANCHES.findall(line):
                        todo.extend(names(group))
        return [line for c in sorted(seen) for line in comps[c]]
    (gate,) = [line for lines in comps.values() for line in lines
               if " conditional(" in line and "/pt.sample/cond" in line]
    greedy, sampling = map(reach, names(_BRANCHES.search(gate).group(1)))
    return greedy, sampling, comps


@pytest.mark.parametrize("name", ["fused_step", "multi_step"])
def test_a_step_programs_sort_sits_in_one_branch_of_a_conditional(
        programs, name):
    """``sample_next`` takes the filtered categorical behind ONE
    ``lax.cond`` on the step's ``temps``: the lowered program holds a real
    ``conditional`` under ``pt.sample`` whose one branch holds the sort
    over the vocabulary (every ``pt.sample`` sort of the program is there)
    and whose other branch holds the argmax and no sort, no cumulative sum
    and no random bits."""
    raw, args, _, _ = programs
    greedy, sampled, comps = sampling_branches(
        raw[name].lower(*args[name]).compiler_ir(dialect="hlo")
        .get_hlo_module().to_string())
    assert not [line for line in greedy if _DRAWS.search(line)]
    assert any("argmax" in line for line in greedy)
    assert any(" sort(" in line for line in sampled)
    for what in ("sort", "cumsum", "threefry"):
        assert any("/pt.sample/cond/branch_1_fun/" in line and what in line
                   for line in sampled), what
    # and nowhere else does the program sort under ``pt.sample``
    inside = set(sampled)
    assert not [line for lines in comps.values() for line in lines
                if "sort" in line and "pt.sample" in line
                and line not in inside]
