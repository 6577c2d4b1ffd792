"""``brumby_doc_reports``' engine with nothing on any device, and its step
programs compiled for a v5e that is described and not attached (the
``on-chip-measurement`` guide's third rehearsal; ``test_aot_ouro.py``'s
manner). A helper, not a test file: ``test_aot_brumby.py`` here and the
one tier-1 case in ``tests/test_brumby.py`` share it. Nothing in it runs
while a module is imported."""
import re

import jax
import jax.numpy as jnp

from benchmark.harness import loader

#: a (slot, layer) state of the shipped configuration, bytes
STATE_BYTES = 34_080_768


def describe_one_chip():
    """A ``SingleDeviceSharding`` on the first chip of a described
    v5e:2x2 (raises where none can be described: the caller skips)."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def engine(one_chip, **over):
    """(engine, {program name: its raw ``jax.jit``}, {name: arguments})
    of the cell's configuration: the model's leaves, the states and every
    buffer are shapes on the described chip."""
    import pytest

    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    cfg = loader.data("configs", "brumby-14b-base-d8")

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=one_chip)
    with paddle.LazyGuard():
        model = loader.module("programs", "brumby").build(cfg)
    model.eval()
    for _, p in model.named_parameters():
        p._value = shape(p._value.shape, jnp.bfloat16)
    raw, mp = {}, pytest.MonkeyPatch()
    orig = LLMEngine._program
    mp.setattr(LLMEngine, "_program", lambda self, name, fn: (
        raw.__setitem__(name, fn), orig(self, name, fn))[1])
    mp.setattr(LLMEngine, "_make_zeros",
               lambda self, s, dtype, spec=None: shape(s, dtype))
    try:
        eng = LLMEngine(model, **dict(cfg["engine"], **over))
        eng._programs()
        eng._multi_fn(int(cfg["engine"]["readout_stride"]))
    finally:
        mp.undo()
    b, chunk = eng.B, eng.chunk
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    i32, f32 = jnp.int32, jnp.float32
    head = ([shape(v.shape, v.dtype) for v in eng._state_vals], eng._k,
            eng._v, eng._logits, eng._lens)
    tables = shape(eng._tables.shape, i32)
    mixed = head + (key, shape((b, chunk), i32), shape((b,), i32),
                    shape((b,), bool), shape((b,), bool), shape((b,), f32),
                    shape((b,), f32), shape((b,), i32), tables)
    decode = head + (shape((b,), bool), key, shape((b,), f32),
                     shape((b,), f32), shape((b,), i32), shape((b,), i32),
                     shape((b,), i32), tables)
    return eng, raw, {"fused_step": mixed, "step": decode,
                      "multi_step": decode}


def compile_for_the_chip(raw, args, name):
    """``name`` compiled by the TPU compiler installed here, outside the
    persistent cache (it could not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return raw[name].trace(*args[name]).lower(
            lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(")


def buffers_of_shape(text, shape):
    """[(opcode, line)] of every instruction of the compiled module
    ``text`` that MAKES an array of ``shape`` (``"f32[16,8,8256,128]"``)
    in a computation that is not the body of a fusion: an instruction
    inside a fusion's body makes no buffer of its own."""
    fused = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", text))
    out, inside = [], None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            inside = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m and inside not in fused and m.group(1).startswith(shape):
            out.append((m.group(2), line.strip()))
    return out


def held_in_place(compiled, eng):
    """What both test files hold a compiled step program to. Returns the
    program's (arguments, temporaries) in bytes."""
    text, mem = compiled.as_text(), compiled.memory_analysis()
    b = eng.B
    state = b * len(eng._layout) * STATE_BYTES
    made = buffers_of_shape(text, f"f32[{b},8,8256,128]")
    assert made, "no instruction of the program makes a state"
    for op, line in made:
        # the state comes in, is handed on, and is updated where it lies
        # by the core (a fusion or a dynamic-update-slice under
        # self_attn/pt.core): nothing copies it
        assert op in ("parameter", "get-tuple-element", "while", "bitcast",
                      "fusion", "dynamic-update-slice"), line
        if op in ("fusion", "dynamic-update-slice"):
            assert "self_attn/pt.core" in line, line
    # the states go out where they came in
    assert mem.alias_size_in_bytes >= state
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < loader.peaks()["devices"]["TPU v5 lite"]["memory_bytes"]
    return mem.argument_size_in_bytes, mem.temp_size_in_bytes
