"""chip_smoke.py on CPU: it refuses to run without a chip, its phases hold
at toy size with the Pallas kernels in interpret mode, and the compile cache
lands where it is told (paddle_tpu/__init__.py::_configure_compile_cache)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
           num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2)


def _run(code_or_script, env_extra, *args):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    return subprocess.run([sys.executable, *code_or_script, *args], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=120)


def test_refuses_cpu_and_says_why():
    proc = _run(["chip_smoke.py"], {})
    assert proc.returncode != 0
    assert "no TPU" in proc.stdout and "'cpu'" in proc.stdout
    # no result line: the contract's JSON object only ever follows a pass
    assert '"ok"' not in proc.stdout
    # with JAX_COMPILATION_CACHE_DIR unset the cache is in the checkout
    assert f"compile cache {os.path.join(REPO, '.jax_cache')}" in proc.stdout


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """Route the toy phases through the Pallas kernels (interpret mode on
    this backend) instead of the CPU fallbacks tier-1 otherwise takes."""
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.ops.kernels import paged_attention
    monkeypatch.setattr(attention, "_use_pallas", lambda q: True)
    monkeypatch.setattr(paged_attention, "paged_attention_enabled",
                        lambda: True)


def test_train_phase_toy_tp(kernels_interpreted, monkeypatch):
    """The train phase with weights laid out by llama_tp_spec (the
    multichip item; the one-chip phase is the same code with no ``place``):
    the flash kernel must go through its shard_map wrapper over the heads'
    axis — GSPMD cannot partition a Mosaic call, and only the chip would
    tell otherwise — and the step must still train."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.ops.kernels import flash_attention

    axes = []
    wrapped = flash_attention.flash_attention_tp

    def spy(*args, **kwargs):
        axes.append(kwargs["axis"])
        return wrapped(*args, **kwargs)
    monkeypatch.setattr(flash_attention, "flash_attention_tp", spy)
    cfg = LlamaConfig(max_position_embeddings=32, use_recompute=True,
                      **dict(TOY, num_attention_heads=4,
                             num_key_value_heads=4))
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("mp",))
    with chip_smoke.lowered_programs() as read:
        losses = chip_smoke.train_phase(
            cfg, batch=2, seq=32, steps=4,
            place=chip_smoke.tp_train_place(mesh))
        programs = read()
    assert axes and set(axes) == {"mp"}
    assert len(losses) == 4 and losses[-1] < losses[0]
    # the step program was dumped; interpreted kernels are plain XLA ops,
    # so the evidence check main() demands on the chip must fail here
    assert any("step_fn" in name for name in programs)
    with pytest.raises(RuntimeError, match="flash_attention_fwd"):
        chip_smoke.require_kernels(chip_smoke.mosaic_calls(programs),
                                   ["flash_attention_fwd"])


def test_serve_phase_toy(kernels_interpreted):
    from paddle_tpu.models import LlamaConfig
    cfg = LlamaConfig(max_position_embeddings=64, **TOY)
    engine_kw = dict(max_batch=2, scheduler="fused", cache_impl="paged",
                     block_size=8, chunk_size=16, readout_stride=2)
    served, ref = chip_smoke.serve_phase(
        cfg, n_requests=3, prompt_lo=9, prompt_hi=20, new_tokens=6,
        engine_kw=engine_kw)
    assert len(served) == len(ref) == 3
    assert all(len(s) == 6 for s in served)


def test_mosaic_calls_reads_kernel_names():
    text = ('stablehlo.custom_call @tpu_custom_call(%0) {backend_config = '
            '"...", kernel_name = "paged_attention_decode"}\n'
            'stablehlo.custom_call @tpu_custom_call(%1) {kernel_name = '
            '"paged_attention_decode"}')
    calls = chip_smoke.mosaic_calls({"jit_multi_step.mlir": text,
                                     "jit_other.mlir": "no kernels here"})
    assert calls == {"paged_attention_decode": {"jit_multi_step.mlir": 2}}
    chip_smoke.require_kernels(calls, ["paged_attention_decode"])


_JIT_ONE = """
import jax, jax.numpy as jnp
import paddle_tpu
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_follows_env(tmp_path):
    target = str(tmp_path / "cache")
    before = set(os.listdir(os.path.join(REPO, ".jax_cache"))) \
        if os.path.isdir(os.path.join(REPO, ".jax_cache")) else set()
    entries = []
    for _ in range(2):
        proc = _run(["-c", _JIT_ONE], {"JAX_COMPILATION_CACHE_DIR": target})
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip().splitlines()[-1] == target
        entries.append(sorted(os.listdir(target)))
    assert entries[0], "no cache entry written where the env says"
    assert entries[1] == entries[0], "the second process missed the cache"
    after = set(os.listdir(os.path.join(REPO, ".jax_cache"))) \
        if os.path.isdir(os.path.join(REPO, ".jax_cache")) else set()
    assert after == before, "an entry leaked into the checkout's cache"
