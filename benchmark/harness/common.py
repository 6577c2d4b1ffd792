"""What the serving and the training runner share: the look for the chip,
the program's model with seeded weights, compilations counted, the traced
stretch of the window, and the device's line of the result."""
from __future__ import annotations

import os
import shutil
import threading
import time

from . import loader, weights
from .trace import WINDOW


class NoChip(RuntimeError):
    pass


def find_devices(chips, require_chip=True):
    """The devices the cell runs on. Without ``require_chip`` (tests
    only) whatever jax has is taken, and the result says so."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"jax found platform {devs[0].platform!r} "
                         f"({kind}); the benchmark measures on a TPU only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax found "
                     f"{len(devs)}")
    return devs[:chips]


def peaks_of(kind):
    table = loader.peaks()["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts the programs jax lowers (cache hit or miss) and the seconds
    it spends compiling, so that the window can show it compiled
    nothing."""

    def __init__(self):
        import jax.monitoring
        self.lowered, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1
        if "/compile/" in event:
            self.seconds += duration

    def snapshot(self):
        return self.lowered, self.seconds


def llama_config(cfg):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        use_recompute=bool(cfg.get("train", {}).get("use_recompute", False)))


def build_model(cfg, seed, reference, mesh=None, axis="tp"):
    """The program's model, built abstract (no float32 copy is ever made)
    and given bfloat16 weights made on the device from the seed, laid out
    over ``mesh`` as they are made. The leaves have to be exactly those
    the plain reference describes for this configuration."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    with paddle.LazyGuard():
        model = LlamaForCausalLM(llama_config(cfg))
    named = list(model.named_parameters())
    mine = {n: tuple(p._value.shape) for n, p in named}
    theirs = {n: tuple(s) for n, s in reference.specs(cfg)}
    if mine != theirs:
        odd = sorted(set(mine.items()) ^ set(theirs.items()))[:6]
        raise RuntimeError(f"the program's leaves are not the "
                           f"reference's: {odd}")
    shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from paddle_tpu.models.llama import llama_tp_spec
        shardings = [NamedSharding(mesh, llama_tp_spec(n, axis=axis))
                     for n, _ in named]
    vals = weights.make(seed, [(n, mine[n]) for n, _ in named],
                        jnp.bfloat16, shardings)
    for (_, p), v in zip(named, vals):
        p._value = v
    return model


def memory_peak_bytes(devices):
    """The peak on the fullest chip, as the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def device_line(devices, memory_peak):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak)}


class TracedStretch:
    """Traces a short steady stretch inside the window from a thread of its
    own, so the load goes on while the profile is written. The stretch is
    one ``bench_window`` annotation: the window and the device intervals
    are then on one clock. The Python tracer is off: a serving loop with it
    on writes gigabytes in seconds."""

    def __init__(self, log_dir, start_at, seconds, clock=time.perf_counter,
                 probe=None):
        self.log_dir, self.start_at, self.seconds = log_dir, start_at, seconds
        self._clock, self._probe = clock, probe
        self.error = None
        self.t0 = self.t1 = None
        #: what ``probe()`` returned at the stretch's two ends
        self.snap0 = self.snap1 = None
        shutil.rmtree(log_dir, ignore_errors=True)
        os.makedirs(log_dir, exist_ok=True)
        self._thread = threading.Thread(target=self._run, name="bench-trace",
                                        daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            delay = self.start_at - self._clock()
            if delay > 0:
                time.sleep(delay)
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(WINDOW):
                    self.t0 = self._clock()
                    if self._probe is not None:
                        self.snap0 = self._probe()
                    time.sleep(self.seconds)
                    if self._probe is not None:
                        self.snap1 = self._probe()
                    self.t1 = self._clock()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported by join(): the run then fails
            self.error = e

    def join(self, timeout=300):
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop in time")
        self._probe = None       # it holds the engine: let that go
        if self.error is not None:
            raise RuntimeError(f"tracing failed: {self.error!r}")
