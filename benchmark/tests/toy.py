"""Toy-size cells for the CPU rehearsals: the real cells' files with the
sizes cut, so that the whole of a run — build, warm, window, drain, check,
trace, reduce, last line — is driven without a chip. Never a measurement:
the result says ``platform: cpu`` and the measurement path refuses it."""
import copy

from benchmark.harness import loader

MODEL = dict(hidden_size=128, intermediate_size=352, num_attention_heads=4,
             num_key_value_heads=2, vocab_size=1024,
             max_position_embeddings=512)
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "memory_bytes": 1 << 34}


def cell(name, variant=None):
    """``variant``: None for the cell as shipped, or one of the harness's
    paths that no shipped cell takes yet, cut from the same files:
    ``"open"`` (scheduled arrivals) and ``"four_chips"`` (the tensor-
    parallel engine over four devices)."""
    c = loader.Cell(name)
    if variant == "four_chips":
        c.chips = 4
    c.config = copy.deepcopy(c.config)
    c.traffic = copy.deepcopy(c.traffic)
    c.file = copy.deepcopy(c.file)
    c.config.update(MODEL)
    c.config["num_hidden_layers"] = 2
    if c.chips == 4:
        c.config["num_key_value_heads"] = 4   # one KV head a chip
    if "engine" in c.config:
        c.config["engine"].update(max_batch=4, chunk_size=32, block_size=16,
                                  max_seq_len=256)
    if "train" in c.config:
        c.config["train"].update(batch=4, seq=64, warm_steps=1)
    t = c.traffic
    if "prompt" in t:
        t["prompt"].update(min=8, max=120, median=40)
        t["output"].update(min=4, max=16, median=8)
        t.update(warm_s=0.5, drain_s=60)
    if variant == "open":
        t.update(kind="open_poisson", rate_rps=6.0, cycle=8)
    if t["kind"] == "closed_clients":
        t["clients"] = 3
    # the limits of ``correct`` are set at the timed sizes, on the chip;
    # these hold the toy sizes on a CPU (read over five seeds: the program
    # <= 0.0025 / 0.00012 and the fp8 control >= 0.02 / 0.0013 in serving)
    # (in training <= 3.3e-5 / 0.0021 / 0.0009; a toy's float32 CPU run)
    limits = c.file["check"]["limits"]
    if "gap_mean" in limits:
        toy_limits = {"gap_max": 0.008, "gap_mean": 0.0004}
        c.file["check"]["limits"] = {k: toy_limits[k] for k in limits}
    else:
        c.file["check"]["limits"] = {"loss_gap": 0.001,
                                     "grad_norm_gap": 0.01,
                                     "delta_norm_gap": 0.005}
    # no Mosaic kernel runs on a CPU: the roofline readers are rehearsed on
    # a recorded trace instead (test_trace.py)
    c.file["per_layer"] = [n for n in c.file["per_layer"]
                           if "roofline" not in n]
    if "start_s" in c.file["trace"]:
        c.file["trace"].update(start_s=0.3, seconds=1.0)
    else:
        c.file["trace"].update(start_step=1, steps=2)
    return c


def cpu_trace(path):
    """A CPU profile in the shape of a chip's: the XLA:CPU client's
    operation events stand in for the device's operations line, and jax's
    own ``PjitFunction(name)`` spans for the modules line. Good for
    rehearsing the control flow, never for a number."""
    import re

    from jax.profiler import ProfileData

    from benchmark.harness import trace as T
    ops, mods, host, window = [], [], [], None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                m = re.match(r"PjitFunction\((\w+)\)", ev.name)
                if ev.name == T.WINDOW:
                    window = (s, e)
                elif ev.name.startswith(T.HOST_SPAN_PREFIX):
                    host.append((s, e, ev.name))
                elif m:
                    mods.append((s, e, "jit_" + m.group(1)))
                elif line.name.startswith("tf_XLA") and e > s:
                    ops.append((s, e, ev.name))
    return T.Trace({0: {T.OPS_LINE: ops, T.MODULES_LINE: mods}}, host, window)
