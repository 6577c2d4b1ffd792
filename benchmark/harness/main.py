"""One run of one cell: ``--workload --seed --seconds --trace``. The last
line of standard output is the result; everything else (medians, counts,
lateness, losses, each number compared beside its limit) goes on earlier
lines."""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

from . import loader, output


def log(msg):
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    #: also compute the lower-precision control's numbers (for setting
    #: limits; the driver never passes it)
    ap.add_argument("--control", choices=("", "int8", "fp8"), default="")
    return ap.parse_args(argv)


def place_caches():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), unless the environment
    already names one. Must run before jax is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(loader.ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def run_cell(cell, seed, seconds, trace, t_start, control=None,
             require_chip=True, peaks=None, tamper=None, load_trace=None):
    """Everything of a run but the look for a chip's arguments and the
    exit: returns (result object, declared metrics). Tests call this with
    ``require_chip=False`` and a toy cell."""
    import jax
    from . import common
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = common.find_devices(cell.chips, require_chip)
    kind = devices[0].device_kind
    peaks = peaks if peaks is not None else common.peaks_of(kind)
    log(f"[run] {cell.name}: seed {seed}, {seconds}s, trace {int(trace)}; "
        f"jax {jax.__version__} on {len(devices)} x {kind} "
        f"({devices[0].platform}); compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    reference = importlib.import_module(
        "benchmark.reference." + cell.config["reference"])
    kind_of = cell.traffic["kind"]
    runner = importlib.import_module(
        "benchmark.harness." + ("train" if kind_of == "train_stream"
                                else "serve"))
    cell.trace_dir = os.path.join(loader.ROOT, ".bench_trace", cell.name)
    res = runner.run(cell, seed, seconds, bool(trace), t_start, devices,
                     reference, control=control, log=log, tamper=tamper)
    ctx = res["ctx"]
    ctx["peaks"] = peaks
    device = common.device_line(devices, res["memory_peak"])
    declared = cell.declared(bool(trace))
    values, breakdown = {}, None
    if trace:
        from .trace import Trace, newest_xplane
        t = time.perf_counter()
        path = newest_xplane(cell.trace_dir)
        tr = (load_trace or Trace.from_file)(path)
        ctx["trace"] = tr
        log(f"[trace] {os.path.getsize(path) / 1e6:.1f} MB, "
            f"{len(tr.chips)} device plane(s), window {tr.window_s:.3f}s, "
            f"reduced in {time.perf_counter() - t:.1f}s")
        device["busy_s"], device["window_s"] = tr.busy_s(), tr.window_s
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        for name in list(declared):
            value = loader.module("metrics", name).read(ctx)
            if value is None:
                log(f"[trace] {name}: nothing to read in this run")
                del declared[name]
            else:
                values[name] = float(value)
    else:
        for name in declared:
            if name not in res["values"]:
                raise output.OutputError(
                    f"metrics.{name}: the run measured no such number")
            values[name] = float(res["values"][name])
    for k, v in sorted(res["values"].items()):
        log(f"[run] {k} = {v!r}")
    obj = output.build(res["correct"], res["attempted"], res["failed"],
                       values, declared, device, bool(trace), breakdown)
    return obj, declared


def main(argv=None, t_start=None):
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(sys.argv[1:] if argv is None else argv)
    place_caches()
    try:
        cell = loader.Cell(args.workload)
        obj, declared = run_cell(cell, args.seed, args.seconds, args.trace,
                                 t_start, control=args.control or None)
        text = output.dumps(obj, declared, bool(args.trace), cell.chips)
    except BaseException as e:  # no result line on any failure
        import traceback
        traceback.print_exc()
        print(f"[run] no result: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        sys.stdout.flush()
        os._exit(1 if not isinstance(e, KeyboardInterrupt) else 130)
    output.finish(text)
