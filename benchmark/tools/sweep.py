"""Find a serving cell's knee once, on the chip: one process, one set-up,
several fixed rates (open loop) or client counts (closed loop), each for
a short window. Writes what it saw under ``chiprun_out/``; the knee and
the sweep are then written into the traffic file by hand.

    python3 benchmark/tools/sweep.py --workload doc_batch \\
        --points 4,8,16 --seconds 20 [--engine '{"max_batch": 16}']
        [--dump-trace 1]
"""
import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import loader, main as M  # noqa: E402


def dump_trace(path, out):
    """The structure of one trace, for the look by hand: planes, lines,
    counts, and the commonest event names with their stats' keys."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            names, n, first = {}, 0, None
            for ev in line.events:
                n += 1
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
                if first is None:
                    first = (ev.name, ev.start_ns, ev.duration_ns,
                             {k: str(v)[:80] for k, v in ev.stats})
            rows.append(f"  LINE {line.name!r}: {n} events; first {first}")
            for name, ns in sorted(names.items(), key=lambda kv: -kv[1])[:25]:
                rows.append(f"      {ns / 1e6:10.3f} ms  {name}")
    with open(out, "w") as f:
        f.write("\n".join(rows) + "\n")


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--points", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--engine", default="{}")
    ap.add_argument("--dump-trace", type=int, default=0)
    args = ap.parse_args(argv)
    M.place_caches()
    import jax
    from benchmark.harness import common, serve
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = loader.Cell(args.workload)
    cell.config["engine"].update(json.loads(args.engine))
    cell.trace_dir = os.path.join(loader.ROOT, ".bench_trace", "sweep")
    devices = common.find_devices(cell.chips)
    reference = importlib.import_module(
        "benchmark.reference." + cell.config["reference"])
    t = time.perf_counter()
    session = serve.Session(cell, args.seed, devices, reference, M.log)
    M.log(f"[sweep] set-up {time.perf_counter() - t:.1f}s, engine "
          f"{cell.config['engine']}")
    key = "clients" if cell.traffic["kind"] == "closed_clients" \
        else "rate_rps"
    out = []
    points = [float(p) for p in args.points.split(",")]
    for i, point in enumerate(points):
        mix = dict(cell.traffic)
        mix[key] = int(point) if key == "clients" else point
        if key == "rate_rps":
            # one cycle of arrivals a window
            mix["cycle"] = max(4, round(point * args.seconds))
        trace = cell.file["trace"] if (
            args.dump_trace and i == len(points) // 2) else None
        m = session.drive(mix, args.seed + 1000 * i, args.seconds, trace)
        ttft = [(r.t_first - r.t_submit) * 1e3 for r in m["ok"]]
        tpot = [(r.t_last - r.t_first) / (r.seen - 1) * 1e3
                for r in m["ok"] if r.seen > 1]
        row = {key: point, "attempted": m["attempted"],
               "failed": m["failed"], **m["values"],
               "ttft_submit_mean_p75_p90_max": [float(np.mean(ttft))] + [
                   float(np.percentile(ttft, q)) for q in (75, 90, 100)],
               "tpot_mean_p75_p90_max": [float(np.mean(tpot))] + [
                   float(np.percentile(tpot, q)) for q in (75, 90, 100)],
               "steps": m["stats1"]["steps"] - m["stats0"]["steps"],
               "window_s": m["window_s"]}
        M.log(f"[sweep] {json.dumps(row)}")
        out.append(row)
        if trace:
            from benchmark.harness.trace import newest_xplane
            os.makedirs("chiprun_out", exist_ok=True)
            dump_trace(newest_xplane(cell.trace_dir),
                       f"chiprun_out/trace_structure_{args.workload}.txt")
        # let the queue of an overloaded point empty before the next
        deadline = time.perf_counter() + 120
        while session.client.active and time.perf_counter() < deadline:
            session.client.poll()
            time.sleep(0.01)
    os.makedirs("chiprun_out", exist_ok=True)
    tag = args.engine.replace('"', "").replace(" ", "").replace(":", "")
    with open(f"chiprun_out/sweep_{args.workload}_{tag}.json", "w") as f:
        json.dump({"engine": cell.config["engine"], "seconds": args.seconds,
                   "points": out,
                   "memory_peak_bytes": common.memory_peak_bytes(devices)},
                  f, indent=1)
    session.close()
    os._exit(0)


if __name__ == "__main__":
    run()
