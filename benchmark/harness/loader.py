"""Finds a cell's files by the names in ``BENCHMARK.json``.

One file per thing: ``configs/<config>.json``, ``traffic/<mix>.json``,
``workloads/<cell>.json``, ``metrics/<name>.py``, ``kernels/<kernel>.py``.
A later PR adds files and a ``workloads`` entry and edits nothing here."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path):
    with open(path) as f:
        return json.load(f)


def benchmark_json():
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def peaks():
    return _json(os.path.join(BENCH, "peaks.json"))


def module(kind, name):
    """Import ``benchmark/<kind>/<name>.py`` by path (metric names carry
    dots, so they are not importable by name)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads``: its configuration, its traffic mix and
    the metrics it reports with and without a trace."""

    def __init__(self, name):
        entries = {w["name"]: w for w in benchmark_json()["workloads"]}
        if name not in entries:
            raise KeyError(f"workload {name!r} is not in BENCHMARK.json "
                           f"(it has {sorted(entries)})")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.file = _json(os.path.join(BENCH, "workloads", name + ".json"))
        for key in ("config", "traffic"):
            if self.file[key] != self.entry[key]:
                raise ValueError(
                    f"workloads/{name}.json: {key} {self.file[key]!r} is "
                    f"not BENCHMARK.json's {self.entry[key]!r}")
        self.config = _json(os.path.join(
            BENCH, "configs", self.entry["config"] + ".json"))
        self.traffic = _json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json"))
        if int(self.config["chips"]) != self.chips:
            raise ValueError(f"{name}: the cell asks for {self.chips} "
                             f"chips, its configuration for "
                             f"{self.config['chips']}")

    def declared(self, trace):
        """{metric name: unit} this cell reports in this mode: the names
        from the cell's own file, the units from ``BENCHMARK.json``."""
        bj = benchmark_json()
        units = {m["name"]: m["unit"]
                 for m in (bj["per_layer"] if trace else bj["end_to_end"])}
        names = self.file["per_layer" if trace else "end_to_end"]
        missing = [n for n in names if n not in units]
        if missing:
            raise ValueError(f"{self.name}: metrics {missing} are not in "
                             f"BENCHMARK.json")
        return {n: units[n] for n in names}
