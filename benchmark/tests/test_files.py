"""BENCHMARK.json against the files it names: every cell, configuration,
traffic mix and metric is found by name, and the two listings agree."""
import json
import os

import pytest

from benchmark.harness import loader
from benchmark.reference import dense_decoder

BJ = loader.benchmark_json()
CELLS = [w["name"] for w in BJ["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_agree_with_benchmark_json(name):
    cell = loader.Cell(name)
    for trace, group in ((False, "end_to_end"), (True, "per_layer")):
        declared = cell.declared(trace)
        listed = {m["name"] for m in BJ[group]
                  if name in m.get("workloads", [name])}
        assert set(declared) == listed
    assert "setup_s" in cell.declared(False)
    assert cell.traffic["kind"] in ("open_poisson", "closed_clients",
                                    "train_stream")


@pytest.mark.parametrize("metric", [m["name"] for m in BJ["per_layer"]])
def test_every_per_layer_metric_has_a_reader_that_agrees(metric):
    entry = next(m for m in BJ["per_layer"] if m["name"] == metric)
    mod = loader.module("metrics", metric)
    assert callable(mod.read)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    e2e = {m["name"]: m for m in BJ["end_to_end"]}
    for cell in entry["workloads"]:
        assert cell in e2e[entry["moves"]].get("workloads", CELLS)


@pytest.mark.parametrize("config", [c["name"] for c in BJ["configs"]])
def test_configuration_keeps_the_published_widths(config):
    entry = next(c for c in BJ["configs"] if c["name"] == config)
    with open(os.path.join(loader.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = dict(hidden_size=4096, intermediate_size=14336,
                     num_attention_heads=32, num_key_value_heads=8,
                     vocab_size=32768, rope_theta=1000000.0,
                     rms_norm_eps=1e-05, max_position_embeddings=32768,
                     num_hidden_layers=32)
    changed = sorted(k for k, v in published.items() if cfg[k] != v)
    assert changed == sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == entry["source"]
    assert dense_decoder.n_params(cfg) > 5e8


def test_the_four_chip_share_is_within_the_contract():
    four = [w for w in BJ["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BJ["workloads"]) // 4)


def test_a_device_without_peaks_is_refused():
    from benchmark.harness import common
    with pytest.raises(common.NoChip, match="peaks.json"):
        common.peaks_of("TPU v9 imaginary")
    with pytest.raises(common.NoChip, match="TPU only"):
        common.find_devices(1, require_chip=True)   # this sandbox: a CPU
