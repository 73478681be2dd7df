"""BENCHMARK.json against the contract's static rules and against the
files it names."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT
from harness import spec

B = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    four = sum(1 for w in B["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(B["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in B["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_names_units_and_whys():
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(names)) == len(names)
    for entry in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in B["workloads"] + B["configs"]:
        assert NAME.match(w["name"]) and 1 <= len(w["why"]) <= 200
    assert any(m["name"] == "setup_s" for m in B["end_to_end"])


def test_moves_is_reported_wherever_the_metric_is():
    cells = [w["name"] for w in B["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in B["end_to_end"]}
    for m in B["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert sum(cell in v for v in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells) for m in B["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_cell_loads_with_its_files(cell):
    c = spec.load_cell(cell)
    assert c.config["source"].startswith("https://")
    assert "assumed" in c.config and "deployment" in c.config
    entry = next(x for x in B["configs"] if x["name"] == c.config_name)
    assert entry["reduced"] == c.config["reduced"]
    for key in c.config["reduced"]:
        assert c.config["published"][key] != c.config[key]
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_module("sources", m.source).read)
    entry = c.config["program"]["entry"]
    assert callable(spec.load_module("entries", entry).run_entry)
    if entry == "serve":
        assert callable(spec.load_module("loops", c.traffic["kind"]).drive)
    if c.traffic["kind"] == "open_loop":
        assert "requests_per_second" in c.traffic_for_config()


def test_flags_carry_the_published_widths():
    for entry in B["configs"]:
        cfg = json.load(open(os.path.join(ROOT, entry["file"])))
        flags = dict(f.lstrip("-").split("=", 1) if "=" in f
                     else (f.lstrip("-"), True)
                     for f in cfg["program"]["flags"])
        assert int(flags["hidden_size"]) == cfg["hidden_size"] == 4096
        assert int(flags["ffn_hidden_size"]) == cfg["intermediate_size"]
        assert int(flags["num_attention_heads"]) == cfg["num_attention_heads"]
        assert int(flags["num_attention_heads_kv"]) == \
            cfg["num_key_value_heads"]
        assert int(flags["num_layers"]) == cfg["num_hidden_layers"]


def test_metric_files_agree_with_benchmark_json():
    for kind, folder in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        listed = {m["name"] for m in B[kind]}
        on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, folder))}
        assert listed == on_disk
        for m in B[kind]:
            body = json.load(open(os.path.join(BENCH, folder,
                                               m["name"] + ".json")))
            assert body["unit"] == m["unit"]
            if kind == "per_layer":
                assert body["layer"] == m["layer"]
                assert body["moves"] == m["moves"]
