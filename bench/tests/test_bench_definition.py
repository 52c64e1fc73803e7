"""BENCHMARK.json resolves to its files and keeps to the contract's form."""

import json
import os
import re

import pytest

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
CATALOG = {
    "deepseek-v2-lite-16b": "https://huggingface.co/deepseek-ai/"
    "DeepSeek-V2-Lite/blob/main/config.json"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"][1].startswith("bench/")
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


def test_names_and_units(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for root, _, files in os.walk(harness.BENCH):
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), harness.ROOT)
            if "__pycache__" not in rel:
                assert FILE.match(rel), rel


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = harness.resolve_cell(bench, w["name"])
        assert os.path.exists(os.path.join(
            harness.ROOT, harness.driver_file(cell.driver)))
        assert os.path.exists(os.path.join(harness.ROOT,
                                           cell.config["reference"]))
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_metric_resolves(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        mod = harness.load_module(harness.metric_file(m["name"]))
        assert callable(mod.read)
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            scope = e2e[m["moves"]].get("workloads")
            assert scope is None or w in scope
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25


def test_configs_keep_their_source(bench):
    for c in bench["configs"]:
        cfg = harness.load_json(c["file"])
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] == []
        if c["name"] in CATALOG:
            assert c["source"] == CATALOG[c["name"]]
    # every number of the catalog entry's config, under the same key
    ds = harness.load_json("bench/configs/deepseek-v2-lite-16b.json")
    assert ds["num_hidden_layers"] == 27 and ds["hidden_size"] == 2048
    assert ds["kv_lora_rank"] == 512 and ds["n_routed_experts"] == 64
    assert ds["num_experts_per_tok"] == 6 and ds["n_shared_experts"] == 2
    assert ds["moe_intermediate_size"] == 1408
    assert ds["intermediate_size"] == 10944
    assert ds["rope_scaling"]["factor"] == 40


def test_peaks_table():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.load_peaks("cpu")


def test_flops_per_token_pins_smollm():
    mfu = harness.load_module(harness.metric_file("train_mfu"))
    cfg = harness.load_json("bench/configs/smollm-360m.json")
    # 6 x 361,821,120 (non-embedding parameters + the tied head) plus
    # causal attention 6 * 32 * 4096 * 960
    assert mfu.flops_per_token(cfg, 4096) == 6.0 * 361_821_120 \
        + 6.0 * 32 * 4096 * 960
    assert abs(mfu.flops_per_token(cfg, 4096) - 2.93e9) < 0.01e9
