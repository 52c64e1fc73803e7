"""BENCHMARK.json resolves to its files and keeps to the contract's form.

A configuration file holds the published ``config.json`` keys.  It may
cut some of them (``reduced``): a depth, or this chip's share of a layer
(experts held, rows of the vocabulary), never a width.  A cut file then
holds ``published``, the source's value of exactly the cut keys, each
unlike the file's; a ``deployment`` that says how many chips share each
layer, and how; and, where the benchmark holds the same source uncut,
that file's value of every other published key.
"""

import copy
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
#: configurations that run at their published size
UNCUT = ("smollm-360m", "deepseek-v2-lite-16b")
#: a width: hidden, intermediate, latent, state or projection size, a key
#: ending in _dim or _rank, a head size, an expansion factor, experts per
#: token
WIDTH = re.compile(r"_dim$|_rank$|hidden_size|intermediate_size|head_size"
                   r"|latent|state_size|d_state|proj|expan|mlp_ratio"
                   r"|experts_per_tok")
#: keys of a configuration file that are the benchmark's, not config.json's
HARNESS_KEYS = {"source", "program", "reference", "reduced", "published",
                "assumed", "assumed_why", "deployment"}


def config_faults(entry: dict, cfg: dict, bench: dict) -> list:
    """What keeps a configuration (``entry`` of BENCHMARK.json, ``cfg``
    its file) from the contract; empty where it keeps to it."""
    faults = []
    reduced, published = cfg.get("reduced"), cfg.get("published", {})
    if cfg.get("source") != entry["source"]:
        faults.append("source differs from BENCHMARK.json's")
    if entry["name"] in CATALOG and entry["source"] != CATALOG[entry["name"]]:
        faults.append("source is not the catalog's")
    if reduced != entry["reduced"]:
        faults.append("reduced differs from BENCHMARK.json's")
        return faults
    deployment = cfg.get("deployment")
    if not isinstance(deployment, str) or not deployment.strip():
        faults.append("no deployment")
    if not reduced:
        if published:
            faults.append("published without reduced")
        return faults
    if not re.search(r"\d", deployment or ""):
        faults.append("deployment names no number of chips")
    if set(published) != set(reduced):
        faults.append(f"published keys {sorted(published)} are not "
                      f"reduced's {sorted(reduced)}")
    for k in reduced:
        if not NAME.match(k) or k in HARNESS_KEYS or k not in cfg:
            faults.append(f"reduced names {k!r}, no published key here")
        elif WIDTH.search(k):
            faults.append(f"reduced names the width {k!r}")
        elif k in published and published[k] == cfg[k]:
            faults.append(f"{k} is not cut: {cfg[k]!r} is the published "
                          f"value")
        elif isinstance(cfg[k], dict):
            for sub, v in cfg[k].items():
                if WIDTH.search(sub) and published[k].get(sub) != v:
                    faults.append(f"the width {k}.{sub} changed")
    for other in bench["configs"]:
        if other["source"] != entry["source"] or other["reduced"] \
                or other["name"] == entry["name"]:
            continue
        uncut = harness.load_json(other["file"])
        for k in set(uncut) - HARNESS_KEYS:
            want = uncut[k]
            got = published.get(k) if k in reduced else cfg.get(k, "absent")
            if got != want:
                faults.append(f"{k}: {got!r} where {other['name']} holds "
                              f"{want!r}")
    return faults


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
        assert config_faults(c, cfg, bench) == [], c["name"]
        if c["name"] in UNCUT:
            assert cfg["reduced"] == c["reduced"] == []
    names = {c["name"] for c in bench["configs"]}
    assert set(UNCUT) <= names
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


DS_SOURCE = CATALOG["deepseek-v2-lite-16b"]


def cut_deepseek():
    """DeepSeek-V2-Lite at one chip's share for training on one v5e:
    the dense layer and 5 expert layers, 8 of the 64 routed experts
    held with 8 chips sharing each layer, an eighth of the
    vocabulary."""
    cfg = copy.deepcopy(harness.load_json(
        "bench/configs/deepseek-v2-lite-16b.json"))
    cut = {"num_hidden_layers": 6, "n_routed_experts": 8,
           "vocab_size": 12800}
    cfg["published"] = {k: cfg[k] for k in cut}
    cfg.update(cut)
    cfg["reduced"] = sorted(cut)
    cfg["deployment"] = ("8 chips share each layer, each holding 8 of the "
                         "64 routed experts and an eighth of the "
                         "vocabulary; the 21 expert layers left out lie on "
                         "further pipeline stages")
    entry = {"name": "deepseek-v2-lite-16b.share8", "source": DS_SOURCE,
             "file": "bench/configs/deepseek-v2-lite-16b.share8.json",
             "reduced": sorted(cut), "why": "one chip's share"}
    return entry, cfg


def test_cut_config_keeps_to_the_contract(bench):
    entry, cfg = cut_deepseek()
    assert config_faults(entry, cfg, bench) == []


def _drop_published(entry, cfg):
    del cfg["published"]["vocab_size"]


def _not_cut(entry, cfg):
    cfg["published"]["num_hidden_layers"] = cfg["num_hidden_layers"]


def _no_deployment(entry, cfg):
    del cfg["deployment"]


def _cuts_a_width(entry, cfg):
    cfg["published"]["kv_lora_rank"] = cfg["kv_lora_rank"]
    cfg["kv_lora_rank"] = 256
    cfg["reduced"] = entry["reduced"] = sorted(cfg["published"])


def _changes_an_unlisted_key(entry, cfg):
    cfg["n_shared_experts"] = 1


def _bench_differs(entry, cfg):
    entry["reduced"] = ["num_hidden_layers"]


@pytest.mark.parametrize("fault", [
    _drop_published, _not_cut, _no_deployment, _cuts_a_width,
    _changes_an_unlisted_key, _bench_differs],
    ids=lambda f: f.__name__.lstrip("_"))
def test_cut_config_fault_is_refused(bench, fault):
    entry, cfg = cut_deepseek()
    fault(entry, cfg)
    assert config_faults(entry, cfg, bench)


MLA_MOE = {"kv_lora_rank": 256, "q_lora_rank": 1536,
           "qk_nope_head_dim": 64, "qk_rope_head_dim": 32,
           "v_head_dim": 64, "moe_intermediate_size": 1024,
           "num_experts_per_tok": 8, "n_shared_experts": 1,
           "first_k_dense_replace": 2}


def deepseek_cell(**changed):
    """The DeepSeek-V2-Lite file as the train driver would take it (the
    program's registry holds its norm epsilon at the 1e-5 default, so
    the file's program overrides it), with ``changed`` keys."""
    cfg = copy.deepcopy(harness.load_json(
        "bench/configs/deepseek-v2-lite-16b.json"))
    cfg["program"]["overrides"] = {"norm_eps": cfg["rms_norm_eps"]}
    cfg.update(changed)
    return harness.Cell(name="ds.train", chips=1,
                        config_name="deepseek-v2-lite-16b", config=cfg,
                        traffic={}, end_to_end=[], per_layer=[])


def test_program_config_takes_the_mla_and_moe_widths():
    drv = harness.load_module(harness.driver_file("train"))
    assert set(MLA_MOE) <= set(drv.WIDTHS)
    cfg = drv.program_config(deepseek_cell())      # q_lora_rank null <-> 0
    assert cfg.mla.kv_lora_rank == 512 and cfg.moe.top_k == 6
    assert cfg.mla.q_lora_rank == 0


@pytest.mark.parametrize("key", sorted(MLA_MOE))
def test_program_config_refuses_a_departing_width(key):
    drv = harness.load_module(harness.driver_file("train"))
    with pytest.raises(harness.BenchError, match=key):
        drv.program_config(deepseek_cell(**{key: MLA_MOE[key]}))
