"""Every configuration, cell, traffic mix and per-layer metric is a file
found by its name, and the configurations hold the published widths."""
import json
import re

import pytest

from bench import spec

B = spec.benchmark()
WIDTH = re.compile(r"(^hidden_size$|intermediate_size$|_dim$|_rank$|latent|"
                   r"state_size|expan|^num_experts_per_tok$)")

# Published widths (the sources named in each configuration file).
PUBLISHED = {
    "internlm2-1.8b": {
        "hidden_size": 2048, "intermediate_size": 8192,
        "num_hidden_layers": 24, "num_attention_heads": 16,
        "num_key_value_heads": 8, "vocab_size": 92544,
        "rope_theta": 1000000, "rms_norm_eps": 1e-05},
    "deepseek-v2-lite-16b": {
        "first_k_dense_replace": 1, "hidden_size": 2048,
        "intermediate_size": 10944, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "moe_intermediate_size": 1408,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 2, "norm_topk_prob": False,
        "num_attention_heads": 16, "num_experts_per_tok": 6,
        "num_hidden_layers": 27, "num_key_value_heads": 16,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 1, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 102400},
}


def _names(kind):
    return sorted(p.stem for p in (spec.BENCH / kind).glob("*.json"))


def _names(kind):
    return sorted(p.stem for p in (spec.BENCH / kind).glob("*.json"))


@pytest.mark.parametrize("name", _names("configs"))
def test_config_holds_the_published_numbers(name):
    c = spec.config(name)
    for key in c["reduced"]:
        assert not WIDTH.search(key), f"a width may not be cut: {key}"
    for key, value in PUBLISHED[name].items():
        if key not in c["reduced"]:
            assert c[key] == value, key
        else:
            assert c[key] != value and c["published"][key] == value, key


@pytest.mark.parametrize("entry", B["configs"], ids=lambda e: e["name"])
def test_benchmark_names_each_config_file(entry):
    c = spec.config(entry["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert c["name"] == entry["name"] and c["source"] == entry["source"]
    assert c["reduced"] == entry["reduced"]


@pytest.mark.parametrize("name", _names("configs"))
def test_config_builds_the_programs_widths(name):
    from repro.configs import get_config
    from repro.models.config import ModelConfig
    c = spec.config(name)
    cfg = ModelConfig(**spec.model(c["model_type"]).program_config(c))
    full = get_config(name).full
    for f in ("d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size",
              "resolved_head_dim", "n_experts", "n_shared_experts",
              "top_k", "moe_d_ff", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "rope_theta"):
        assert getattr(cfg, f) == getattr(full, f), f


def test_every_file_is_found_by_name():
    cells = {w["name"]: w for w in B["workloads"]}
    assert sorted(cells) == _names("workloads")
    assert {e["name"] for e in B["configs"]} <= set(_names("configs"))
    for name, entry in cells.items():
        w = spec.workload(name)
        for key in ("config", "traffic", "chips", "why"):
            assert w[key] == entry[key], (name, key)
        spec.config(w["config"])
        spec.traffic(w["traffic"])
        spec.model(spec.config(w["config"])["model_type"])
    for m in B["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
        assert set(m["workloads"]) <= set(cells)


def test_a_new_cell_is_only_new_files(tmp_path):
    """A cell, a configuration, a mix and a metric added as files only
    are found by name, with no file of the harness edited."""
    for kind in ("configs", "workloads", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    c = dict(spec.config("internlm2-1.8b"), name="internlm2-new")
    (tmp_path / "configs" / "internlm2-new.json").write_text(json.dumps(c))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps(
        dict(spec.traffic("chat"), clients=16)))
    (tmp_path / "workloads" / "internlm2-new.burst.json").write_text(
        json.dumps({"config": "internlm2-new", "traffic": "burst",
                    "chips": 1, "why": "x", "serving": {}}))
    (tmp_path / "metrics" / "queue.wait_ms.py").write_text(
        "def read(ctx):\n    return None\n")
    w = spec.workload("internlm2-new.burst", tmp_path)
    assert spec.config(w["config"], tmp_path)["name"] == "internlm2-new"
    assert spec.traffic(w["traffic"], tmp_path)["clients"] == 16
    assert spec.metric_reader("queue.wait_ms", tmp_path).read(None) is None
    bench = {"per_layer": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in spec.cell_metrics(bench, "y", "per_layer")] \
        == ["a"]


def test_missing_files_are_errors():
    with pytest.raises(FileNotFoundError):
        spec.workload("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.config("no-such-config")
