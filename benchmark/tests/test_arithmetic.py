"""FLOPs and bytes kept with the benchmark, against hand numbers."""
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind, name):
    path = os.path.join(HERE, "..", kind, name)
    if name.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    spec = importlib.util.spec_from_file_location("m_" + name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gpt = load("models", "gpt.py")


@pytest.mark.parametrize("config,gflop", [("gpt2_345m", 2.42), ("gpt2_774m", 5.20)])
def test_flops_per_item(config, gflop):
    # by hand, 345M: 6 x (24 x 12 x 1024^2 + 50304 x 1024) = 2.121e9, plus
    # 12 x 24 x 1024 x 1024 = 0.302e9; 774M: 6 x (36 x 12 x 1280^2 + 50304 x
    # 1280) = 4.633e9, plus 12 x 36 x 1280 x 1024 = 0.566e9
    got = gpt.flops_per_item(load("configs", config + ".json"), 1024)
    assert abs(got / 1e9 - gflop) < 0.01


def test_flash_flops_per_step():
    # 345M, B=8: one product is 2 x 8 x 16 x 1024 x 1024 x 64 / 2 (causal)
    # = 8.59e9; six products, 24 layers: 1.237e12
    got = gpt.flash_flops_per_step(load("configs", "gpt2_345m.json"), 8, 1024)
    assert abs(got / 1e12 - 1.237) < 0.001


def test_kv_bytes_per_token():
    cfg = load("configs", "gpt2_345m.json")
    assert gpt.kv_bytes_per_token(cfg, "bfloat16") == 98304
    assert gpt.kv_bytes_per_token(cfg, "float32") == 196608


def test_configs_are_the_published_sizes():
    m, l = load("configs", "gpt2_345m.json"), load("configs", "gpt2_774m.json")
    assert (m["n_embd"], m["n_layer"], m["n_head"], m["n_inner"]) == (1024, 24, 16, 4096)
    assert (l["n_embd"], l["n_layer"], l["n_head"], l["n_inner"]) == (1280, 36, 20, 5120)
    for c in (m, l):
        assert c["vocab_size"] == 50257 and c["n_positions"] == 1024
        assert c["reduced"] == [] and c["resid_pdrop"] == 0.1


def test_unknown_chip_is_an_error():
    from benchmark.harness import peaks
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
