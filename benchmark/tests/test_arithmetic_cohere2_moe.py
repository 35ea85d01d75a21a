"""`models/cohere2_moe.py`: FLOPs and bytes against hand numbers, at the
published widths of `command_a_plus_ep8` (a new file beside
`test_arithmetic.py`, which a PR that adds a configuration may not edit)."""
import pytest

from benchmark.tests.test_arithmetic import load

fam = load("models", "cohere2_moe.py")
CONFIG = load("configs", "command_a_plus_ep8.json")


def test_matmul_parameters_a_token():
    sz = fam.sizes(CONFIG)
    # a layer: W_q 4096 x 16384 and W_o (2 x 67,108,864), W_k and W_v
    # 4096 x 1024 (2 x 4,194,304) = 142,606,336; the router 4096 x 128 =
    # 524,288; four shared experts 4 x 3 x 4096^2 = 201,326,592; the
    # token's expected share of the 16 held experts, 8 x 16 / 128 = 1
    # expert = 50,331,648: 394,788,864, four layers 1,579,155,456
    assert fam.matmul_params_per_token(sz, False) == 1_579_155_456
    # the tied head: 4096 x 32,768 rows of the embedding
    assert fam.matmul_params_per_token(sz, True) == 1_579_155_456 + 134_217_728


def test_a_chunk_of_2048_at_position_4096():
    sz = fam.sizes(CONFIG)
    # contexts 4,097 .. 6,144: the full layer attends over their sum,
    # 2048 x (4097 + 6144) / 2 = 10,486,784 positions; each of the three
    # window layers over min(context, 4096) = 4096 a token, 8,388,608;
    # 4 x 128 heads x 128 = 65,536 FLOP a position a token
    attention = 65_536 * (10_486_784 + 3 * 8_388_608)
    assert fam.attention_flops(sz, 10_486_784, 8_388_608) == attention
    want = 2 * 2048 * 1_579_155_456 + 2 * 4096 * 32768 + attention
    assert fam.prefill_flops(sz, 4096, 2048) == pytest.approx(want, rel=1e-12)
    assert want / 1e12 == pytest.approx(8.805, abs=0.001)
    # without the window the three layers would attend over 10,486,784 too
    assert 65_536 * 4 * 10_486_784 / attention == pytest.approx(1.176, abs=0.001)


def test_bytes_a_decode_step_reads_and_a_position_holds():
    sz = fam.sizes(CONFIG)
    # K and V rows of 8 heads x 128 in bf16: 4 KiB a position a layer
    assert fam.kv_bytes_per_token(sz, "bfloat16") == 4 * 4096
    # a slot at position 9,999: 10,000 positions in the full layer, the
    # last 4,096 in each window layer
    assert fam.decode_read_bytes(sz, 10_000, 4096, "bfloat16") \
        == 4096 * (10_000 + 3 * 4096)
    assert fam.decode_read_bytes(sz, 10_000, 4096, "float32") \
        == 8192 * (10_000 + 3 * 4096)
    # what `serve.mfu_pct`'s reader asks with the counters of another
    # family: nothing counted, nothing added
    assert fam.attention_flops(sz, 0, 0) == 0


def test_the_configuration_is_the_published_one_cut_to_the_chips_share():
    c = CONFIG
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["num_experts_per_tok"], c["num_shared_experts"],
            c["sliding_window"], c["rope_theta"]) == (
        4096, 128, 8, 128, 4096, 8, 4, 4096, 50000)
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                              "vocab_size": 262144}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (4, 16, 32768)
    sz = fam.sizes(c)
    assert sz["layer_types"] == ("sliding_attention",) * 3 + ("full_attention",)
    assert sz["experts_held"] == (0, 16) and sz["n_routed_experts"] == 128
    for key in ("assumed", "deployment", "bytes", "not_built"):
        assert c[key]


def test_the_rehearsal_sizes_are_the_tiny_presets():
    from paddle_tpu.models.cohere2_moe import cohere2_moe_tiny
    rs, tiny = fam.sizes(CONFIG, rehearse=True), cohere2_moe_tiny()
    for key in ("hidden_size", "num_heads", "num_kv_heads", "head_dim",
                "intermediate_size", "n_routed_experts", "experts_held",
                "n_shared_experts", "sliding_window", "layer_types",
                "vocab_size", "context_block"):
        assert rs[key] == getattr(tiny, key), key
