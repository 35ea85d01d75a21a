"""`models/xing4.py`: parameters, FLOPs and bytes against hand numbers,
at the published widths of `xing4_29b_ep1` (a new file beside
`test_arithmetic.py`, which a PR that adds a configuration may not edit)."""
import pytest

from benchmark.tests.test_arithmetic import load

fam = load("models", "xing4.py")
CONFIG = load("configs", "xing4_29b_ep1.json")

# a layer's attention: W_qa 3584 x 768 (2,752,512) + W_qb 768 x 32 x 192
# (4,718,592) + W_kva 3584 x 576 (2,064,384) + W_kvb 512 x 32 x 256
# (4,194,304) + W_o 4096 x 3584 (14,680,064)
ATTN = 28_409_856
# a sublayer's mHC: phi 14,336 x 24 + 3 gates + b 24
MHC = 344_064 + 3 + 24
# every layer: attention, the latent norms (768 + 512), two mHC
# sublayers, two norms of 3,584
LAYER = ATTN + 1280 + 2 * MHC + 2 * 3584
DENSE = LAYER + 3 * 3584 * 9216
EXPERT = 3 * 3584 * 1024                               # 11,010,048
SPARSE = LAYER + 3584 * 64 + 64 + 65 * EXPERT          # router, bias, 1 + 64
EMBED_HEAD = 2 * 131072 * 3584 + 3584                  # and the final norm


def test_parameter_count_at_the_cut_and_whole():
    sz = fam.sizes(CONFIG)
    assert MHC == 344_091
    assert fam.param_count(sz) == DENSE + 5 * SPARSE + EMBED_HEAD \
        == 4_792_669_828
    assert fam.param_count(sz) / 1e6 == pytest.approx(4792.7, abs=0.05)
    assert fam.param_count(sz) * 2 / 1e9 == pytest.approx(9.59, abs=0.005)
    # the whole model: 2 dense + 38 expert layers (no MTP layer)
    assert fam.param_count(sz, 2, 38) == 2 * DENSE + 38 * SPARSE + EMBED_HEAD
    assert fam.param_count(sz, 2, 38) / 1e9 == pytest.approx(29.5, abs=0.05)
    assert DENSE / 1e6 == pytest.approx(128.2, abs=0.05)
    assert SPARSE / 1e6 == pytest.approx(745.0, abs=0.05)
    assert 64 * EXPERT / 1e6 == pytest.approx(704.6, abs=0.05)


def test_the_count_is_the_programs():
    """The same function at the rehearsal widths against the model the
    program builds there."""
    import numpy as np
    model = fam.build_model(CONFIG, 0, rehearse=True, dtype="float32")
    assert fam.param_count(fam.sizes(CONFIG, rehearse=True)) == sum(
        int(np.prod(p.shape)) for p in model.parameters())


def test_flops_a_token_at_two_contexts():
    sz = fam.sizes(CONFIG)
    # matmul parameters a token passes: six layers' attention and both
    # phi (2 x 344,064); the dense FFN; five x (router 229,376 + the
    # shared expert + its top-4 experts = 5 x 11,010,048)
    body = 6 * (ATTN + 2 * 344_064) + 3 * 3584 * 9216 \
        + 5 * (3584 * 64 + 5 * EXPERT)
    assert fam.matmul_params_per_token(sz, False) == body == 550_076_416
    head = 3584 * 131072
    assert fam.matmul_params_per_token(sz, True) == body + head
    # attention: 32 heads x (192 + 128) x 2 = 20,480 FLOP a position a
    # token a layer, six layers, over ALL of the context
    assert fam.attention_flops(sz, 1) == 6 * 20_480
    for ctx in (1, 3000):
        assert fam.flops_per_token(sz, ctx) \
            == 2 * (body + head) + 6 * 20_480 * ctx
    assert fam.flops_per_token(sz, 3000) / 1e9 == pytest.approx(2.408, abs=1e-3)
    assert fam.flops_per_token(sz, 16384) / 1e9 == pytest.approx(4.053, abs=1e-3)
    # a chunk of 2,048 at position 2,048: contexts 2,049 .. 4,096
    want = 2 * 2048 * body + 2 * head + 6 * 20_480 * (2048 * (2049 + 4096) // 2)
    assert fam.prefill_flops(sz, 2048, 2048) == pytest.approx(want, rel=1e-12)
    assert want / 1e12 == pytest.approx(3.027, abs=1e-3)
    # what `serve.mfu_pct`'s reader hands over for the decode steps: the
    # positions they attended over, and a second count this family has
    # no product for
    assert fam.attention_flops(sz, 1000, 1000) == 6 * 20_480 * 1000


def test_latent_bytes_a_decode_step_reads_and_a_position_holds():
    sz = fam.sizes(CONFIG)
    # 576 latent values in bf16, ONCE a layer (keys and values are one
    # row), six layers: 6,912 B a position
    assert fam.decode_read_bytes(sz, 1) == 6 * 1152 == 6912
    assert fam.decode_read_bytes(sz, 64 * 3000, "bfloat16") == 64 * 3000 * 6912
    assert fam.decode_read_bytes(sz, 10, "float32") == 10 * 2 * 6912
    # as stored: 576 values in 640 lanes
    assert fam.kv_bytes_per_token(sz, "bfloat16") == 6 * 1280 == 7680


def test_the_pool_and_the_weights_leave_room_on_the_chip():
    sz = fam.sizes(CONFIG)
    system = load("workloads", "xing4_29b_ep1.serve.closed64_ctx2k.json")
    eng = system["engine"]
    positions = (eng["num_pages"] - 1) * eng["block_size"]
    assert positions == 393_216 and eng["max_batch_slots"] == 64
    pool = eng["num_pages"] * eng["block_size"] \
        * fam.kv_bytes_per_token(sz, eng["cache_dtype"])
    assert pool / 1e9 == pytest.approx(3.02, abs=0.005)
    assert (pool + 2 * fam.param_count(sz)) / 1e9 == pytest.approx(12.6, abs=0.05)
    # the longest request of the mix fits a slot, a chunk to spare
    mix = load("traffic", "serve.closed64_ctx2k.json")
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= eng["max_context_len"] == 18_432


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    c = CONFIG
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["n_shared_experts"], c["vocab_size"],
            c["hc_mult"], c["hc_sinkhorn_iters"], c["hc_eps"],
            c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"],
            c["rope_theta"], c["routed_scaling_factor"], c["ep_size"]) == (
        3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 64, 4, 1, 131072,
        4, 20, 1e-6, -30, 30, 10000, 2, 1)
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "num_nextn_predict_layers"]
    assert c["published"] == {"num_hidden_layers": 40,
                              "first_k_dense_replace": 2,
                              "num_nextn_predict_layers": 1}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["num_nextn_predict_layers"]) == (6, 1, 0)
    assert c["layers_held"] == [1, 2, 3, 4, 5, 6]
    sz = fam.sizes(c)
    assert sz["mlp_layer_types"] == ("dense",) + ("sparse",) * 5
    assert sz["experts_held"] == (0, 64) and sz["n_routed_experts"] == 64
    for key in ("assumed", "deployment", "bytes", "not_built"):
        assert c[key]


def test_the_rehearsal_sizes_are_the_tiny_preset():
    from paddle_tpu.models.xing4 import xing4_tiny
    rs, tiny = fam.sizes(CONFIG, rehearse=True), xing4_tiny()
    for key in ("hidden_size", "num_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "num_experts_per_tok", "experts_held",
                "mlp_layer_types", "hc_mult", "hc_sinkhorn_iters",
                "vocab_size", "context_block"):
        assert rs[key] == getattr(tiny, key), key
    assert (rs["rope"]["factor"], rs["rope"]["original_max"]) \
        == (tiny.rope_factor, tiny.rope_original_max_position)
