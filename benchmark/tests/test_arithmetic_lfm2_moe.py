"""`models/lfm2_moe.py`: parameters, FLOPs and bytes against hand
numbers, at the published widths of `lfm2_24b_ep1` (a new file beside
`test_arithmetic.py`, which a PR that adds a configuration may not edit)."""
import pytest

from benchmark.tests.test_arithmetic import load

fam = load("models", "lfm2_moe.py")
CONFIG = load("configs", "lfm2_24b_ep1.json")

D = 2048
# a conv operator: in-proj 2048 x 6144 + depthwise kernel 3 x 2048 +
# out-proj 2048 x 2048
CONV = D * 6144 + 3 * D + D * D                       # 16,783,360
# an attention operator: q 2048 x 2048, k and v 2048 x 512 each, o 2048 x
# 2048, the q and k norms of 64
ATTN = 2 * D * D + 2 * D * 512 + 2 * 64              # 10,485,888
NORMS = 2 * D
DENSE_FFN = 3 * D * 11776                             # 72,351,744
EXPERT = 3 * D * 1536                                 # 9,437,184
ROUTER = D * 64 + 64
EMBED = 65536 * D                                     # tied head


def test_lfm2_parameters_at_the_cut_and_whole():
    sz = fam.sizes(CONFIG)
    assert sz["layer_types"] == ("conv", "full_attention", "conv", "conv",
                                 "conv", "full_attention", "conv", "conv",
                                 "conv")
    assert sz["mlp_layer_types"] == ("dense",) + ("sparse",) * 8
    dense = CONV + DENSE_FFN + NORMS
    attn_layer = ATTN + ROUTER + 64 * EXPERT + NORMS
    conv_layer = CONV + ROUTER + 64 * EXPERT + NORMS
    want = dense + 2 * attn_layer + 6 * conv_layer + EMBED + D
    assert fam.param_count(sz) == want == 5_177_950_976
    assert dense / 1e6 == pytest.approx(89.1, abs=0.05)
    assert attn_layer / 1e6 == pytest.approx(614.6, abs=0.05)
    assert conv_layer / 1e6 == pytest.approx(620.9, abs=0.05)
    assert fam.param_count(sz) * 2 / 1e9 == pytest.approx(10.36, abs=0.005)
    # the whole model: 40 published layers, 2 of them dense
    whole = dict(sz, layer_types=tuple(CONFIG["published"]["layer_types"]),
                 mlp_layer_types=("dense",) * 2 + ("sparse",) * 38)
    assert fam.param_count(whole) / 1e9 == pytest.approx(23.84, abs=0.005)


def test_lfm2_count_is_the_programs():
    """The same function at the rehearsal widths against the model the
    program builds there."""
    import numpy as np
    model = fam.build_model(CONFIG, 0, rehearse=True, dtype="float32")
    assert fam.param_count(fam.sizes(CONFIG, rehearse=True)) == sum(
        int(np.prod(p.shape)) for p in model.parameters())


def test_lfm2_flops_a_token_and_a_chunk():
    sz = fam.sizes(CONFIG)
    # matmul parameters a token passes: seven conv layers' two
    # projections (4 D^2), two attention layers' four, the dense FFN, and
    # eight x (router + its top-4 experts)
    body = 7 * 4 * D * D + 2 * (ATTN - 128) + DENSE_FFN \
        + 8 * (D * 64 + 4 * EXPERT)
    assert fam.matmul_params_per_token(sz, False) == body == 513_802_240
    head = D * 65536
    assert fam.matmul_params_per_token(sz, True) == body + head
    # attention: 32 heads x 2 x 64 x 2 = 8,192 FLOP a position a token a
    # layer, two layers; the conv taps 7 x (2 x 3 + 1) x 2,048 a token
    assert fam.attention_flops(sz, 1) == 2 * 8192
    assert fam.conv_flops(sz, 1) == 7 * 7 * D
    for ctx in (1, 1410):
        assert fam.flops_per_token(sz, ctx) \
            == 2 * (body + head) + 7 * 7 * D + 2 * 8192 * ctx
    assert fam.flops_per_token(sz, 1410) / 1e9 == pytest.approx(1.319,
                                                                 abs=1e-3)
    # a chunk of 2,048 at position 2,048: contexts 2,049 .. 4,096
    want = 2 * 2048 * body + 2 * head + 2048 * 7 * 7 * D \
        + 2 * 8192 * (2048 * (2049 + 4096) // 2)
    assert fam.prefill_flops(sz, 2048, 2048) == pytest.approx(want, rel=1e-12)
    assert want / 1e12 == pytest.approx(2.208, abs=1e-3)
    assert fam.attention_flops(sz, 1000, 1000) == 2 * 8192 * 1000


def test_lfm2_bytes_a_position_a_slot_and_an_expert():
    sz = fam.sizes(CONFIG)
    # K and V of 8 heads of 64 in bf16, two attention layers
    assert fam.kv_bytes_per_token(sz, "bfloat16") == 2 * 2 * 512 * 2 == 4096
    assert fam.decode_read_bytes(sz, 1000) == 1000 * 4096
    # the last two inputs of seven conv layers
    assert fam.state_bytes_per_slot(sz, "bfloat16") == 7 * 2 * D * 2 == 57344
    # gate, up and down of one expert
    assert fam.expert_bytes(sz) == EXPERT * 2 == 18_874_368
    # a decode step that gives every expert of the eight layers a pair
    assert 8 * 64 * fam.expert_bytes(sz) / 1e9 == pytest.approx(9.66,
                                                                abs=0.005)
