"""Model family `xing4`: a four-stream mHC residual path, dense MLA over
the latent cache and expert layers with every expert held, of
`paddle_tpu.models.xing4` (Xing4.0-29B-A4B), as ONE chip serves the
layers it holds whole (`ep_size` 1).

Found by a configuration's `"model": "xing4"`. Builds the model through
the public API at the configuration's widths, names the plain reference,
and keeps with the benchmark the arithmetic a later PR may not change:
the parameter count, model FLOPs a token by context length, the bytes a
decode step must read from the latent pool, the bytes a cached position
holds.
"""

from __future__ import annotations

REFERENCE = "xing4"             # benchmark/reference/xing4.py

#: configuration file key -> Xing4Config field (widths and counts)
_FIELDS = {"hidden_size": "hidden_size", "num_attention_heads": "num_heads",
           "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
           "qk_nope_head_dim": "qk_nope_head_dim",
           "qk_rope_head_dim": "qk_rope_head_dim",
           "v_head_dim": "v_head_dim",
           "intermediate_size": "intermediate_size",
           "moe_intermediate_size": "moe_intermediate_size",
           "n_routed_experts": "n_routed_experts",
           "num_experts_per_tok": "num_experts_per_tok",
           "routed_scaling_factor": "routed_scaling_factor",
           "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters",
           "hc_eps": "hc_eps", "rope_theta": "rope_theta",
           "rms_norm_eps": "rms_norm_eps",
           "initializer_range": "initializer_range",
           "vocab_size": "vocab_size",
           "max_position_embeddings": "max_position_embeddings"}

#: `--rehearse`: the widths of `xing4_tiny`, so the CPU can walk the path
#: (four streams, a dense then an expert layer, 8 experts top-2 all
#: held). Weights of N(0, 0.2): at these widths a sublayer's output is
#: then as large as the stream it is added to, as it is at the published
#: widths with 0.02, so the streams part and `H_res` = identity shows
_REHEARSE = {"initializer_range": 0.2,
             "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
             "kv_lora_rank": 16, "qk_nope_head_dim": 12,
             "qk_rope_head_dim": 4, "v_head_dim": 16,
             "intermediate_size": 128, "moe_intermediate_size": 32,
             "n_routed_experts": 8, "num_experts_per_tok": 2,
             "vocab_size": 256, "max_position_embeddings": 4096,
             "num_hidden_layers": 2, "first_k_dense_replace": 1,
             "layers_held": [1, 2], "experts_held": [0, 8],
             "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                              "mscale": 1, "mscale_all_dim": 1,
                              "original_max_position_embeddings": 32,
                              "type": "yarn"},
             "context_block": 8}


def sizes(config: dict, rehearse: bool = False) -> dict:
    """The numbers of a configuration file this family reads, by
    Xing4Config's field names, and what the reference and the readers
    need of them (`rope`: YaRN's parameters; `mlp_layer_types`: the
    leading dense layers held, then expert layers)."""
    src = {**config, **(_REHEARSE if rehearse else {})}
    out = {field: src[key] for key, field in _FIELDS.items()}
    held, dense = src["layers_held"], src["first_k_dense_replace"]
    ys = src["rope_scaling"]
    assert ys["type"] == "yarn" and len(held) == src["num_hidden_layers"]
    out.update(
        experts_held=tuple(src["experts_held"]),
        mlp_layer_types=("dense",) * dense + ("sparse",) * (len(held) - dense),
        mhc_h_res_clamp=(float(config["mhc_h_res_clamp_min"]),
                         float(config["mhc_h_res_clamp_max"])),
        rope_theta=float(src["rope_theta"]),
        rope=dict(factor=float(ys["factor"]),
                  original_max=int(ys["original_max_position_embeddings"]),
                  beta_fast=float(ys["beta_fast"]),
                  beta_slow=float(ys["beta_slow"]),
                  mscale=float(ys["mscale"]),
                  mscale_all_dim=float(ys["mscale_all_dim"])),
        context_block=src.get("context_block", 512),
        padded_vocab_size=out["vocab_size"])
    # every expert of a layer lives here: the deployment's ep_size is 1
    assert out["experts_held"] == (0, out["n_routed_experts"])
    return out


def build_model(config: dict, seed: int, *, rehearse: bool = False,
                dtype: str = "bfloat16"):
    """`Xing4ForCausalLM` with weights drawn from `seed`, built in
    `dtype` (a float32 build of the published widths would not fit)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
    sz = sizes(config, rehearse)
    rope = sz.pop("rope")
    sz.pop("padded_vocab_size")
    paddle.seed(seed)
    return Xing4ForCausalLM(Xing4Config(
        dtype=dtype, rope_factor=rope["factor"],
        rope_original_max_position=rope["original_max"],
        rope_beta_fast=rope["beta_fast"], rope_beta_slow=rope["beta_slow"],
        rope_mscale=rope["mscale"],
        rope_mscale_all_dim=rope["mscale_all_dim"], **sz))


# -- arithmetic kept with the benchmark ---------------------------------------

def _attention_params(sz: dict) -> int:
    D, H = sz["hidden_size"], sz["num_heads"]
    return (D * sz["q_lora_rank"]
            + sz["q_lora_rank"] * H * (sz["qk_nope_head_dim"]
                                       + sz["qk_rope_head_dim"])
            + D * (sz["kv_lora_rank"] + sz["qk_rope_head_dim"])
            + sz["kv_lora_rank"] * H * (sz["qk_nope_head_dim"]
                                        + sz["v_head_dim"])
            + H * sz["v_head_dim"] * D)


def _mhc_columns(sz: dict) -> int:
    n = sz["hc_mult"]
    return 2 * n + n * n


def param_count(sz: dict, dense_layers: int = None,
                expert_layers: int = None) -> int:
    """Every parameter of the model over `dense_layers` and
    `expert_layers` (default: the layers `sz` holds), the embedding and
    the head: a layer is its attention (with the two latent norms), two
    mHC sublayers (`phi`, 3 gates, `b`), two norms and its FFN; an
    expert layer's FFN is the router (with its bias), the shared expert
    and ALL routed experts. The published widths: 4,792.7 M at the cut
    (1 + 5 layers), 29.5 B whole (2 + 38)."""
    D, n = sz["hidden_size"], sz["hc_mult"]
    if dense_layers is None:
        dense_layers = sum(t == "dense" for t in sz["mlp_layer_types"])
        expert_layers = len(sz["mlp_layer_types"]) - dense_layers
    cols = _mhc_columns(sz)
    layer = (_attention_params(sz) + sz["q_lora_rank"] + sz["kv_lora_rank"]
             + 2 * (n * D * cols + 3 + cols) + 2 * D)
    expert = 3 * D * sz["moe_intermediate_size"]
    return (dense_layers * (layer + 3 * D * sz["intermediate_size"])
            + expert_layers * (layer + D * sz["n_routed_experts"]
                               + sz["n_routed_experts"]
                               + expert * (1 + sz["n_routed_experts"]))
            + 2 * sz["vocab_size"] * D + D)


def matmul_params_per_token(sz: dict, head: bool) -> float:
    """Parameters that sit in a matmul one token passes through: the
    attention projections and both sublayers' `phi` in every layer, the
    dense FFN or the router, the shared expert and the token's top-k
    experts (all held here), and the output head where the token needs
    logits (`head`)."""
    D = sz["hidden_size"]
    both = _attention_params(sz) + 2 * sz["hc_mult"] * D * _mhc_columns(sz)
    expert = 3 * D * sz["moe_intermediate_size"]
    here = sz["num_experts_per_tok"] * sz["experts_held"][1] \
        / sz["n_routed_experts"]
    total = 0.0
    for mlp in sz["mlp_layer_types"]:
        total += both + (3 * D * sz["intermediate_size"] if mlp == "dense"
                         else D * sz["n_routed_experts"]
                         + expert * (1 + here))
    return total + (D * sz["vocab_size"] if head else 0)


def attention_flops(sz: dict, attended, scored=0):
    """FLOPs of the attention products for tokens that together attend
    over `attended` positions in a layer: a head's q.k over the nope and
    rope dims and p.v over the value dim at each position, every layer
    (the model's count, 20,480 a position a token a layer at the
    published widths; the absorbed form a decode step executes multiplies
    against 576 + 512 latent values a head instead). `scored` is what
    `serve.mfu_pct`'s reader hands a family with an indexer: no such
    product here."""
    per = 2.0 * sz["num_heads"] * (sz["qk_nope_head_dim"]
                                   + sz["qk_rope_head_dim"]
                                   + sz["v_head_dim"])
    return len(sz["mlp_layer_types"]) * per * attended


def flops_per_token(sz: dict, ctx: int, head: bool = True) -> float:
    """Model FLOPs of ONE token whose context (itself included) is `ctx`
    positions: 2 x the matmul parameters it passes, attention over ALL
    ctx positions in every layer (no selection). The published widths on
    this chip's six layers at ctx 3,000: 2.41 GFLOP with the head."""
    return 2.0 * matmul_params_per_token(sz, head) \
        + attention_flops(sz, ctx)


def prefill_flops(sz: dict, pos: int, n: int) -> float:
    """Model FLOPs of a prompt chunk of `n` tokens at positions
    `pos .. pos + n - 1` (contexts pos + 1 .. pos + n), logits for its
    last token only."""
    return (2.0 * n * matmul_params_per_token(sz, False)
            + 2.0 * sz["hidden_size"] * sz["vocab_size"]
            + attention_flops(sz, sum(range(pos + 1, pos + n + 1))))


def decode_read_bytes(sz: dict, positions, cache_dtype: str = "bfloat16"):
    """Bytes the decode steps must read from the latent pool for
    slot-steps that together have `positions` cached positions to attend
    over (pos + 1 a slot): the 576 latent values of each, ONCE a layer
    (keys and values are the same row), in every layer — 6 x 1,152 B a
    position at the published widths in bf16."""
    import jax.numpy as jnp
    return float(positions) * len(sz["mlp_layer_types"]) \
        * (sz["kv_lora_rank"] + sz["qk_rope_head_dim"]) \
        * jnp.dtype(cache_dtype).itemsize


def kv_bytes_per_token(sz: dict, cache_dtype: str) -> int:
    """Bytes one cached position HOLDS: the latent in every layer as
    stored — a row wider than 128 lanes is rounded up to whole lanes
    (576 values take 640; `decode_read_bytes` counts the 576 that are
    needed). Published widths in bf16: 6 x 1,280 = 7,680 B, of which
    6,912 B are payload."""
    import jax.numpy as jnp
    w = sz["kv_lora_rank"] + sz["qk_rope_head_dim"]
    lanes = w if w <= 128 else -(-w // 128) * 128
    return jnp.dtype(cache_dtype).itemsize * len(sz["mlp_layer_types"]) * lanes
