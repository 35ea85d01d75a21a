"""Model family `glm_moe_dsa`: MLA + learned sparse attention + expert
layers of `paddle_tpu.models.glm_moe_dsa` (GLM-5.2), as ONE chip of an
expert-parallel group serves it.

Found by a configuration's `"model": "glm_moe_dsa"`. Builds the model
through the public API at the configuration's widths, names the plain
reference, and keeps with the benchmark the arithmetic a later PR may
not change: model FLOPs a token by context length, the bytes a decode
step must read from each pool, the bytes a cached position holds.
"""

from __future__ import annotations

REFERENCE = "glm_moe_dsa"       # benchmark/reference/glm_moe_dsa.py

#: configuration file key -> GlmMoeDsaConfig field (widths and counts)
_FIELDS = {"hidden_size": "hidden_size", "num_attention_heads": "num_heads",
           "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
           "qk_nope_head_dim": "qk_nope_head_dim",
           "qk_rope_head_dim": "qk_rope_head_dim",
           "v_head_dim": "v_head_dim", "index_n_heads": "index_n_heads",
           "index_head_dim": "index_head_dim", "index_topk": "index_topk",
           "intermediate_size": "intermediate_size",
           "moe_intermediate_size": "moe_intermediate_size",
           "num_experts_per_tok": "num_experts_per_tok",
           "routed_scaling_factor": "routed_scaling_factor",
           "rms_norm_eps": "rms_norm_eps",
           "initializer_range": "initializer_range",
           "padded_vocab_size": "vocab_size",
           "max_position_embeddings": "max_position_embeddings"}

#: `--rehearse`: the widths of `glm_moe_dsa_tiny`, so the CPU can walk
#: the path (same layer pattern, 2 of 8 experts held, index_topk 8)
_REHEARSE = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
             "kv_lora_rank": 16, "qk_nope_head_dim": 12,
             "qk_rope_head_dim": 4, "v_head_dim": 16, "index_n_heads": 2,
             "index_head_dim": 8, "index_topk": 8, "intermediate_size": 128,
             "moe_intermediate_size": 32, "num_experts_per_tok": 2,
             "padded_vocab_size": 256, "max_position_embeddings": 4096,
             "routed_experts_routed_over": 8, "experts_held": [2, 2],
             "context_block": 8}


def sizes(config: dict, rehearse: bool = False) -> dict:
    """The numbers of a configuration file this family reads, by
    GlmMoeDsaConfig's field names, and what the reference needs of
    them."""
    src = {**config, **(_REHEARSE if rehearse else {})}
    out = {field: src[key] for key, field in _FIELDS.items()}
    held = src["layers_held"]
    out.update(
        n_routed_experts=src["routed_experts_routed_over"],
        experts_held=tuple(src["experts_held"]),
        mlp_layer_types=tuple("sparse" if src["mlp_layer_types"][i] == "sparse"
                              else "dense" for i in held),
        indexer_types=tuple(src["indexer_types"][i] for i in held),
        rope_theta=float(src["rope_parameters"]["rope_theta"]),
        context_block=src.get("context_block", 512),
        padded_vocab_size=out["vocab_size"])
    assert len(held) == src["num_hidden_layers"]
    assert out["experts_held"][1] == (2 if rehearse
                                      else config["n_routed_experts"])
    return out


def build_model(config: dict, seed: int, *, rehearse: bool = False,
                dtype: str = "bfloat16"):
    """`GlmMoeDsaForCausalLM` with weights drawn from `seed`, built in
    `dtype` (a float32 build of the published widths would not fit)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                               GlmMoeDsaForCausalLM)
    sz = sizes(config, rehearse)
    sz.pop("padded_vocab_size")
    paddle.seed(seed)
    return GlmMoeDsaForCausalLM(GlmMoeDsaConfig(dtype=dtype, **sz))


# -- arithmetic kept with the benchmark ---------------------------------------

def matmul_params_per_token(sz: dict, head: bool) -> float:
    """Parameters that sit in a matmul one token passes through on this
    chip: attention and indexer projections, the dense FFN or the
    router, the shared expert and the token's EXPECTED share of the
    held experts (top-k x held / routed-over of an expert each), and
    the output head where the token needs logits (`head`)."""
    D, H = sz["hidden_size"], sz["num_heads"]
    attn = (D * sz["q_lora_rank"]
            + sz["q_lora_rank"] * H * (sz["qk_nope_head_dim"]
                                       + sz["qk_rope_head_dim"])
            + D * (sz["kv_lora_rank"] + sz["qk_rope_head_dim"])
            + sz["kv_lora_rank"] * H * (sz["qk_nope_head_dim"]
                                        + sz["v_head_dim"])
            + H * sz["v_head_dim"] * D)
    indexer = (sz["q_lora_rank"] * sz["index_n_heads"] * sz["index_head_dim"]
               + D * sz["index_head_dim"] + D * sz["index_n_heads"])
    expert = 3 * D * sz["moe_intermediate_size"]
    here = sz["num_experts_per_tok"] * sz["experts_held"][1] \
        / sz["n_routed_experts"]
    total = 0.0
    for mlp, ind in zip(sz["mlp_layer_types"], sz["indexer_types"]):
        total += attn + (indexer if ind == "full" else 0)
        total += (3 * D * sz["intermediate_size"] if mlp == "dense"
                  else D * sz["n_routed_experts"] + expert * (1 + here))
    return total + (D * sz["vocab_size"] if head else 0)


def attention_flops(sz: dict, attended, scored):
    """FLOPs of the attention products and of the index scores for
    tokens that together attend over `attended` selected positions and
    score `scored` cached index keys: a head's q.k (nope + rope dims)
    and p.v at each attended position, every layer; an index head's
    qI.kI at each scored key, `full` layers."""
    per_attended = 2.0 * sz["num_heads"] * (
        sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"] + sz["v_head_dim"])
    per_scored = 2.0 * sz["index_n_heads"] * sz["index_head_dim"]
    n_full = sum(t == "full" for t in sz["indexer_types"])
    return (len(sz["indexer_types"]) * per_attended * attended
            + n_full * per_scored * scored)


def flops_per_token(sz: dict, ctx: int, head: bool = True) -> float:
    """Model FLOPs of ONE token whose context (itself included) is
    `ctx` positions: 2 x the matmul parameters it passes, attention over
    the min(ctx, index_topk) selected positions, index scores over all
    ctx. The published widths at ctx 10,000 on this chip's share: 3.7
    GFLOP with the head, 3.4 without."""
    return (2.0 * matmul_params_per_token(sz, head)
            + attention_flops(sz, min(ctx, sz["index_topk"]), ctx))


def prefill_flops(sz: dict, pos: int, n: int) -> float:
    """Model FLOPs of a prompt chunk of `n` tokens at positions
    `pos .. pos + n - 1` (contexts pos + 1 .. pos + n), logits for its
    last token only."""
    k = sz["index_topk"]
    ctxs = range(pos + 1, pos + n + 1)
    return (2.0 * n * matmul_params_per_token(sz, False)
            + 2.0 * sz["hidden_size"] * sz["vocab_size"]
            + attention_flops(sz, sum(min(c, k) for c in ctxs), sum(ctxs)))


def decode_read_bytes(sz: dict, selected: int, available: int,
                      cache_dtype: str) -> dict:
    """Bytes the decode steps must read from each pool for slot-steps
    that together selected `selected` positions out of `available`
    cached: the latent rows of the selected positions in every layer,
    every cached index key in the `full` layers."""
    import jax.numpy as jnp
    b = jnp.dtype(cache_dtype).itemsize
    n_full = sum(t == "full" for t in sz["indexer_types"])
    return {"latent": len(sz["indexer_types"]) * selected * b
            * (sz["kv_lora_rank"] + sz["qk_rope_head_dim"]),
            "index": n_full * available * b * sz["index_head_dim"]}


def kv_bytes_per_token(sz: dict, cache_dtype: str) -> int:
    """Bytes one cached position HOLDS: the latent in every layer, the
    index key in `full` layers, each as stored — a row wider than 128
    lanes is rounded up to whole lanes (the latent's 576 values take
    640; `decode_read_bytes` counts the 576 that are needed). Published
    widths in bf16: 5 x 1,280 + 2 x 256 = 6,912 B, of which 6,272 B
    are payload."""
    import jax.numpy as jnp
    lanes = lambda w: w if w <= 128 else -(-w // 128) * 128
    n_full = sum(t == "full" for t in sz["indexer_types"])
    return jnp.dtype(cache_dtype).itemsize * (
        len(sz["indexer_types"])
        * lanes(sz["kv_lora_rank"] + sz["qk_rope_head_dim"])
        + n_full * lanes(sz["index_head_dim"]))
