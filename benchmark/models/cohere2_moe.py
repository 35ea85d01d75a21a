"""Model family `cohere2_moe`: window and full attention layers over
grouped K/V heads and a parallel block of sparse and averaged shared
experts, of `paddle_tpu.models.cohere2_moe` (Command A+), as ONE chip of
an expert-parallel group serves it.

Found by a configuration's `"model": "cohere2_moe"`. Builds the model
through the public API at the configuration's widths, names the plain
reference, and keeps with the benchmark the arithmetic a later PR may
not change: model FLOPs a token by context length with the window in
them, the bytes a decode step must read from the pages of each lifetime,
the bytes a cached position holds.
"""

from __future__ import annotations

REFERENCE = "cohere2_moe"       # benchmark/reference/cohere2_moe.py

WINDOW, FULL = "sliding_attention", "full_attention"

#: configuration file key -> Cohere2MoeConfig field (widths and counts)
_FIELDS = {"hidden_size": "hidden_size", "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "intermediate_size",
           "num_experts_per_tok": "num_experts_per_tok",
           "num_shared_experts": "n_shared_experts",
           "sliding_window": "sliding_window",
           "layer_norm_eps": "layer_norm_eps", "logit_scale": "logit_scale",
           "initializer_range": "initializer_range",
           "vocab_size": "vocab_size",
           "max_position_embeddings": "max_position_embeddings"}

#: `--rehearse`: the widths of `cohere2_moe_tiny`, so the CPU can walk
#: the path (same period, 8 query on 2 K/V heads, a window of 8, 2 of 8
#: experts held, 2 shared)
_REHEARSE = {"hidden_size": 64, "num_attention_heads": 8,
             "num_key_value_heads": 2, "head_dim": 16,
             "intermediate_size": 32, "num_experts_per_tok": 2,
             "num_shared_experts": 2, "sliding_window": 8,
             "vocab_size": 256, "max_position_embeddings": 4096,
             "routed_experts_routed_over": 8, "experts_held": [2, 2],
             "context_block": 8}


def sizes(config: dict, rehearse: bool = False) -> dict:
    """The numbers of a configuration file this family reads, by
    Cohere2MoeConfig's field names, and what the reference and the
    readers need of them (`mlp_layer_types`: every layer is an expert
    layer)."""
    src = {**config, **(_REHEARSE if rehearse else {})}
    out = {field: src[key] for key, field in _FIELDS.items()}
    held = src["layers_held"]
    out.update(
        n_routed_experts=src["routed_experts_routed_over"],
        experts_held=tuple(src["experts_held"]),
        layer_types=tuple(src["layer_types"][i] for i in held),
        mlp_layer_types=("sparse",) * len(held),
        rope_theta=float(src["rope_parameters"]["rope_theta"]),
        context_block=src.get("context_block", 256),
        padded_vocab_size=out["vocab_size"])
    assert len(held) == src["num_hidden_layers"]
    assert out["experts_held"][1] == (2 if rehearse
                                      else config["num_experts"])
    return out


def build_model(config: dict, seed: int, *, rehearse: bool = False,
                dtype: str = "bfloat16"):
    """`Cohere2MoeForCausalLM` with weights drawn from `seed`, built in
    `dtype` (a float32 build of the published widths would not fit)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                               Cohere2MoeForCausalLM)
    sz = sizes(config, rehearse)
    for key in ("padded_vocab_size", "mlp_layer_types"):
        sz.pop(key)
    paddle.seed(seed)
    return Cohere2MoeForCausalLM(Cohere2MoeConfig(dtype=dtype, **sz))


# -- arithmetic kept with the benchmark ---------------------------------------

def _layers(sz: dict) -> tuple:
    """(full layers, window layers) held."""
    return (sum(t == FULL for t in sz["layer_types"]),
            sum(t == WINDOW for t in sz["layer_types"]))


def matmul_params_per_token(sz: dict, head: bool) -> float:
    """Parameters that sit in a matmul one token passes through on this
    chip: the four attention projections, the router, the shared experts
    and the token's EXPECTED share of the held experts (top-k x held /
    routed-over of an expert each), every layer, and the tied output
    head where the token needs logits (`head`)."""
    D, dh = sz["hidden_size"], sz["head_dim"]
    attn = 2 * D * sz["num_heads"] * dh + 2 * D * sz["num_kv_heads"] * dh
    expert = 3 * D * sz["intermediate_size"]
    here = sz["num_experts_per_tok"] * sz["experts_held"][1] \
        / sz["n_routed_experts"]
    layer = attn + D * sz["n_routed_experts"] \
        + expert * (sz["n_shared_experts"] + here)
    return len(sz["layer_types"]) * layer \
        + (D * sz["vocab_size"] if head else 0)


def attention_flops(sz: dict, full_positions, window_positions):
    """FLOPs of the attention products for tokens that together attend
    over `full_positions` positions in a full layer and
    `window_positions` in a window layer: a query head's q.k and p.v at
    each attended position (65,536 FLOP a position a token a layer at
    128 heads of 128)."""
    per = 4.0 * sz["num_heads"] * sz["head_dim"]
    n_full, n_win = _layers(sz)
    return per * (n_full * full_positions + n_win * window_positions)


def prefill_flops(sz: dict, pos: int, n: int) -> float:
    """Model FLOPs of a prompt chunk of `n` tokens at positions
    `pos .. pos + n - 1` (contexts pos + 1 .. pos + n; a window layer
    attends over min(context, window) of them), logits for its last
    token only."""
    W = sz["sliding_window"]
    ctxs = range(pos + 1, pos + n + 1)
    return (2.0 * n * matmul_params_per_token(sz, False)
            + 2.0 * sz["hidden_size"] * sz["vocab_size"]
            + attention_flops(sz, sum(ctxs), sum(min(c, W) for c in ctxs)))


def decode_read_bytes(sz: dict, read_full, read_window,
                      cache_dtype: str = "bfloat16") -> float:
    """Bytes the decode steps must read from the pages for slot-steps
    that together have `read_full` positions to read in a full layer
    (pos + 1 a slot) and `read_window` in a window layer (min(pos + 1,
    window)): K and V rows of `num_kv_heads x head_dim` each."""
    import jax.numpy as jnp
    row = 2 * sz["num_kv_heads"] * sz["head_dim"] \
        * jnp.dtype(cache_dtype).itemsize
    n_full, n_win = _layers(sz)
    return float(row) * (n_full * read_full + n_win * read_window)


def kv_bytes_per_token(sz: dict, cache_dtype: str) -> int:
    """Bytes one cached position HOLDS while every layer keeps it: K and
    V rows of `num_kv_heads x head_dim` in each layer (published widths
    in bf16: 4 x 4 KiB). A window layer keeps only the last
    `sliding_window` positions of a slot."""
    import jax.numpy as jnp
    return jnp.dtype(cache_dtype).itemsize * len(sz["layer_types"]) \
        * 2 * sz["num_kv_heads"] * sz["head_dim"]
