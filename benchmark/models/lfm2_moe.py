"""Model family `lfm2_moe`: gated short-convolution and GQA attention
layers, a leading dense FFN and expert layers with every expert held, of
`paddle_tpu.models.lfm2_moe` (LFM2-24B-A2B), as ONE chip serves the
layers it holds whole (EP1).

Found by a configuration's `"model": "lfm2_moe"`. Builds the model
through the public API at the configuration's widths, names the plain
reference, and keeps with the benchmark the arithmetic a later PR may
not change: the parameter count, model FLOPs a token by context length,
the bytes a decode step must read of the K/V pages and of an expert,
the bytes a cached position and a slot's state hold.
"""

from __future__ import annotations

REFERENCE = "lfm2_moe"          # benchmark/reference/lfm2_moe.py

#: configuration file key -> Lfm2MoeConfig field (widths and counts)
_FIELDS = {"hidden_size": "hidden_size", "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "intermediate_size": "intermediate_size",
           "moe_intermediate_size": "moe_intermediate_size",
           "num_experts": "n_routed_experts",
           "num_experts_per_tok": "num_experts_per_tok",
           "routed_scaling_factor": "routed_scaling_factor",
           "conv_L_cache": "conv_L_cache", "norm_eps": "rms_norm_eps",
           "initializer_range": "initializer_range",
           "vocab_size": "vocab_size",
           "max_position_embeddings": "max_position_embeddings"}

#: the renormalization's eps (configuration's `assumed.routing`)
ROUTER_EPS = 1e-6

#: `--rehearse`: the widths of `lfm2_moe_tiny`, so the CPU can walk the
#: path (a dense conv layer, then attention and three conv layers as
#: expert layers; 8 query heads on 2 K/V heads, 8 experts top-4 all
#: held). Weights of N(0, 0.2): at these widths a layer's output is then
#: as large as the row it is added to, as it is at the published widths
#: with 0.02, so the carried state shows
_REHEARSE = {"initializer_range": 0.2,
             "hidden_size": 64, "num_attention_heads": 8,
             "num_key_value_heads": 2, "intermediate_size": 128,
             "moe_intermediate_size": 32, "num_experts": 8,
             "num_experts_per_tok": 4, "vocab_size": 256,
             "max_position_embeddings": 4096, "num_hidden_layers": 5,
             "num_dense_layers": 1,
             "layer_types": ["conv", "full_attention", "conv", "conv",
                             "conv"],
             "layers_held": [1, 2, 3, 4, 5], "experts_held": [0, 8],
             "context_block": 8}


def sizes(config: dict, rehearse: bool = False) -> dict:
    """The numbers of a configuration file this family reads, by
    Lfm2MoeConfig's field names, and what the reference and the readers
    need of them (`layer_types`, `mlp_layer_types`: the leading dense
    layers held, then expert layers)."""
    src = {**config, **(_REHEARSE if rehearse else {})}
    out = {field: src[key] for key, field in _FIELDS.items()}
    held, dense = src["layers_held"], src["num_dense_layers"]
    assert len(held) == src["num_hidden_layers"] == len(src["layer_types"])
    out.update(
        head_dim=out["hidden_size"] // out["num_heads"],
        experts_held=tuple(src["experts_held"]),
        layer_types=tuple(src["layer_types"]),
        mlp_layer_types=("dense",) * dense + ("sparse",) * (len(held) - dense),
        rope_theta=float(src["rope_parameters"]["rope_theta"]),
        router_eps=ROUTER_EPS,
        context_block=src.get("context_block", 256),
        padded_vocab_size=out["vocab_size"])
    # every expert of a layer lives here
    assert out["experts_held"] == (0, out["n_routed_experts"])
    return out


def build_model(config: dict, seed: int, *, rehearse: bool = False,
                dtype: str = "bfloat16"):
    """`Lfm2MoeForCausalLM` with weights drawn from `seed`, built in
    `dtype`. Imports the family first: a program without it fails here,
    before anything is drawn."""
    import paddle_tpu as paddle
    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeForCausalLM
    sz = sizes(config, rehearse)
    sz.pop("padded_vocab_size")
    paddle.seed(seed)
    return Lfm2MoeForCausalLM(Lfm2MoeConfig(dtype=dtype, **sz))


# -- arithmetic kept with the benchmark ---------------------------------------

def _layers(sz: dict, kind: str) -> int:
    return sum(t == kind for t in sz["layer_types"])


def _conv_params(sz: dict) -> int:
    D = sz["hidden_size"]
    return D * 3 * D + sz["conv_L_cache"] * D + D * D


def _attention_params(sz: dict) -> int:
    D, dh = sz["hidden_size"], sz["head_dim"]
    return (2 * D * sz["num_heads"] * dh + 2 * D * sz["num_kv_heads"] * dh
            + 2 * dh)


def expert_params(sz: dict) -> int:
    """One routed expert: gate, up and down of `moe_intermediate_size`."""
    return 3 * sz["hidden_size"] * sz["moe_intermediate_size"]


def expert_bytes(sz: dict, dtype: str = "bfloat16") -> int:
    """Bytes of ONE expert's weights: what a decode step reads of an
    expert given at least one pair (3 x 2,048 x 1,536 x 2 B = 18.87 MB
    at the published widths in bf16)."""
    import jax.numpy as jnp
    return expert_params(sz) * jnp.dtype(dtype).itemsize


def param_count(sz: dict) -> int:
    """Every parameter of the layers `sz` holds (their operators as
    `layer_types` says, two norms each, the dense FFN or the router with
    its bias and ALL routed experts), the embedding (the head is tied to
    it) and the final norm: 5,178.3 M at the published widths on this
    cut. The whole model is `sz` with the published `layer_types` and
    its `mlp_layer_types`."""
    D = sz["hidden_size"]
    ffn = {"dense": 3 * D * sz["intermediate_size"],
           "sparse": D * sz["n_routed_experts"] + sz["n_routed_experts"]
           + sz["n_routed_experts"] * expert_params(sz)}
    ops = {"conv": _conv_params(sz), "full_attention": _attention_params(sz)}
    return sum(ops[op] + ffn[mlp] + 2 * D for op, mlp in
               zip(sz["layer_types"], sz["mlp_layer_types"])) \
        + sz["vocab_size"] * D + D


def matmul_params_per_token(sz: dict, head: bool) -> float:
    """Parameters that sit in a matmul one token passes through: every
    operator's projections (the conv's in- and out-projection; the
    attention's four), the dense FFN or the router and the token's
    top-k experts, and the head where the token needs logits (`head`).
    The depthwise taps are elementwise, not a matmul."""
    D = sz["hidden_size"]
    total = 0.0
    for op, mlp in zip(sz["layer_types"], sz["mlp_layer_types"]):
        total += 4 * D * D if op == "conv" else \
            _attention_params(sz) - 2 * sz["head_dim"]
        total += 3 * D * sz["intermediate_size"] if mlp == "dense" else \
            D * sz["n_routed_experts"] \
            + sz["num_experts_per_tok"] * expert_params(sz)
    return total + (D * sz["vocab_size"] if head else 0)


def attention_flops(sz: dict, attended, scored=0):
    """FLOPs of the attention products for tokens that together attend
    over `attended` positions in a layer: q.k and p.v of every query head
    at each position, in every attention layer (2 x 32 x 2 x 64 = 8,192
    a position a token a layer at the published widths). `scored` is
    what `serve.mfu_pct`'s reader hands a family with an indexer: none
    here."""
    per = 2.0 * sz["num_heads"] * 2 * sz["head_dim"]
    return _layers(sz, "full_attention") * per * attended


def conv_flops(sz: dict, tokens) -> float:
    """The conv layers' elementwise work for `tokens` tokens: u = B * X,
    L taps, C * z (2 L + 1 FLOPs a lane), every conv layer."""
    return _layers(sz, "conv") * (2 * sz["conv_L_cache"] + 1) \
        * sz["hidden_size"] * float(tokens)


def flops_per_token(sz: dict, ctx: int, head: bool = True) -> float:
    """Model FLOPs of ONE token whose context (itself included) is `ctx`
    positions: 2 x the matmul parameters it passes, the conv layers'
    elementwise work, attention over all ctx positions in each attention
    layer. The published widths on this chip's nine layers at ctx 1,410:
    1.319 GFLOP with the head."""
    return 2.0 * matmul_params_per_token(sz, head) + conv_flops(sz, 1) \
        + attention_flops(sz, ctx)


def prefill_flops(sz: dict, pos: int, n: int) -> float:
    """Model FLOPs of a prompt chunk of `n` tokens at positions
    `pos .. pos + n - 1` (contexts pos + 1 .. pos + n), logits for its
    last token only."""
    return (2.0 * n * matmul_params_per_token(sz, False)
            + 2.0 * sz["hidden_size"] * sz["vocab_size"] + conv_flops(sz, n)
            + attention_flops(sz, sum(range(pos + 1, pos + n + 1))))


def decode_read_bytes(sz: dict, positions, cache_dtype: str = "bfloat16"):
    """Bytes the decode steps must read from the K/V pages for
    slot-steps that together have `positions` cached positions to attend
    over (pos + 1 a slot): K and V of the 8 K/V heads ONCE (the 4 query
    heads of a group share them), in each attention layer — 2 x 2,048 B
    a position at the published widths in bf16."""
    import jax.numpy as jnp
    return float(positions) * _layers(sz, "full_attention") * 2 \
        * sz["num_kv_heads"] * sz["head_dim"] * jnp.dtype(cache_dtype).itemsize


def kv_bytes_per_token(sz: dict, cache_dtype: str) -> int:
    """Bytes one cached position HOLDS: K and V of every attention layer
    (2 x 2 x 512 x 2 B = 4,096 B at the published widths in bf16)."""
    import jax.numpy as jnp
    return jnp.dtype(cache_dtype).itemsize * _layers(sz, "full_attention") \
        * 2 * sz["num_kv_heads"] * sz["head_dim"]


def state_bytes_per_slot(sz: dict, cache_dtype: str) -> int:
    """Bytes a slot's conv state holds, whatever its context: the last
    `conv_L_cache - 1` inputs of every conv layer (7 x 2 x 2,048 x 2 B =
    57,344 B at the published widths in bf16)."""
    import jax.numpy as jnp
    return jnp.dtype(cache_dtype).itemsize * _layers(sz, "conv") \
        * (sz["conv_L_cache"] - 1) * sz["hidden_size"]
