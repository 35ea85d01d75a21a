"""GPT-2 forward and loss in plain jax.numpy, float32 — the yardstick.

Follows Radford et al. 2019 ("Language Models are Unsupervised Multitask
Learners") and the released `gpt-2/src/model.py`: token + learned
position embeddings, N pre-LayerNorm blocks (x + attn(ln1(x)); x +
mlp(ln2(x))), causal multi-head attention scaled by 1/sqrt(head size),
a 4x feed-forward with the tanh-approximated GELU of the release,
LayerNorm eps 1e-5, a final LayerNorm and the output projection tied to
the token embedding. No kernels, no cache, no dropout (evaluation), no
batching tricks; every matmul at `jax.default_matmul_precision("highest")`
because a float32 dot on a TPU is a bf16 pass otherwise.

It takes the system's weights BY NAME (the `state_dict` names of
`paddle_tpu.models.gpt.GPTForPretraining`) and nothing else from the
program. Departures from the published description, all of layout and
none of mathematics:

- the vocabulary is whatever the embedding holds (the configurations pad
  50257 to 50304 for the MXU; the padding rows are ordinary rows here
  and in the system, so the softmax runs over 50304 logits in both);
- q, k and v come from one fused weight `[E, 3, H, D]` and bias
  `[3, H, D]` (the release's `c_attn` is the same matrix reshaped), the
  output projection is `[H, D, E]`;
- the loss is the mean over ALL positions of the batch of the
  cross-entropy against `labels` (the caller shifts).

A gradient check against `TrainStep` needs a hook into it and is left to
a later PR (PERF.md, Open questions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def num_layers(weights) -> int:
    return 1 + max(int(k.split(".")[2]) for k in weights
                   if k.startswith("gpt.layers."))


_BLOCK_KEYS = ("ln1.weight", "ln1.bias", "attn.qkv_weight", "attn.qkv_bias",
               "attn.out_weight", "attn.out_bias", "ln2.weight", "ln2.bias",
               "mlp.w_in", "mlp.b_in", "mlp.w_out", "mlp.b_out")


@jax.jit
def _embed(ids, wte, wpe):
    S = ids.shape[1]
    return wte.astype(jnp.float32)[ids] + wpe.astype(jnp.float32)[:S][None]


@jax.jit
def _block(x, w):
    """One pre-LN block. Jitted alone and called once per layer, so a
    36-layer reference compiles one block, not 36."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    S = x.shape[1]
    with jax.default_matmul_precision("highest"):
        h = _layer_norm(x, w["ln1.weight"], w["ln1.bias"])
        qkv = jnp.einsum("bse,ethd->bsthd", h, w["attn.qkv_weight"]) \
            + w["attn.qkv_bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("bshd,hde->bse", a, w["attn.out_weight"]) \
            + w["attn.out_bias"]
        h = _layer_norm(x, w["ln2.weight"], w["ln2.bias"])
        h = _gelu_tanh(h @ w["mlp.w_in"] + w["mlp.b_in"])
        return x + h @ w["mlp.w_out"] + w["mlp.b_out"]


@jax.jit
def _head(x, ln_w, ln_b, wte):
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, ln_w.astype(jnp.float32), ln_b.astype(jnp.float32))
        return jnp.einsum("bse,ve->bsv", x, wte.astype(jnp.float32))


def forward(weights, ids):
    """`weights`: name -> array (any float dtype; read as float32).
    `ids`: int [B, S]. Returns float32 logits [B, S, V]."""
    wte = weights["gpt.word_embeddings.weight"]
    x = _embed(jnp.asarray(ids), wte, weights["gpt.position_embeddings.weight"])
    for i in range(num_layers(weights)):
        p = f"gpt.layers.{i}."
        x = _block(x, {k: weights[p + k] for k in _BLOCK_KEYS})
    return _head(x, weights["gpt.final_norm.weight"],
                 weights["gpt.final_norm.bias"], wte)


@jax.jit
def loss(logits, labels):
    """Mean cross-entropy over every position, float32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


def forward_and_loss(weights, ids, labels):
    """(loss, logits at the last position [B, V]) — what `correct` reads."""
    logits = forward(weights, ids)
    return loss(logits, labels), logits[:, -1]
