"""Model family `gpt`: GPT-2 style decoders of `paddle_tpu.models.gpt`.

Found by a configuration's `"model": "gpt"`. Builds the model and loss
through the public API, and keeps with the benchmark the arithmetic a
later PR may not change: FLOPs per item, the FLOPs and bytes each kernel
needs for a cell's shapes, the name of the plain reference.
"""

from __future__ import annotations

REFERENCE = "gpt2"          # benchmark/reference/gpt2.py

#: configuration file key -> GPTConfig field
_FIELDS = {"n_embd": "hidden_size", "n_layer": "num_layers",
           "n_head": "num_heads", "n_inner": "intermediate_size",
           "n_positions": "max_position_embeddings",
           "padded_vocab_size": "vocab_size",
           "resid_pdrop": "hidden_dropout_prob",
           "attn_pdrop": "attention_dropout_prob",
           "initializer_range": "initializer_range"}

#: `--rehearse`: the widths of `gpt_tiny`, so the CPU can walk the path
_REHEARSE = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 256,
             "n_positions": 128, "padded_vocab_size": 256}


def sizes(config: dict, rehearse: bool = False) -> dict:
    """The numbers of a configuration file this family reads."""
    out = {k: config[k] for k in _FIELDS}
    if rehearse:
        out.update(_REHEARSE)
    return out


def build_model(config: dict, seed: int, *, rehearse: bool = False,
                **overrides):
    """`GPTForPretraining` with weights drawn from `seed`. `overrides`
    are GPTConfig fields a cell sets (use_recompute, a dropout the cell
    had to turn off)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    sz = sizes(config, rehearse)
    fields = {_FIELDS[k]: v for k, v in sz.items()}
    fields.update(overrides)
    paddle.seed(seed)
    return GPTForPretraining(GPTConfig(**fields))


def make_loss_fn(amp_level: str):
    """The loss a trainer of this family runs: next-token cross-entropy
    under autocast."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    crit = GPTPretrainingCriterion()

    def loss_fn(layer, ids, labels):
        with paddle.amp.auto_cast(level=amp_level):
            return crit(layer(ids), labels)
    return loss_fn


def eval_loss_and_outputs(model, amp_level: str):
    """The system's side of the train cells' `correct`: evaluation-mode
    loss and the outputs that are compared (for this family the logits
    at the last position), in the compute type it trains in, as one
    jitted function of (params, *batch); the reference's
    `forward_and_loss(params, *batch)` returns the same pair."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.functional import functional_call
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    crit = GPTPretrainingCriterion()

    @jax.jit
    def fn(params, ids, labels):
        with paddle.amp.auto_cast(level=amp_level):
            logits, _ = functional_call(model, params, ids, training=False)
            loss = crit(Tensor(logits), Tensor(labels))
        return loss._data, logits[:, -1]
    return fn


# -- what a trainer of this family feeds on -----------------------------------

def _seq(config: dict, mix: dict, rehearse: bool) -> int:
    n_pos = sizes(config, rehearse)["n_positions"]
    return min(int(mix["seq"]), n_pos // 4 if rehearse else n_pos)


def dataset(config: dict, mix: dict, seed: int, *, rehearse: bool = False):
    """Map-style dataset of one sample of a train mix, a tuple of
    arrays (here ids[S], labels[S]); `paddle.io.DataLoader` batches it
    and the runner hands a batch to the step, the evaluation function
    and the reference as `*batch`."""
    from benchmark.harness.loadgen import TokenStream
    return TokenStream(mix, sizes(config, rehearse)["padded_vocab_size"],
                       _seq(config, mix, rehearse), seed)


def items_per_step(config: dict, mix: dict, *, rehearse: bool = False) -> int:
    """Items (the configuration's `item`: tokens) one step trains."""
    return int(mix["batch"]) * _seq(config, mix, rehearse)


def step_counts(config: dict, mix: dict, *, rehearse: bool = False) -> dict:
    """What the per-layer readers need of the arithmetic below for one
    training step of this mix, by the names they read in `run.counts`."""
    B, S = int(mix["batch"]), _seq(config, mix, rehearse)
    return {"flops_per_item": flops_per_item(config, S),
            "flash_flops_per_step": flash_flops_per_step(config, B, S)}


# -- arithmetic kept with the benchmark ---------------------------------------

def flops_per_item(config: dict, seq: int) -> float:
    """Training FLOPs one token needs, forward and backward, nothing
    recomputed: 6 x (parameters that sit in a matmul) + 12 L S E for
    the attention products (PaLM appendix B, which counts the full
    S x S product; copied from bench.py `gpt_flops_per_token`).
    GPT-2 345M at S=1024: 2.42 GFLOP; 774M: 5.20 GFLOP."""
    E, L = config["n_embd"], config["n_layer"]
    V = config["padded_vocab_size"]
    ffn = config["n_inner"]
    p_block = L * (4 * E * E + 2 * E * ffn)
    return 6.0 * (p_block + V * E) + 12.0 * L * E * seq


def flash_flops_per_step(config: dict, batch: int, seq: int) -> float:
    """FLOPs the flash kernels of ONE training step need over all
    layers and the whole batch: the two products of the forward
    (Q K^T, P V) and the four of the backward (dV, dP, dQ, dK), each
    2 B H S S D, the causal half counted once. Not counted: the
    forward that recompute runs a second time, nor the Q K^T the
    backward kernel rebuilds — the kernels' TIME includes both, so the
    share says what the needed work costs."""
    H, D = config["n_head"], config["n_embd"] // config["n_head"]
    per_product = 2.0 * batch * H * seq * seq * D / 2.0
    return config["n_layer"] * 6 * per_product


def kv_bytes_per_token(config: dict, cache_dtype: str) -> int:
    """K and V bytes one cached position holds across all layers."""
    import jax.numpy as jnp
    return 2 * config["n_layer"] * config["n_embd"] * jnp.dtype(cache_dtype).itemsize
