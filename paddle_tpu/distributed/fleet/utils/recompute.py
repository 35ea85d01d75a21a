"""Fleet utilities: activation recomputation.

reference parity: python/paddle/distributed/fleet/utils/recompute.py
(RecomputeFunction.forward/backward:63,182 — CUDA RNG-state stashing +
re-forward under enable_grad). The TPU-native redesign is `jax.checkpoint`:
under jit the XLA backward rematerializes the segment instead of saving
activations; in eager the tape's VJP closure holds only the segment inputs
and the values the segment names as worth keeping (a flash-attention
kernel's output and log-sum-exp; a GPT block's products and its
attention branch: :func:`resolve_checkpoint_policy`).
RNG consistency is free here — dropout keys are split at Python trace time
(core/random.trace_rng), so the rematerialized forward replays the same
keys without the reference's fork_rng dance.
"""

from __future__ import annotations

import functools
from typing import Any

import jax

from ....core.tensor import Tensor, apply
from ....nn.layer import Layer
from ....ops.pallas import FLASH_RESIDUAL_NAMES

__all__ = ["recompute", "recompute_sequential", "resolve_checkpoint_policy",
           "flash_residuals_policy", "LAYER_RESIDUAL_NAMES"]

#: ``checkpoint_name`` tags of the values a differentiated decoder block
#: (``models.gpt``) would rebuild with an MXU product or a collective:
#: the attention branch (attention's out-projection after its
#: tensor-parallel sum and the hidden dropout: the operand of the
#: mid-layer residual add), the FFN's first product before its
#: activation, and the fused QKV product. The default policy keeps them
#: beside the flash residuals, so a recomputed body runs its norms, the
#: residual add and the activation again and no product; a body that
#: holds no such name (BERT, ERNIE, the transformer encoder) resolves as
#: before.
LAYER_RESIDUAL_NAMES = ("attn_branch", "ffn_in_product", "qkv_product")

#: named selective-remat policies (jax.checkpoint_policies) a user may
#: choose; the default is none of them (:func:`resolve_checkpoint_policy`).
#: ``dots_with_no_batch_dims_saveable`` keeps every MXU (matmul) output
#: resident and rematerializes the elementwise tail (the T5X/MaxText
#: recipe), at the HBM cost of every product a body makes.
_POLICY_NAMES = (
    # NOTE: only plain PREDICATES belong here. jax.checkpoint_policies
    # also exports factories (offload_dot_with_no_batch_dims,
    # save_only_these_names, ...) that take configuration and RETURN a
    # predicate — pass the constructed predicate as a callable instead.
    "dots_saveable",
    "dots_with_no_batch_dims_saveable",
    "checkpoint_dots",
    "checkpoint_dots_with_no_batch_dims",
    "everything_saveable",
    "nothing_saveable",
)
_POLICY_ALIASES = {
    "save_dots": "dots_saveable",
    "save_dots_no_batch": "dots_with_no_batch_dims_saveable",
    "full": "nothing_saveable",
    "none": "everything_saveable",
}


@functools.lru_cache(maxsize=None)
def _keep_names(names):
    return jax.checkpoint_policies.save_only_these_names(*names)


@functools.lru_cache(maxsize=None)
def _named_policy(name):
    """The predicate of one policy name (None: the default). Built once
    a name: ``nn.scan`` keys its trace cache on the predicate's identity."""
    cp = jax.checkpoint_policies
    if name == "nothing_saveable":
        return cp.nothing_saveable
    keep = _keep_names(FLASH_RESIDUAL_NAMES + LAYER_RESIDUAL_NAMES)
    if name is None:
        return keep
    return cp.save_from_both_policies(getattr(cp, name), keep)


def flash_residuals_policy():
    """Keep the flash kernels' output and log-sum-exp and nothing else.
    The pipeline stage's remat policy: a stage holds its residuals for
    every tick of its schedule (microbatches + stages - 1), so it keeps
    none of a block's :data:`LAYER_RESIDUAL_NAMES`. Kept, they would be
    32 MiB a layer a tick at GPT-2 345M's widths and microbatches of 2
    (``tests/test_tpu_compile.py`` compiles the fill-drain step both
    ways for a described v5e)."""
    return _keep_names(FLASH_RESIDUAL_NAMES)


def resolve_checkpoint_policy(policy):
    """Resolve a remat policy spec to a ``jax.checkpoint_policies`` predicate.

    Every policy but ``"full"`` keeps the values a recomputed body would
    rebuild with a kernel, a product or a collective, where the
    differentiated forward names them:

    - a flash-attention kernel's output and log-sum-exp
      (``ops.pallas.FLASH_RESIDUAL_NAMES``): the second run would write
      the same bits and costs as much as the backward kernel;
    - a GPT block's :data:`LAYER_RESIDUAL_NAMES`: the attention branch
      (so the body runs attention's out-projection, its tensor-parallel
      all-reduce and its dropout kernel once), the FFN's first product
      before its activation and the fused QKV product. A recomputed GPT
      body then runs its two norms, the residual add and its activation
      again, and no product. What is kept is what the forward produced,
      so the loss and every gradient are what ``"full"`` and no
      recompute give: bit for bit on the CPU, and on a TPU to the last
      bits that each program's order of summation sets (they part
      ``"full"`` from no recompute there too).

    A body that names none of them (BERT, ERNIE, the transformer
    encoder) resolves to what the policy says alone.

    - None (the default): ``save_only_these_names(<all those names>)``;
    - a policy name or alias (model configs carry the string form,
      ``recompute_policy='dots_with_no_batch_dims_saveable'``, so they
      stay picklable): that policy AND the named values
      (``save_from_both_policies``; no dots policy keeps a custom
      call's output, nor the attention branch after its dropout);
    - ``"full"`` / ``"nothing_saveable"``: jax's literal meaning, nothing
      is kept and every product and kernel runs again. It holds the
      least: the choice for a stack that does not fit memory otherwise;
    - a callable: returned as it is."""
    if callable(policy):
        return policy
    if policy is None:
        return _named_policy(None)
    name = _POLICY_ALIASES.get(str(policy), str(policy))
    if name not in _POLICY_NAMES:
        raise ValueError(
            f"unknown recompute policy {policy!r}; expected one of "
            f"{sorted(_POLICY_NAMES + tuple(_POLICY_ALIASES))} or a "
            "jax.checkpoint_policies callable")
    return _named_policy(name)


def recompute(function, *args, use_reentrant: bool = True,
              preserve_rng_state: bool = True, policy=None, **kwargs):
    """Run ``function(*args)`` with activation checkpointing.

    ``function`` may be a Layer (its parameters join the gradient path) or
    any callable over Tensors. Memory: the backward keeps only the segment
    inputs + params and rematerializes intermediates (reference:
    fleet/utils/recompute.py:63; here via jax.checkpoint, which also
    applies inside a jitted TrainStep trace).

    ``policy`` (TPU-native extension): a ``jax.checkpoint_policies``
    predicate or its name for SELECTIVE checkpointing. The default keeps
    what the segment names as costly to rebuild (a flash kernel's output
    and log-sum-exp, a GPT block's products and attention branch) and
    recomputes the rest; ``dots_with_no_batch_dims_saveable`` keeps every
    matmul output besides; ``"full"`` keeps nothing, for a segment that
    does not fit memory otherwise (:func:`resolve_checkpoint_policy`).
    """
    del use_reentrant, preserve_rng_state   # parity knobs; single behavior
    policy = resolve_checkpoint_policy(policy)

    # Gradients only flow through explicit apply() args, so parameters must
    # be passed in — harvest them from the callable: the Layer itself, a
    # bound method's Layer, and any Layer/Tensor captured in closure cells
    # (the `recompute(lambda x: f(block(x)), x)` pattern).
    layers = []
    if isinstance(function, Layer):
        layers.append(function)
    self_obj = getattr(function, "__self__", None)
    if isinstance(self_obj, Layer) and self_obj not in layers:
        layers.append(self_obj)
    loose_tensors = []
    for cell in getattr(function, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        if isinstance(v, Layer) and v not in layers:
            layers.append(v)
        elif isinstance(v, Tensor) and not v.stop_gradient:
            loose_tensors.append(v)

    p_entries = []                       # (layer_idx, name, tensor)
    for li, lyr in enumerate(layers):
        for k, p in lyr.named_parameters():
            p_entries.append((li, k, p))
    p_tensors = [p for _, _, p in p_entries]
    n_p = len(p_tensors)
    n_loose = len(loose_tensors)

    tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    tensor_args = [args[i] for i in tensor_idx]

    def pure(*raw):
        import contextlib
        from ....jit.functional import bind
        per_layer = [dict() for _ in layers]
        for (li, k, _), arr in zip(p_entries, raw[:n_p]):
            per_layer[li][k] = arr
        xs = list(args)
        for i, arr in zip(tensor_idx, raw[n_p + n_loose:]):
            xs[i] = Tensor(arr)
        with contextlib.ExitStack() as stack:
            for t, arr in zip(loose_tensors, raw[n_p:n_p + n_loose]):
                saved = t._data
                t._data = arr
                stack.callback(lambda t=t, s=saved: setattr(t, "_data", s))
            for lyr, p_arrays in zip(layers, per_layer):
                stack.enter_context(bind(lyr, p_arrays, None))
            out = (layers[0](*xs, **kwargs) if isinstance(function, Layer)
                   else function(*xs, **kwargs))
        outs = out if isinstance(out, (tuple, list)) else (out,)
        flat = tuple(o._data if isinstance(o, Tensor) else o for o in outs)
        return flat if len(flat) > 1 else flat[0]

    ck = jax.checkpoint(pure, policy=policy)
    return apply(ck, *p_tensors, *loose_tensors, *tensor_args,
                 name="recompute")


class _Segment(Layer):
    """A chunk of layers/callables as ONE Layer, so recompute() harvests
    the chunk's parameters into the gradient path."""

    def __init__(self, fns):
        super().__init__()
        self._fns = list(fns)
        for i, f in enumerate(self._fns):
            if isinstance(f, Layer):
                self.add_sublayer(str(i), f)

    def forward(self, *xs):
        cur = xs
        for f in self._fns:
            cur = f(*cur) if isinstance(cur, tuple) else f(cur)
        return cur


def recompute_sequential(ctx: Any, functions, *args, **kwargs):
    """Checkpoint a sequence of layers segment by segment (reference:
    fleet/utils/recompute.py recompute_sequential — segments kwarg)."""
    segments = int((ctx or {}).get("segments", 1)) if isinstance(ctx, dict) \
        else int(getattr(ctx, "segments", 1) or 1)
    funcs = list(functions)
    if not funcs:
        return args[0] if len(args) == 1 else args
    seg_size = max(1, (len(funcs) + segments - 1) // segments)
    out = args
    for s in range(0, len(funcs), seg_size):
        seg = _Segment(funcs[s:s + seg_size])
        out = recompute(seg, *(out if isinstance(out, tuple) else (out,)),
                        **kwargs)
    return out
