"""Dev tool: attribute GPT-2 345M step time by timing ablations on the chip.

Usage: python tools/prof_gpt.py [mode ...|all]   (modes: see MODES dict)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import time

import numpy as np


def log(m):
    print(m, file=sys.stderr, flush=True)


def _sync(out):
    """Drain the dispatch pipeline with a scalar readback (works on
    wrapped Tensors, which have no block_until_ready)."""
    if isinstance(out, tuple):
        out = out[0]
    return float(out._data if hasattr(out, "_data") else out)


def timed(fn, args, iters=8):
    _sync(fn(*args))
    for _ in range(2):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters * 1e3


def build(B=8, S=1024, drop=0.1, remat=None, fwd_only=False,
          grads_only=False, mt=False, state_dtype="float32"):
    """remat: None | 'full' | 'dots' (selective: save dot outputs)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       GPTPretrainingCriterion, gpt2_medium)
    from paddle_tpu.optimizer import AdamW

    cfg = gpt2_medium(use_recompute=(remat is not None),
                      hidden_dropout_prob=drop, attention_dropout_prob=drop)
    paddle.seed(0)
    import paddle_tpu.distributed.fleet.utils.recompute  # noqa: F401
    # the package attr `recompute` is the *function* (star-import shadows
    # the submodule) — bind the module via sys.modules
    rc = sys.modules["paddle_tpu.distributed.fleet.utils.recompute"]
    utils_pkg = sys.modules["paddle_tpu.distributed.fleet.utils"]
    if remat == "dots":
        def sel(fn, *a, **k):
            return rc.recompute(
                fn, *a,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                **k)
        utils_pkg.recompute = sel
    else:  # undo a selective-remat patch left by an earlier mode
        utils_pkg.recompute = rc.recompute
    model = GPTForPretraining(cfg)
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(layer, ids, labels):
        with paddle.amp.auto_cast(level="O1"):
            return crit(layer(ids), labels)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)

    if fwd_only or grads_only:
        from paddle_tpu.core.random import trace_rng
        from paddle_tpu.core.tensor import Tensor, no_grad
        from paddle_tpu.jit.functional import bind, buffer_arrays, \
            param_arrays
        import jax.numpy as jnp
        params = param_arrays(model)
        bufs = buffer_arrays(model)

        def pure(p, i, la):
            with trace_rng(jax.random.key(0)), no_grad():
                with bind(model, p, dict(bufs)):
                    return loss_fn(model, Tensor(i),
                                   Tensor(la))._data.astype(jnp.float32)

        if fwd_only:
            f = jax.jit(pure)
            return (lambda i, la: f(params, i, la)), (ids, labels)
        g = jax.jit(jax.value_and_grad(pure))
        return (lambda i, la: g(params, i, la)), (ids, labels)

    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, use_multi_tensor=mt,
                state_dtype=state_dtype)
    step = TrainStep(model, loss_fn, opt)
    return step, (ids, labels)


MODES = {
    "base": dict(),
    "base_mt": dict(mt=True),
    "mt_bf16st": dict(mt=True, state_dtype="bfloat16"),
    "bf16st": dict(state_dtype="bfloat16"),
    "b16_bf16st": dict(B=16, state_dtype="bfloat16"),
    "b12_bf16st": dict(B=12, state_dtype="bfloat16"),
    "b12_mt": dict(B=12, mt=True),
    "fwdonly": dict(fwd_only=True),
    "gradsonly": dict(grads_only=True),
    "nodrop": dict(drop=0.0),
    "b12": dict(B=12),
    "b16_fullremat": dict(B=16, remat="full"),
    "b16_selremat": dict(B=16, remat="dots"),
    "b12_selremat": dict(B=12, remat="dots"),
}


def mfu(tok_s, cfg_h=1024, cfg_L=24, V=50304, S=1024):
    p_block = cfg_L * 12 * cfg_h * cfg_h
    flops_token = 6 * (p_block + V * cfg_h) + 12 * cfg_L * cfg_h * S
    from paddle_tpu.cost_model import device_peak_flops
    peak = device_peak_flops()
    if peak is None:
        import jax
        raise RuntimeError(
            f"unknown device kind {jax.devices()[0].device_kind!r}: no "
            "peak FLOP/s in paddle_tpu.cost_model.PEAK_FLOPS")
    return tok_s * flops_token / peak


def main():
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    import paddle_tpu as paddle
    paddle.set_flags({"tpu_matmul_precision": "default"})
    which = sys.argv[1:] or ["base", "fwdonly", "gradsonly", "nodrop"]
    if which == ["all"]:
        which = list(MODES)
    for name in which:
        kw = MODES[name]
        t0 = time.perf_counter()
        step, args = build(**kw)
        ms = timed(step, args)
        B = kw.get("B", 8)
        tok = B * 1024 / (ms / 1e3)
        log(f"{name:16s} {ms:7.1f} ms/step  {tok:10,.0f} tok/s  "
            f"model-MFU={mfu(tok):.3f}  (B={B}, built+timed in "
            f"{time.perf_counter()-t0:.0f}s)")


if __name__ == "__main__":
    main()
