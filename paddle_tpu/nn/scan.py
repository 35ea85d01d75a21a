"""Scan-over-layers: homogeneous layer stacks as ONE ``jax.lax.scan``.

The TPU-native answer to the O(num_layers) trace/compile cost of running a
decoder stack as a Python loop over ``LayerList`` (the reference traces one
sub-graph per layer; XLA then compiles L inlined copies of the same block).
Here the per-layer parameters are stacked along a new leading axis and the
block body is traced ONCE as the scan body — the T5X/MaxText
scan-over-stacked-params recipe:

- trace + compile cost: O(1) in the number of layers (the headline win:
  20-30s cold compiles on 24-layer stacks collapse to the single-block
  cost);
- the public surface is unchanged: parameters stay stored per layer on the
  real blocks (``layers.0.attn.qkv_weight`` state_dict names, ``LayerList``
  indexing/iteration, per-layer ``Parameter.spec`` TP shardings) — the
  stack is an internal, trace-time layout (docs/PARITY.md);
- remat composes INSIDE the body: ``jax.checkpoint(body, policy=...)``
  (``prevent_cse=False`` per the jax guidance for remat-in-scan). What a
  policy keeps is ``fleet.utils.recompute.resolve_checkpoint_policy``'s
  to say: by default the values the body names as costly to rebuild and
  nothing else (the flash kernel's output and log-sum-exp; a GPT
  block's QKV and FFN-in products and its attention branch after
  dropout, so a recomputed GPT body runs no product and no all-reduce
  again), a dots
  policy the MXU outputs besides, ``"full"`` nothing (the least memory);
- RNG: each layer folds its index into the scan's base key, so dropout
  masks stay distinct per layer (the loop path draws per-layer keys from
  the trace counter instead — same distribution, different realization).

Gradient flow in eager mode rides the tape: the per-layer parameter stack
is the taped ``stack`` op (its VJP unstacks cotangents back onto each
block's Parameter) and the scan itself is one taped ``apply`` node.
"""

from __future__ import annotations

import contextlib
import warnings
import weakref

import jax
import jax.numpy as jnp

from ..core.flags import get_flag
from ..core.random import make_rng, trace_rng
from ..core.tensor import Tensor, apply

__all__ = ["can_scan_layers", "scan_layers", "scan_layers_with_cache",
           "invalidate_scan_cache", "note_scan_fallback", "SCAN_STATS"]

#: observability for the trace-count assertion helper
#: (paddle_tpu.utils.compilation): ``body_traces`` counts how many times a
#: scan body was traced at the Python level — pinned by tests to be
#: independent of the number of layers. ``fallbacks`` counts
#: :func:`note_scan_fallback` calls (stacks that were scan-eligible but
#: degraded to the Python loop, e.g. legacy KV-cache decode).
SCAN_STATS = {"body_traces": 0, "scan_calls": 0, "fallbacks": 0}

#: (reason, stack) pairs already warned about — the fallback warning is
#: one-time per cause so a decode loop does not spam stderr per step
_FALLBACK_WARNED: set = set()


def reset_scan_stats():
    SCAN_STATS["body_traces"] = 0
    SCAN_STATS["scan_calls"] = 0
    SCAN_STATS["fallbacks"] = 0
    _FALLBACK_WARNED.clear()


def note_scan_fallback(reason: str, stack: str = "") -> None:
    """Record that an otherwise scan-eligible stack ran as the Python
    loop — the silent-degradation path this exists to make loud.

    Emits a one-time RuntimeWarning per (reason, stack) naming the cause,
    bumps ``SCAN_STATS['fallbacks']`` always, and (monitor mode) a
    ``scan_fallback_total`` registry counter. Known reasons:
    ``legacy_static_cache`` (list-of-StaticCache decode predates the
    paged layout and has per-layer python state the scan cannot carry),
    ``scan_decode_disabled`` (FLAGS_scan_decode kill switch).
    """
    SCAN_STATS["fallbacks"] += 1
    key = (reason, stack)
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        warnings.warn(
            f"scan-over-layers fell back to the per-layer Python loop for "
            f"{stack or 'a layer stack'} (reason: {reason}); trace/compile "
            "cost is O(num_layers) on this path. Paged-KV decode "
            "(paddle_tpu.serving) runs under scan; FLAGS_scan_decode "
            "controls it.", RuntimeWarning, stacklevel=3)
    from ..monitor import enabled as _mon_enabled
    if _mon_enabled():
        from ..monitor import get_registry
        get_registry().counter(
            "scan_fallback_total",
            "scan-eligible stacks that degraded to the per-layer Python "
            "loop, by cause").inc(reason=reason, stack=stack)


def _config_sig(block):
    """Per-block NON-parameter config fingerprint: simple-typed attributes
    and callables (activation fns) on every sublayer. The scan body runs
    every layer through block[0]'s forward, so per-layer config divergence
    the param signature cannot see (a hand-tuned ``layers[i].dropout.p``,
    a swapped activation on the same class) must veto the scan. Callables
    compare by IDENTITY — distinct lambdas share a ``__qualname__`` but
    are different functions."""
    sig = []
    for path, lyr in block.named_sublayers(include_self=True):
        for k in sorted(vars(lyr)):
            if k.startswith("_") or k == "training":
                continue
            v = vars(lyr)[k]
            if isinstance(v, (int, float, bool, str, type(None))):
                sig.append((path, k, v))
            elif callable(v) and not hasattr(v, "named_parameters"):
                sig.append((path, k, id(v)))
    return tuple(sig)


#: cached per-stack config-homogeneity verdicts, keyed on the LayerList —
#: the vars() walk over every sublayer is the expensive part of the scan
#: eligibility check and cannot change without someone mutating a layer
#: in place (see invalidate_scan_cache). Invalidated automatically when
#: the stack's membership changes (block identity token).
_CFG_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def invalidate_scan_cache(blocks=None):
    """Drop cached scan-eligibility verdicts (all, or one stack's).

    The per-layer CONFIG check runs once per stack membership and is then
    cached; editing a layer's non-parameter attribute in place AFTER the
    stack has been used (e.g. ``layers[i].dropout.p = ...``) needs this
    call (or a ``FLAGS_scan_layers`` toggle) for the next forward to
    re-evaluate. Parameter replacement/reshape is always re-checked."""
    if blocks is None:
        _CFG_CACHE.clear()
    else:
        try:
            _CFG_CACHE.pop(blocks, None)
        except TypeError:
            pass


def _configs_homogeneous(blocks_obj, blocks) -> bool:
    token = tuple(id(b) for b in blocks)
    try:
        ent = _CFG_CACHE.get(blocks_obj)
    except TypeError:
        ent = None
    if ent is not None and ent[0] == token:
        return ent[1]
    ok = len({_config_sig(b) for b in blocks}) == 1
    try:
        _CFG_CACHE[blocks_obj] = (token, ok)
    except TypeError:
        pass                      # plain list / non-weakrefable container
    return ok


def can_scan_layers(blocks) -> bool:
    """True when ``blocks`` is a homogeneous stack the scan path can run:
    >= 2 layers of the same class with identical parameter names/shapes/
    dtypes, identical non-parameter config (:func:`_config_sig`, verdict
    cached per stack — see :func:`invalidate_scan_cache`), and no buffers
    (running-stat layers need per-layer state threading the scan does not
    model)."""
    if not get_flag("scan_layers"):
        return False
    blocks_obj = blocks
    blocks = list(blocks)
    if len(blocks) < 2:
        return False
    cls = type(blocks[0])
    ref = None
    for b in blocks:
        if type(b) is not cls:
            return False
        if any(True for _ in b.named_buffers()):
            return False
        sig = tuple((n, tuple(p.shape), str(p.dtype))
                    for n, p in b.named_parameters())
        if ref is None:
            ref = sig
        elif sig != ref:
            return False
    if not ref:
        return False
    # LIVE check (not cached): per-layer train/eval heterogeneity — the
    # body would apply block[0]'s mode to every layer. model.train()/
    # .eval() set all blocks uniformly; a hand-frozen subset must veto.
    if len({bool(b.training) for b in blocks}) > 1:
        return False
    return _configs_homogeneous(blocks_obj, blocks)


def _stack_params(template, names, specs, arrs, num_layers: int):
    """The ``[L, ...]`` stacks of every block's parameters, built inside
    the traced fn (name-major ``arrs``), pinned to the per-layer TP specs
    (leading layer axis replicated; no-op without an active mesh). Each
    stack is traced under the block of the sublayer that owns the
    parameter (``Layer.block``), so the copy — and, in the backward
    pass, the unstacking of its gradient — counts with that block."""
    from ..distributed.spmd import constrain
    owners = sorted(((n, l.block) for n, l in template.named_sublayers()
                     if l.block is not None), key=lambda nl: -len(nl[0]))
    stacked = {}
    for i, n in enumerate(names):
        block = next((b for o, b in owners if n.startswith(o + ".")), None)
        layers = arrs[i * num_layers:(i + 1) * num_layers]
        with (jax.named_scope(block) if block
              else contextlib.nullcontext()):
            stacked[n] = jnp.stack(layers, axis=0)
            if specs[n] is not None:
                stacked[n] = constrain(stacked[n], None, *tuple(specs[n]))
    return stacked


def scan_layers(blocks, x, *extra, policy=None, use_recompute: bool = False,
                num_aux: int = 0, token_extra=None,
                name: str = "scan_layers"):
    """Run ``x`` through ``blocks`` sequentially via one ``jax.lax.scan``.

    ``blocks``: homogeneous Layers (pre-validated with
    :func:`can_scan_layers`). ``extra``: broadcast (non-scanned) Tensor
    arguments passed to every block call, e.g. an attention mask.
    ``policy``: a ``jax.checkpoint_policies`` predicate (or name, or None
    — see ``fleet.utils.recompute.resolve_checkpoint_policy``, which
    keeps the values a body names as costly to rebuild under each but
    ``"full"``) for remat; only applied when ``use_recompute``.

    ``num_aux``: when > 0, each block's forward returns ``(x, aux_1, ...,
    aux_{num_aux})`` and the per-layer aux values leave the scan as
    scanned-over outputs stacked ``[L, ...]`` — the side channel MoE
    stacks use for per-layer router losses/stats (a value produced
    inside the scan body can only escape as a scan output; storing it on
    the layer would leak a body tracer). The call then returns
    ``(y, aux_1_stacked, ..., aux_n_stacked)``.

    Returns the final hidden states Tensor (or the tuple above).
    Equivalent to ``for b in blocks: x = b(x, *extra)`` up to float
    reassociation (and dropout-mask realization when training with
    dropout).
    """
    from ..distributed.fleet.utils.recompute import resolve_checkpoint_policy
    from ..jit.functional import bind

    blocks = list(blocks)
    template = blocks[0]
    num_layers = len(blocks)
    policy = resolve_checkpoint_policy(policy)

    names = [n for n, _ in template.named_parameters()]
    specs = {n: getattr(p, "spec", None)
             for n, p in template.named_parameters()}
    per_block = [dict(b.named_parameters()) for b in blocks]

    # every block's Parameters enter the ONE apply below directly
    # (name-major order); the [L, ...] stacks are built INSIDE the traced
    # fn, so eager backward unstacks cotangents onto each block's own
    # Parameter via this op's VJP — no per-call taped stack ops, and warm
    # eager steps are a single cached-jit dispatch
    flat_params = [pb[n] for n in names for pb in per_block]

    # one base key per scan call; layers fold in their index, so masks are
    # distinct per layer and per step. Eval-mode forwards never consume
    # randomness — skip the key entirely so inference jaxprs (ONNX/export
    # consumers) carry no PRNG constants or dead fold_in ops. The key is
    # an ARGUMENT (not a closure capture): the eager jit-op cache replays
    # a cached trace, and a captured key would freeze the first step's
    # dropout masks forever.
    training = bool(getattr(template, "training", True))
    key_args = ()
    if training:
        k = make_rng(None)
        key_args = (k._data if isinstance(k, Tensor) else k,)

    SCAN_STATS["scan_calls"] += 1

    def _scan_fn(x_arr, *arrs):
        if training:
            key, arrs = arrs[0], arrs[1:]
        else:
            key = None
        n_p = len(names) * num_layers
        p_stacked = _stack_params(template, names, specs, arrs, num_layers)
        extra_raw = arrs[n_p:]

        def body(carry, xs):
            SCAN_STATS["body_traces"] += 1
            p_slice, idx = xs
            rng_ctx = (trace_rng(jax.random.fold_in(key, idx))
                       if key is not None else contextlib.nullcontext())
            with rng_ctx, bind(template, p_slice, None):
                out = template(Tensor(carry),
                               *[Tensor(e) if hasattr(e, "dtype") else e
                                 for e in extra_raw])
            aux_raw = None
            if num_aux:
                out, aux = out[0], tuple(out[1:1 + num_aux])
                aux_raw = tuple(a._data if isinstance(a, Tensor) else a
                                for a in aux)
            out = out._data if isinstance(out, Tensor) else out
            return out.astype(carry.dtype), aux_raw

        if use_recompute:
            # prevent_cse=False: inside scan the loop structure already
            # rules out the CSE hazard jax.checkpoint guards against
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        y, ys = jax.lax.scan(
            body, x_arr,
            (p_stacked, jnp.arange(num_layers, dtype=jnp.int32)))
        if num_aux:
            return (y,) + tuple(ys)
        return y

    x_t = x if isinstance(x, Tensor) else Tensor(x)
    # token-keyed eager jit cache: hot eager loops replay a cached jitted
    # scan instead of re-tracing the body per step. The token encodes every
    # closure-captured value with semantic effect; the cache's strong ref
    # to the first call's closure keeps `template` alive, so id(template)
    # cannot be reused while the entry lives.
    # the resolver hands out ONE predicate a policy name, so its
    # identity tells the policies apart
    policy_tok = id(policy)
    # _config_sig(template) rides in the token so an IN-PLACE config edit
    # (e.g. setting every layer's dropout p) changes the key and retraces —
    # a cached trace must never replay stale config values
    # token_extra: hashable caller-supplied material for flag-dependent
    # block internals the config signature cannot see (e.g. the MoE
    # dispatch-mode kill switch — a cached trace must never replay a
    # stale dispatch path)
    token = ("scan_layers", name, id(template), num_layers, training,
             bool(use_recompute), policy_tok, len(extra), num_aux,
             token_extra, _config_sig(template))
    return apply(_scan_fn, x_t, *key_args, *flat_params, *extra, name=name,
                 _cache_token=token)


def scan_layers_with_cache(blocks, x, cache, *extra, body_call,
                           scan_in=(), name: str = "scan_layers_cache"):
    """Run ``x`` through ``blocks`` as ONE ``jax.lax.scan`` while
    threading cache state that every layer reads and updates — the
    decode-time counterpart of :func:`scan_layers` (the paged-KV serving
    path, ISSUE 6).

    ``cache``: tuple of Tensors/arrays the layers SHARE (the K/V page
    pools, viewed as one pool of ``L*P`` pages). They are never scanned
    over: they ride the scan CARRY whole, beside ``x``, so no iteration
    slices a layer's part out or stacks it back, and an update a layer
    makes (a scatter of the rows it produced) is made in place on the
    carried buffer. A layer finds its own part by what ``scan_in`` hands
    it (its first page). ``extra``: broadcast (non-scanned) arguments
    shared by every layer (block tables, per-slot positions).

    ``scan_in``: per-layer stacked arrays (``[L, ...]``) that scan as
    INPUTS ONLY — each layer sees its slice (its first page in the
    pool; the serving LoRA pools: per-layer adapter weights that the
    decode step reads but never writes).

    ``body_call(template, x, cache, extras, scan_in_slices)`` adapts the
    generic scan to the stack's block signature: it must run
    ``template`` (the first block, with that layer's params bound) and
    return ``(x, new_cache)`` with ``new_cache`` matching ``cache``'s
    structure and shapes. Pass a module-level function — its identity
    rides the eager jit-cache token.

    Eval-mode only (decode never trains): a training-mode template is
    rejected rather than silently dropping dropout randomness.

    Returns ``(y, new_cache)``.
    """
    blocks = list(blocks)
    template = blocks[0]
    num_layers = len(blocks)
    if bool(getattr(template, "training", False)):
        raise ValueError(
            "scan_layers_with_cache is an eval/decode path; call "
            "model.eval() first (training-mode dropout would need a "
            "per-layer RNG this cache-threading scan does not carry)")

    from ..jit.functional import bind as bind_

    names = [n for n, _ in template.named_parameters()]
    specs = {n: getattr(p, "spec", None)
             for n, p in template.named_parameters()}
    per_block = [dict(b.named_parameters()) for b in blocks]
    flat_params = [pb[n] for n in names for pb in per_block]
    n_cache = len(cache)
    n_scan_in = len(scan_in)

    SCAN_STATS["scan_calls"] += 1

    def _scan_fn(x_arr, *arrs):
        n_p = len(names) * num_layers
        p_stacked = _stack_params(template, names, specs, arrs, num_layers)
        cache_raw = arrs[n_p:n_p + n_cache]
        scan_in_raw = arrs[n_p + n_cache:n_p + n_cache + n_scan_in]
        extra_raw = arrs[n_p + n_cache + n_scan_in:]

        def body(carry, xs):
            SCAN_STATS["body_traces"] += 1
            h, cache_c = carry
            p_slice, scan_in_slice = xs
            extras_t = tuple(Tensor(e) if hasattr(e, "dtype") else e
                             for e in extra_raw)
            with bind_(template, p_slice, None):
                out, new_cache = body_call(
                    template, Tensor(h),
                    tuple(Tensor(c) for c in cache_c), extras_t,
                    tuple(Tensor(s) for s in scan_in_slice))
            out = out._data if isinstance(out, Tensor) else out
            new_cache = tuple(c._data if isinstance(c, Tensor) else c
                              for c in new_cache)
            return (out.astype(h.dtype), new_cache), None

        (y, new_cache), _ = jax.lax.scan(
            body, (x_arr, tuple(cache_raw)),
            (p_stacked, tuple(scan_in_raw)))
        return (y,) + tuple(new_cache)

    x_t = x if isinstance(x, Tensor) else Tensor(x)
    token = ("scan_layers_cache", name, id(template), num_layers, n_cache,
             n_scan_in, len(extra), id(body_call), _config_sig(template))
    out = apply(_scan_fn, x_t, *flat_params, *cache, *scan_in, *extra,
                name=name, _cache_token=token)
    return out[0], tuple(out[1:])
