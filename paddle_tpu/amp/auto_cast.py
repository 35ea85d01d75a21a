"""AMP autocast.

Reference: imperative AMP lists (paddle/fluid/imperative/amp_auto_cast.h:38-66,
AutoCastInputs O1 / CastPureFp16Inputs O2) and python amp/auto_cast.py.

TPU-native: bf16 is the default low precision (no loss scaling needed);
fp16 kept for parity. O1 casts inputs of allow-listed ops; O2 runs the whole
region in low precision except block-listed ops. Implemented as a context
that installs a cast policy consulted by core.tensor.apply via an op-name
filter wrapper around the nn functional layer.
"""

from __future__ import annotations

import contextlib
import threading

import jax.numpy as jnp

from ..core import dtypes
from ..core.tensor import Tensor

# Ops whose inputs are cast to low precision in O1 (MXU-bound ops).
# `embedding` is here so the activation stream STARTS in bf16: with the
# table gathered low-precision, every downstream residual add / dropout /
# norm rides bf16 HBM traffic instead of f32 (the norms keep f32 internal
# stats — see layer_norm in nn/functional.py).
white_list = {
    "matmul", "mm", "bmm", "linear", "conv1d", "conv2d", "conv3d",
    "conv1d_transpose", "conv2d_transpose", "conv3d_transpose",
    "scaled_dot_product_attention", "einsum", "embedding",
    # the model zoo's fused matmul-class ops (GPT/BERT/ERNIE attention
    # projections and LM heads) — without these the attention branch of
    # the residual stream silently rides f32 under O1
    "fused_qkv", "attn_out", "mlm_head", "ernie_mlm_head", "lm_logits",
}

# Ops kept in fp32 even under O2 (numerically sensitive). `layer_norm`
# and `batch_norm` are deliberately absent: both compute statistics in
# f32 internally and return the input dtype (batch_norm folds to one
# bf16 multiply-add in the conv epilogue), so casting their inputs up
# would only double activation bandwidth — on ResNet-50 the old
# blacklisted batch_norm cost ~40 ms/step in convert/copy traffic. The
# f32 EMA buffers are safe either way: the running-stat update consumes
# the f32 statistics, never the low-precision activations. group/
# instance norm keep the conservative listing (unfused normalizers).
black_list = {
    "softmax", "log_softmax", "cross_entropy", "softmax_with_cross_entropy",
    "group_norm", "instance_norm", "norm",
    "mean", "sum", "exp", "log", "logsumexp", "erf", "erfinv", "pow",
    "cumsum", "rsqrt", "sqrt", "square",
}

# Never cast, at ANY level: the op preserves its inputs' dtypes and runs
# f32 statistics internally; a blanket cast would also hit its f32 state
# buffers (see _cast_target). fused_conv_bn resolves the conv-operand cast
# itself (nn/functional.py) so its f32 EMA buffers ride through untouched.
# checkpoint_name only names a value for the remat policy (models/gpt.py).
_keep_dtype = {"batch_norm", "fused_conv_bn", "checkpoint_name"}

_tls = threading.local()


class _AmpState:
    __slots__ = ("enabled", "dtype", "level", "custom_white", "custom_black",
                 "wl", "bl")

    def __init__(self, enabled, dtype, level, custom_white, custom_black):
        self.enabled = enabled
        self.dtype = dtype
        self.level = level
        self.custom_white = custom_white or set()
        self.custom_black = custom_black or set()
        # effective lists resolved ONCE per context (the custom lists are
        # fixed for the state's lifetime; per-op set unions would sit on
        # the hot eager dispatch path)
        self.wl = (white_list | self.custom_white) - self.custom_black
        self.bl = black_list | self.custom_black


def amp_state():
    return getattr(_tls, "amp", None)


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast analogue (bf16-first on TPU)."""
    prev = amp_state()
    _tls.amp = _AmpState(enable, dtypes.convert_dtype(dtype), level,
                         set(custom_white_list or []), set(custom_black_list or []))
    try:
        yield
    finally:
        _tls.amp = prev


amp_guard = auto_cast


def _cast_target(op_name: str, st):
    """The ONE policy resolver: target jnp dtype for op inputs, or None
    (leave dtypes alone). Both the actual cast and the cache token derive
    from this, so they can never desynchronize."""
    if st is None or not st.enabled:
        return None
    if op_name in _keep_dtype and op_name not in st.custom_black \
            and op_name not in st.custom_white:
        # dtype-preserving ops: casting would hit EVERY float input —
        # including batch_norm's f32 running-stat buffers, whose EMA
        # write-back must never round through bf16. The op handles its
        # own internal precision (f32 stats, input-dtype application).
        # An EXPLICIT custom listing overrides the default (the user's
        # debugging knob keeps working).
        return None
    if st.level == "O2":
        return jnp.float32 if op_name in st.bl else st.dtype
    if op_name in st.wl:
        return st.dtype
    if op_name in st.bl:
        return jnp.float32
    return None


def amp_target_dtype(op_name: str):
    """Dispatch-layer hook: the cast-target dtype STRING for this op
    under the active policy, or None. Resolved once at op-dispatch time —
    the value (not the thread-local state) is captured by any deferred
    trace, so a backward jitted outside the autocast context still
    replays the forward's policy."""
    target = _cast_target(op_name, amp_state())
    return None if target is None else str(jnp.dtype(target))


from ..core.tensor import set_amp_target_hook  # noqa: E402

set_amp_target_hook(amp_target_dtype)


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """O2 decoration: cast model params to low precision, keep fp32 master
    weights inside the optimizer (reference: amp/auto_cast.py decorate)."""
    d = dtypes.convert_dtype(dtype)
    single = not isinstance(models, (list, tuple))
    model_list = [models] if single else list(models)
    if level == "O2":
        for m in model_list:
            m.to(dtype=d)
        if optimizers is not None:
            opts = [optimizers] if not isinstance(optimizers, (list, tuple)) else optimizers
            for o in opts:
                o._multi_precision = True
    if optimizers is None:
        return models
    return models, optimizers
