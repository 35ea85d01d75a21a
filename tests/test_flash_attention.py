"""Flash-attention kernel correctness vs the XLA sdpa composition.

Analogue of the reference's fused-attention parity tests
(reference: test_fused_attention_op.py — fused kernel vs the unfused
composition within tolerance). Runs the same Pallas kernels through the
interpreter on CPU; the TPU path compiles the identical kernel code.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import _sdpa_xla
from paddle_tpu.ops.pallas.flash_attention import flash_attention

# the module (the package re-exports a same-named function over it)
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

# the kernels are called directly: the marker selects the interpreter
pytestmark = pytest.mark.pallas

B, S, H, D = 2, 256, 2, 64


def _qkv(seed=0, dtype=np.float32, s=S):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(B, s, H, D).astype(dtype) * 0.5  # noqa: E731
    return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())


def _ref(q, k, v, mask=None, causal=False):
    with jax.default_matmul_precision("highest"):
        return _sdpa_xla(q, k, v, mask, 0.0, causal, None)


def test_forward_matches_xla():
    q, k, v = _qkv()
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_forward_causal():
    q, k, v = _qkv(1)
    out = flash_attention(q, k, v, causal=True)
    ref = _ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forward_key_padding_bias():
    q, k, v = _qkv(2)
    keep = np.ones((B, 1, 1, S), np.float32)
    keep[:, :, :, S // 2:] = 0.0          # mask out second half of keys
    bias = (1.0 - keep) * -1e30
    out = flash_attention(q, k, v, bias=jnp.asarray(bias))
    ref = _ref(q, k, v, mask=jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_xla(causal):
    q, k, v = _qkv(3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name}")


def test_grads_with_bias():
    q, k, v = _qkv(4)
    keep = np.ones((B, 1, 1, S), np.float32)
    keep[:, :, :, -64:] = 0.0
    bias = jnp.asarray((1.0 - keep) * -1e30)

    g_flash = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, bias=bias) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(_ref(q, k, v, mask=bias) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


def test_learned_bias_gradient():
    # a trainable additive bias (ALiBi-style, finite values) must receive
    # the true gradient on the flash path, matching the XLA composition
    q, k, v = _qkv(10)
    rng = np.random.RandomState(11)
    bias = jnp.asarray(rng.randn(B, 1, 1, S).astype(np.float32))

    db_flash = jax.grad(
        lambda b_: jnp.sum(flash_attention(q, k, v, bias=b_) ** 2))(bias)
    db_ref = jax.grad(
        lambda b_: jnp.sum(_ref(q, k, v, mask=b_) ** 2))(bias)
    assert float(jnp.max(jnp.abs(db_ref))) > 1e-3   # non-trivial gradient
    np.testing.assert_allclose(np.asarray(db_flash), np.asarray(db_ref),
                               atol=5e-4, rtol=5e-4)


def test_rectangular_seq_lens():
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(B, 128, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, 384, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, 384, H, D).astype(np.float32))
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_rectangular_causal_bottom_right():
    # chunked prefill: 128 new queries against a 384-long KV cache; causal
    # alignment must be bottom-right (row i sees keys <= i + Sk - Sq)
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(B, 128, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, 384, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, 384, H, D).astype(np.float32))
    out = flash_attention(q, k, v, causal=True)
    ref = _ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # and grads
    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        _ref(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_non_dividing_seq_len_picks_smaller_block():
    # S=768 does not divide the 512 default block; kernel must pick 384
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 768, 2, D).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 768, 2, D).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 768, 2, D).astype(np.float32))
    out = flash_attention(q, k, v, causal=True)
    ref = _ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_bf16_inputs(what):
    q, k, v = _qkv(6, dtype=np.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    if what == "forward":
        got, ref = [flash_attention(qb, kb, vb)], [_ref(q, k, v)]
    else:
        got = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(qb, kb, vb)
        ref = jax.grad(lambda q, k, v: jnp.sum(_ref(q, k, v) ** 2),
                       argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        assert a.dtype == jnp.bfloat16
        # a few bf16 eps (7.8e-3) of the result's range: the inputs'
        # rounding, and the output's
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)), np.asarray(b),
            atol=3e-2 * float(jnp.max(jnp.abs(b))), rtol=3e-2)


# -- the precision rule: MXU passes follow the operands' types (ISSUE 29) ------

def _kernel_dots(fn, *args):
    """``{kernel name: [(lhs dtype, rhs dtype, out dtype, precision)]}``
    for every ``dot_general`` inside a ``pallas_call`` of ``fn``'s jaxpr."""
    found = {}

    def walk(jaxpr, kernel):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                walk(e.params["jaxpr"], found.setdefault(e.params["name"], []))
                continue
            if e.primitive.name == "dot_general" and kernel is not None:
                kernel.append((*(str(x.aval.dtype) for x in e.invars),
                               str(e.outvars[0].aval.dtype),
                               e.params["precision"]))
            for val in e.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, kernel)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


_BF16_DOT = ("bfloat16", "bfloat16", "float32", None)
_HIGHEST = (jax.lax.Precision.HIGHEST,) * 2
_F32_DOT = ("float32", "float32", "float32", _HIGHEST)
# products a tile: q k^T, p v | q k^T, dO v^T, ds k, ds^T q, p^T dO
_DOTS = {  # (route, operands): {kernel: dots a (bi, hh) iteration}
    ("v2", "bf16"): {"flash_fwd": 1 + 3, "flash_bwd": 1 + 1 + 3 + 3 + 3},
    ("v2", "f32"): {"flash_fwd": 2, "flash_bwd": 5},
    ("v1", "bf16"): {"flash_fwd_v1": 1 + 3, "flash_dq_v1": 1 + 1 + 3,
                     "flash_dkv_v1": 1 + 1 + 3 + 3},
    ("v1", "f32"): {"flash_fwd_v1": 2, "flash_dq_v1": 3, "flash_dkv_v1": 4},
}


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["nodrop", "drop0.1"])
@pytest.mark.parametrize("operands,flag", [
    ("bf16", "highest"), ("f32", "highest"), ("f32", "default")])
@pytest.mark.parametrize("route", ["v2", "v1"])
def test_mxu_passes_follow_operand_types(route, operands, flag, dropout):
    """The rule, pinned from the jaxpr of the forward and of the VJP.

    bf16 q/k/v/dO: every product inside the kernels is a bf16 x bf16
    ``dot_general`` with an f32 result and no ``precision=``: ONE for the
    products of two operands read from bf16 refs, THREE for an f32 tile
    (probabilities, ``ds``) against a bf16 operand — 4 a tile forward,
    11 in the fused backward (v1: 4, then 5 and 8), and no f32 dot is
    left to round a tile in one part. f32 q/k/v follow the flag: f32 at
    ``HIGHEST`` under its default, one bf16 pass under ``default`` — as
    before the rule. A causal v2 kernel holds those products once for a
    tile below the diagonal and once a row group of a tile that
    straddles it (ISSUE 36), every one of them by the same rule."""
    from paddle_tpu.core.flags import flag_scope
    dtype = jnp.bfloat16 if operands == "bf16" else jnp.float32
    q, k, v = (x.astype(dtype) for x in _qkv(13))
    bias = jnp.zeros((B, 1, 1, S), jnp.float32) if route == "v1" else None
    key = jax.random.key(0)

    def attend(q, k, v):
        return flash_attention(q, k, v, bias=bias, causal=route == "v2",
                               dropout_rate=dropout,
                               dropout_key=key if dropout else None)

    with flag_scope("tpu_matmul_precision", flag):
        dots = _kernel_dots(
            lambda q, k, v: jax.vjp(attend, q, k, v)[1](q), q, k, v)
    expect = _DOTS[route, operands]
    assert set(dots) == set(expect)
    if route == "v2":
        hp, _, bb_fwd, bb_bwd = fa._v2_plan(q, None, S, S)
        plan, _ = fa._causal_plan(S, S, S, S)
        bodies = 1 + sum(len(groups) for groups in plan.values())
        assert bodies == 1 + S // 128
        tiles = {"flash_fwd": bb_fwd * hp * bodies,
                 "flash_bwd": bb_bwd * hp * bodies}
    for name, found in dots.items():
        one = _F32_DOT if (operands, flag) == ("f32", "highest") \
            else _BF16_DOT
        assert set(found) == {one}, (name, set(found))
        assert len(found) == expect[name] * (
            tiles[name] if route == "v2" else 1), name


class _F32Results:
    """``jax`` as the kernels' wrappers see it, with every bf16 result
    declared f32: the kernels' final ``.astype(ref.dtype)`` is then no
    rounding, and what they computed can be compared to f32 accuracy."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def ShapeDtypeStruct(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32 if dtype == jnp.bfloat16 else dtype)


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["nodrop", "drop0.1"])
@pytest.mark.parametrize("case", ["full", "causal", "rect-causal", "v1-bias"])
def test_bf16_operands_give_the_f32_path_s_numbers(monkeypatch, case,
                                                   dropout):
    """bf16 q/k/v/dO through the kernels against the SAME values upcast
    to f32 through the kernels (f32 operands at ``HIGHEST``): out, dq,
    dk, dv before the final cast agree to f32 rounding — 1e-5 of the f32
    result's range, where a probability or ``ds`` tile rounded to bf16 in
    one part would read 1e-3."""
    monkeypatch.setattr(fa, "jax", _F32Results())
    sq, sk = (128, 384) if case == "rect-causal" else (S, S)
    rng = np.random.RandomState(14)

    def mk(s):
        return jnp.asarray(rng.randn(B, s, H, D).astype(np.float32) * 0.5) \
            .astype(jnp.bfloat16)

    q, k, v, do = mk(sq), mk(sk), mk(sk), mk(sq)
    bias = None
    if case == "v1-bias":
        bias = jnp.asarray(rng.randn(B, 1, 1, sk).astype(np.float32))
    causal = "causal" in case
    seed = jnp.asarray([3, 5], jnp.int32)
    blocks = (128, 128)                  # several tiles a sweep

    def run(cast):
        q_, k_, v_, do_ = (cast(x) for x in (q, k, v, do))
        o, lse = fa._fwd(q_, k_, v_, bias, 0.125, causal, *blocks,
                         seed=seed, rate=dropout)
        return o, lse, lambda o, lse: fa._bwd_impl(
            q_, k_, v_, bias, cast(o), lse, do_, 0.125, causal, *blocks,
            seed=seed, rate=dropout)[:3]

    o32, lse32, bwd32 = run(lambda x: x.astype(jnp.float32))
    o16, lse16, bwd16 = run(lambda x: x)
    assert o16.dtype == jnp.float32      # the final cast skipped
    # both backwards from ONE saved output and log-sum-exp: the output as
    # the bf16 step stores it
    o_saved = o32.astype(jnp.bfloat16)
    pairs = [("out", o16, o32), ("lse", lse16, lse32)] + [
        (n, a, b) for n, a, b in zip(("dq", "dk", "dv"),
                                     bwd16(o_saved, lse32),
                                     bwd32(o_saved, lse32))]
    for name, got, ref in pairs:
        got, ref = np.asarray(got), np.asarray(ref)
        assert got.dtype == np.float32
        span = float(ref.max() - ref.min())
        assert float(np.abs(got - ref).max()) <= 1e-5 * span, name


def _host_keep(S, b, h, rate):
    """Reconstruct the kernel's stateless dropout mask on the host (same
    murmur3-finalizer hash over absolute coordinates, uint64 arithmetic)."""
    rows = np.arange(S, dtype=np.uint64)[:, None]
    cols = np.arange(S, dtype=np.uint64)[None, :]
    M = np.uint64(0xFFFFFFFF)
    bh = (np.uint64(b) * np.uint64(0xAC564B05)
          + np.uint64(h) * np.uint64(19349663)) & M
    x = ((rows * np.uint64(0x9E3779B1)) & M) \
        ^ ((cols * np.uint64(0x85EBCA6B)) & M) ^ bh
    x &= M
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & M
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0xC2B2AE35)) & M
    x ^= x >> np.uint64(16)
    thresh = np.uint64(min(rate, 0.999999) * 4294967296.0)
    return (x >= thresh).astype(np.float32) / (1.0 - rate)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [256, 128])
def test_dropout_matches_host_mask_reference(causal, block):
    # in-kernel dropout (stateless hash) vs a pure-JAX reference using the
    # reconstructed mask: forward AND analytic grads must agree — this is
    # the fwd/bwd mask-consistency proof (backward REGENERATES the mask).
    # block=128 gives a 2x2 tile grid, exercising the transposed dkv grid
    # and the per-tile coordinate mixing; B=2 exercises the batch fold.
    from paddle_tpu.ops.pallas.flash_attention import _flash

    Bv, Sv, Hv, Dv = 2, 256, 2, 64
    rate = 0.3
    rng = np.random.RandomState(12)
    # _flash takes the framework [B, S, H, D] layout directly
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(Bv, Sv, Hv, Dv).astype(np.float32)) * 0.3
    q, k, v = mk(), mk(), mk()
    seed_f = jnp.zeros((2,), jnp.float32)
    keep = jnp.asarray(np.stack([np.stack(
        [_host_keep(Sv, b, h, rate) for h in range(Hv)])
        for b in range(Bv)]))
    G = jnp.asarray(rng.randn(Bv, Sv, Hv, Dv).astype(np.float32))
    cm = jnp.tril(jnp.ones((Sv, Sv), bool))

    def ref_loss(q_, k_, v_):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * 0.125
        if causal:
            s = jnp.where(cm[None, None], s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p * keep, v_) * G)

    def kern_loss(q_, k_, v_):
        return jnp.sum(_flash(q_, k_, v_, None, seed_f, 0.125, causal,
                              block, block, rate) * G)

    o_k = _flash(q, k, v, None, seed_f, 0.125, causal, block, block, rate)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.125
    if causal:
        s = jnp.where(cm[None, None], s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o_r = jnp.einsum("bhqk,bkhd->bqhd", p * keep, v)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)

    g_k = jax.grad(kern_loss, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_k, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name}")


# -- the causal diagonal: two kinds of running tile (ISSUE 36) ------------------

def _one_group(block):
    """``_group_rows`` of the body before row groups: a tile that
    straddles the diagonal is ONE group under a mask of all of it."""
    return block


def _keep_of(Bv, Hv, Sv, rate):
    return jnp.asarray(np.stack([np.stack(
        [_host_keep(Sv, b, h, rate) for h in range(Hv)]) for b in range(Bv)]))


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["nodrop", "drop0.1"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_causal_s1024_block512_matches_the_composition(dtype, dropout):
    """The train cells' tiling: S=1024 in blocks of 512 is three running
    tiles a head, two of them on the diagonal in four row groups each and
    one below it with no mask code. Forward and the three gradients
    against the XLA composition (float32 at ``highest``; under dropout
    with the kernel's own keep bits, rebuilt on the host)."""
    Bv, Sv, Hv, Dv = 1, 1024, 2, 64
    plan, computed = fa._causal_plan(Sv, Sv, 512, 512)
    assert [len(g) for g in plan.values()] == [4] and computed == 9 * 256 * 256
    rng = np.random.RandomState(36)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(Bv, Sv, Hv, Dv).astype(np.float32) * 0.5)
    q, k, v, G = mk(), mk(), mk(), mk()
    keep = _keep_of(Bv, Hv, Sv, dropout) if dropout else 1.0
    cm = jnp.tril(jnp.ones((Sv, Sv), bool))
    seed_f = jnp.zeros((2,), jnp.float32)

    def ref(q_, k_, v_):
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * 0.125
            p = jax.nn.softmax(jnp.where(cm[None, None], s, -1e30), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p * keep, v_)

    def kern(q_, k_, v_):
        return fa._flash(q_, k_, v_, None, seed_f, 0.125, True, 512, 512,
                         dropout).astype(jnp.float32)

    cast = lambda x: x.astype(dtype)  # noqa: E731
    got = [kern(cast(q), cast(k), cast(v))] + list(jax.grad(
        lambda *a: jnp.sum(kern(*a) * G), argnums=(0, 1, 2))(
            cast(q), cast(k), cast(v)))
    want = [ref(q, k, v)] + list(jax.grad(
        lambda *a: jnp.sum(ref(*a) * G), argnums=(0, 1, 2))(q, k, v))
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        a = np.asarray(a.astype(jnp.float32))
        if dtype == "bfloat16":          # test_bf16_inputs' tolerance
            np.testing.assert_allclose(
                a, np.asarray(b), rtol=3e-2, err_msg=name,
                atol=3e-2 * float(jnp.max(jnp.abs(b))))
        else:
            tol = 2e-5 if name == "out" else 5e-4
            np.testing.assert_allclose(a, np.asarray(b), atol=tol, rtol=tol,
                                       err_msg=name)


def _through_kernels(q, k, v, do, blocks, rate=0.1):
    seed = jnp.asarray([3, 5], jnp.int32)
    o, lse = fa._fwd(q, k, v, None, 0.125, True, *blocks, seed=seed,
                     rate=rate)
    return (o, lse) + fa._bwd_impl(q, k, v, None, o, lse, do, 0.125, True,
                                   *blocks, seed=seed, rate=rate)[:3]


@pytest.mark.parametrize("block,sub", [(512, 128), (512, 256), (384, 128),
                                       (256, 128)])
def test_every_row_group_plan_gives_the_one_group_numbers(monkeypatch,
                                                          block, sub):
    """The same tensors, dropout ON, under every row-group size a block
    can be given and under the one-group body: out, log-sum-exp, dq, dk
    and dv agree to f32 rounding (only the order of a row's f32 sum may
    differ) — so an element's keep bit does not depend on the plan, and
    no group reads or writes a column it should not."""
    Sv = 2 * block
    rng = np.random.RandomState(block + sub)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(1, Sv, 2, 64).astype(np.float32) * 0.5)
    q, k, v, do = mk(), mk(), mk(), mk()
    monkeypatch.setattr(fa, "_group_rows", _one_group)
    assert fa._causal_plan(Sv, Sv, block, block)[0] is None
    want = _through_kernels(q, k, v, do, (block, block))
    monkeypatch.setattr(fa, "_group_rows", lambda b: sub)
    plan, _ = fa._causal_plan(Sv, Sv, block, block)
    assert [len(g) for g in plan.values()] == [block // sub]
    got = _through_kernels(q, k, v, do, (block, block))
    for a, b, name in zip(got, want, ("out", "lse", "dq", "dk", "dv")):
        a, b = np.asarray(a), np.asarray(b)
        span = float(b.max() - b.min())
        assert float(np.abs(a - b).max()) <= 1e-5 * span, name


@pytest.mark.parametrize("sq,sk,sub,engages", [
    (256, 512, 128, True), (512, 640, 256, False)],
    ids=["off256-sub128", "off128-sub256"])
def test_row_groups_behind_a_cache(monkeypatch, sq, sk, sub, engages):
    """Queries behind a longer cache (``off`` = Sk - Sq). ``off`` a
    multiple of the group's rows: the groups engage, at the two places a
    straddling tile of 256 x 512 can lie. ``off`` = 128 under groups of
    256: a group's edge would fall inside a lane tile, and the call
    takes the one-group body. Either way the composition's numbers."""
    monkeypatch.setattr(fa, "_group_rows", lambda b: min(b, sub))
    bq, bk = fa._pick_block(sq, 512), fa._pick_block(sk, 512)
    plan, computed = fa._causal_plan(sq, sk, bq, bk)
    if engages:
        # tile (0, 0): rows see 256 more columns than their own index
        assert plan == {256: ((0, 128, 384, 256), (128, 128, 512, 384))}
        assert computed == 128 * (384 + 512)
    else:
        assert plan is None and computed == 512 * 640
    rng = np.random.RandomState(sq + sk)
    q = jnp.asarray(rng.randn(1, sq, 2, 64).astype(np.float32))
    k, v = (jnp.asarray(rng.randn(1, sk, 2, 64).astype(np.float32))
            for _ in "kv")
    loss = lambda f: lambda q, k, v: jnp.sum(  # noqa: E731
        f(q, k, v, causal=True) ** 2)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(_ref(q, k, v, causal=True)), atol=2e-5, rtol=2e-5)
    for a, b in zip(jax.grad(loss(flash_attention), (0, 1, 2))(q, k, v),
                    jax.grad(loss(_ref), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("Bv", [2, 1], ids=["two-rows-a-step", "one-head-a-step"])
def test_row_groups_under_grouped_kv_heads(Bv):
    """Command A+'s first chunks: query heads of 128 on a quarter as many
    K/V heads, several tiles a head (the K/V blocks' index maps divide
    the head index; a row group reads the K/V block's first rows). With
    ONE head of one batch row a grid step the forward keeps its
    straddling tiles whole (it lost 4% on the chip in groups), and still
    runs no mask code below the diagonal."""
    from paddle_tpu.ops import pallas as pallas_ops
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(Bv, 512, 8, 128).astype(np.float32))
    k, v = (jnp.asarray(rng.randn(Bv, 512, 2, 128).astype(np.float32))
            for _ in "kv")
    pallas_ops.reset_pallas_stats()
    got = flash_attention(q, k, v, causal=True, block_q=256, block_k=256)
    whole = Bv * 8 * 3 * 256 * 256
    assert pallas_ops.FLASH_CAUSAL_WORK["flash_fwd"][0] == (
        whole if Bv == 1 else whole * 5 // 6)
    rep = lambda a: jnp.repeat(a, 4, axis=2)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref(q, rep(k), rep(v), causal=True)),
        atol=2e-5, rtol=2e-5)


def _masked_work_reader():
    """The benchmark's reader of the record, loaded as ``run.py`` does."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "layer_metrics", "flash.masked_work_pct.train.py")
    spec = importlib.util.spec_from_file_location("masked_work", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("rows,share,masked", [(_one_group, 0.75, 33.27),
                                               (None, 0.5625, 11.02)],
                         ids=["one-group", "the-plan"])
def test_causal_work_record(monkeypatch, rows, share, masked):
    """What a traced causal call adds to the always-on record, a kernel:
    the score elements it will compute and those the mask keeps. S=1024
    in blocks of 512: 0.75 S^2 computed under one group (three whole
    tiles of four), 0.5625 under the plan (a diagonal tile computes 10
    of its 16 pieces of 128 x 128), S (S + 1) / 2 = 0.5005 S^2 kept; the
    benchmark's ``flash.masked_work_pct.train`` reads the record."""
    from paddle_tpu.ops import pallas as pallas_ops
    if rows is not None:
        monkeypatch.setattr(fa, "_group_rows", rows)
    Bv, Sv, Hv = 2, 1024, 2
    q = jnp.zeros((Bv, Sv, Hv, 64), jnp.bfloat16)
    pallas_ops.reset_pallas_stats()
    # non-causal calls are not in the record
    jax.make_jaxpr(lambda q: flash_attention(q, q, q))(q)
    assert pallas_ops.FLASH_CAUSAL_WORK == {}
    assert _masked_work_reader()(None) is None
    jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, causal=True).astype(jnp.float32))))(q)
    each = [Bv * Hv * int(share * Sv * Sv), Bv * Hv * Sv * (Sv + 1) // 2]
    assert pallas_ops.FLASH_CAUSAL_WORK == {"flash_fwd": each,
                                            "flash_bwd": each}
    row = {r["kernel"]: r for r in pallas_ops.kernels()}["flash_attention"]
    assert row["causal_work"]["flash_bwd"] == {"computed": each[0],
                                               "kept": each[1]}
    # `flash.masked_work_pct.train`: 100 x (1 - kept / computed)
    assert round(_masked_work_reader()(None), 2) == masked


def test_dropout_public_api_guards():
    q = jnp.asarray(np.random.RandomState(0)
                    .randn(1, 256, 2, 64).astype(np.float32))
    # missing key raises on every backend (interpret path works too)
    with pytest.raises(ValueError, match="dropout_key"):
        flash_attention(q, q, q, dropout_rate=0.5)
    # rate >= 1: defined all-zeros output (XLA-fallback parity), no NaN
    out = flash_attention(q, q, q, dropout_rate=1.0,
                          dropout_key=jax.random.key(0))
    assert float(jnp.abs(out).max()) == 0.0
    # dropout through the public API runs in interpret mode as well
    out = flash_attention(q, q, q, dropout_rate=0.5,
                          dropout_key=jax.random.key(0))
    assert np.isfinite(np.asarray(out)).all()


def test_jit_and_under_trainstep_shapes():
    q, k, v = _qkv(7)
    jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    out = jitted(q, k, v)
    assert out.shape == (B, S, H, D)


def test_env_block_override_validated_and_scoped(monkeypatch):
    """PTPU_FLASH_BLOCK_Q/K overrides: a bad value raises an error NAMING
    the env var; a valid override only applies when the caller left the
    block size at its default (explicit arguments always win)."""
    import pytest

    from paddle_tpu.ops.pallas import flash_attention as fa

    # bad values: named error, raised before any kernel work
    q = jnp.zeros((1, 128, 1, 8), jnp.float32)
    monkeypatch.setenv("PTPU_FLASH_BLOCK_Q", "not_a_number")
    with pytest.raises(ValueError, match="PTPU_FLASH_BLOCK_Q"):
        fa.flash_attention(q, q, q)
    monkeypatch.setenv("PTPU_FLASH_BLOCK_Q", "100")     # not a 128 multiple
    with pytest.raises(ValueError, match="PTPU_FLASH_BLOCK_Q"):
        fa.flash_attention(q, q, q)
    monkeypatch.delenv("PTPU_FLASH_BLOCK_Q")

    # precedence: capture what reaches the kernel without running it
    seen = {}

    def fake_flash(q_, k_, v_, bias, seed_f, scale, causal, bq, bk, rate):
        seen["bq"], seen["bk"] = bq, bk
        return q_

    monkeypatch.setattr(fa, "_flash", fake_flash)
    q = jnp.zeros((1, 512, 1, 8), jnp.float32)
    monkeypatch.setenv("PTPU_FLASH_BLOCK_Q", "128")
    fa.flash_attention(q, q, q)                      # default -> env applies
    assert seen["bq"] == 128
    fa.flash_attention(q, q, q, block_q=256)         # explicit arg wins
    assert seen["bq"] == 256


@pytest.fixture
def tp_dp_mesh(monkeypatch):
    """A dp2 x mp2 mesh active, and the flash gate's backend check faked
    (the marker keeps the kernel interpreted)."""
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed.spmd import make_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh({"dp": 2, "sharding": 1, "mp": 2}, jax.devices()[:4])
    dist_env.set_mesh(mesh)
    yield mesh
    dist_env.reset()


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "bias"])
def test_flash_dispatch_runs_per_shard_under_a_mesh(tp_dp_mesh, masked):
    """GSPMD cannot partition a Mosaic kernel, so under a mesh the flash
    dispatch is a shard_map — batch rows over the data axes, heads over
    mp. Same values and gradients as the unsharded reference, and the
    result keeps that layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.ops import attention

    q, k, v = _qkv(11)
    mask = None
    if masked:
        keep = np.ones((B, 1, 1, S), np.float32)
        keep[0, ..., S // 2:] = 0.0
        mask = jnp.asarray(np.where(keep > 0, 0.0, -1e30)
                           .astype(np.float32))
    assert attention._mesh_splits(B, H) == (2, 2)
    assert attention._flash_supported(q, k, v, mask, 0.0)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * (q + 1.0))

    on_mesh = lambda q, k, v: attention.sdpa_array(  # noqa: E731
        q, k, v, mask, 0.0, not masked, None)
    ref = lambda q, k, v: _ref(q, k, v, mask, causal=not masked)  # noqa
    out = jax.jit(on_mesh)(q, k, v)
    assert out.sharding.is_equivalent_to(
        NamedSharding(tp_dp_mesh, P("dp", None, "mp", None)), out.ndim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g = jax.jit(jax.grad(loss(on_mesh), (0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_dispatch_under_a_mesh_dropout_and_indivisible(tp_dp_mesh):
    """Each shard folds its mesh position into the dropout key (the
    kernel hashes LOCAL coordinates, so shards would otherwise redraw one
    mask); shapes that do not divide the mesh layout take the XLA path."""
    from paddle_tpu.ops import attention

    q, k, v = _qkv(12)
    out = jax.jit(lambda q, k, v, key: attention.sdpa_array(
        q, k, v, None, 0.5, True, key))(q, k, v, jax.random.key(3))
    out = np.asarray(out)
    assert np.isfinite(out).all()
    # identical inputs in both batch rows: only the masks can differ
    same = jax.jit(lambda q, key: attention.sdpa_array(
        q, q, q, None, 0.5, True, key))(
            jnp.broadcast_to(q[:1], q.shape), jax.random.key(3))
    same = np.asarray(same)
    assert not np.allclose(same[0], same[1])
    # 3 batch rows over dp=2: not this kernel's layout
    q3 = jnp.concatenate([q, q[:1]])
    assert attention._mesh_splits(3, H) is None
    assert not attention._flash_supported(q3, q3, q3, None, 0.0)
