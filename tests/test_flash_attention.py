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
    before the rule."""
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
        tiles = {"flash_fwd": bb_fwd * hp, "flash_bwd": bb_bwd * hp}
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
