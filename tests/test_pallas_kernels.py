"""Pallas kernel-layer parity + kill-switch tests (ISSUE 7).

Three kernels behind one dispatch convention (ops/pallas/__init__.py):
fused chunked-CE, paged flash-decode, int8 quantized matmul. Each is
pinned three ways here:

- PARITY: the kernel body (run on CPU via the interpreter — the
  ``pallas`` marker flips FLAGS_pallas_interpret) matches the reference
  math to the module's documented tolerances;
- KILL SWITCH: with the kernel's flag off, dispatch serves the XLA
  fallback and the numbers are bit-identical to the pre-kernel
  implementation;
- OBSERVABILITY: fallbacks land in PALLAS_STATS and (monitor mode) the
  ``pallas_fallback_total{kernel,reason}`` counter; ``kernels()``
  enumerates the layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import chunked_ce as cce
from paddle_tpu.ops import pallas as pallas_ops


# ---------------------------------------------------------------------------
# dispatch convention / registry
# ---------------------------------------------------------------------------


def test_kernel_registry_enumerates_the_layer():
    rows = {r["kernel"]: r for r in pallas_ops.kernels()}
    assert set(rows) == {"flash_attention", "chunked_ce", "paged_decode",
                         "int8_matmul", "bgmv"}
    assert rows["chunked_ce"]["flag"] == "FLAGS_pallas_ce"
    assert rows["paged_decode"]["flag"] == "FLAGS_pallas_paged_decode"
    assert rows["int8_matmul"]["flag"] == "FLAGS_pallas_int8"
    assert rows["bgmv"]["flag"] == "FLAGS_pallas_bgmv"
    # CPU backend without the interpreter: nothing is live
    assert not any(r["live"] for r in rows.values())
    for r in rows.values():
        assert r["fallback"]            # every kernel names its fallback


@pytest.mark.pallas
def test_kernel_registry_live_under_interpret():
    rows = {r["kernel"]: r for r in pallas_ops.kernels()}
    assert rows["chunked_ce"]["live"]
    assert rows["paged_decode"]["live"]
    assert rows["int8_matmul"]["live"]
    with flag_scope("pallas_ce", False):
        rows = {r["kernel"]: r for r in pallas_ops.kernels()}
        assert not rows["chunked_ce"]["live"]
        assert rows["chunked_ce"]["flag_value"] is False


def test_fallbacks_counted_in_stats_and_registry():
    from paddle_tpu.monitor import scoped_registry
    with scoped_registry() as reg, flag_scope("monitor", True):
        assert not pallas_ops.kernel_enabled("chunked_ce")  # CPU backend
        with flag_scope("pallas_interpret", True), \
                flag_scope("pallas_int8", False):
            assert not pallas_ops.kernel_enabled("int8_matmul")
    assert pallas_ops.PALLAS_STATS[("chunked_ce", "cpu_backend")] == 1
    assert pallas_ops.PALLAS_STATS[("int8_matmul", "flag_off")] == 1
    c = reg.counter("pallas_fallback_total")
    assert c.value(kernel="chunked_ce", reason="cpu_backend") == 1
    assert c.value(kernel="int8_matmul", reason="flag_off") == 1
    # kernels() surfaces the observed fallbacks without inflating them
    rows = {r["kernel"]: r for r in pallas_ops.kernels()}
    assert rows["chunked_ce"]["fallbacks_seen"] == {"cpu_backend": 1}
    assert pallas_ops.PALLAS_STATS[("chunked_ce", "cpu_backend")] == 1


@pytest.mark.parametrize("name", ["chunked_ce", "paged_decode",
                                  "int8_matmul", "bgmv"])
def test_compiled_kernels_fall_back_counted_under_a_mesh(name, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel (lowering raises "Mosaic
    kernels cannot be automatically partitioned"), and these call sites
    have no per-shard form: on a TPU under a multi-device mesh they take
    the XLA path, counted as ``mesh``. Interpreted kernel bodies are
    plain XLA ops and stay live; a one-device mesh partitions nothing."""
    import jax
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed.spmd import make_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        dist_env.set_mesh(make_mesh({"dp": 1, "mp": 1}, jax.devices()[:1]))
        assert pallas_ops.kernel_enabled(name)
        dist_env.set_mesh(make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4]))
        assert not pallas_ops.kernel_enabled(name)
        assert dict(pallas_ops.PALLAS_STATS) == {(name, "mesh"): 1}
        with flag_scope("pallas_interpret", True):
            assert pallas_ops.kernel_enabled(name)
    finally:
        dist_env.reset()


def test_monitor_report_kernels_mode(capsys):
    """tools/monitor_report.py --kernels renders the live inventory."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "tools"))
    import monitor_report
    pallas_ops.note_fallback("chunked_ce", "cpu_backend")
    assert monitor_report.main(["--kernels"]) == 0
    out = capsys.readouterr().out
    assert "ops.pallas kernel layer" in out
    for name in ("flash_attention", "chunked_ce", "paged_decode",
                 "int8_matmul"):
        assert name in out
    assert "FLAGS_pallas_ce=on" in out
    assert "cpu_backend:1" in out


# ---------------------------------------------------------------------------
# fused chunked-CE
# ---------------------------------------------------------------------------


def _dense_nll(lg, lab):
    lg32 = lg.astype(jnp.float32)
    return (jax.nn.logsumexp(lg32, -1)
            - jnp.take_along_axis(lg32, lab[:, None], 1)[:, 0])


@pytest.mark.pallas
@pytest.mark.parametrize("N,V,chunk", [(8, 50, 16), (4, 5, 8),
                                       (24, 129, 64), (7, 256, 256)])
def test_ce_kernel_parity_fwd_bwd(N, V, chunk):
    rng = np.random.RandomState(0)
    lg = jnp.asarray((rng.randn(N, V) * 3).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))
    assert pallas_ops.kernel_enabled("chunked_ce", note=False)
    got = cce.hard_nll(lg, lab, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_nll(lg, lab)),
                               rtol=1e-6, atol=1e-6)
    g_ref = jax.grad(lambda l: _dense_nll(l, lab).sum())(lg)
    g_got = jax.grad(lambda l: cce.hard_nll(l, lab, chunk=chunk).sum())(lg)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.pallas
def test_ce_kernel_bf16_f32_accumulation():
    rng = np.random.RandomState(1)
    lg = jnp.asarray(rng.randn(6, 40).astype(np.float32)) \
        .astype(jnp.bfloat16)
    lab = jnp.asarray(rng.randint(0, 40, (6,)).astype(np.int32))
    got = jax.jit(lambda l: cce.hard_nll(l, lab, chunk=16))(lg)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_nll(lg, lab)),
                               rtol=2e-2, atol=1e-2)
    g = jax.grad(lambda l: cce.hard_nll(l, lab, chunk=16).sum())(lg)
    assert g.dtype == jnp.bfloat16


@pytest.mark.pallas
def test_ce_kernel_through_cross_entropy_epilogue():
    """F.cross_entropy keeps ignore_index / class weights / reduction in
    the epilogue OUTSIDE the kernel — parity vs the dense path."""
    rng = np.random.RandomState(2)
    logits_np = (rng.randn(8, 50) * 2).astype(np.float32)
    labels_np = rng.randint(0, 50, (8,)).astype(np.int64)
    labels_np[2] = -100
    w_np = rng.uniform(0.2, 2.0, (50,)).astype(np.float32)
    with flag_scope("chunked_ce_threshold", 8), \
            flag_scope("chunked_ce_chunk", 16):
        x1 = Tensor(logits_np)
        x1.stop_gradient = False
        l1 = F.cross_entropy(x1, Tensor(labels_np), weight=Tensor(w_np))
    with flag_scope("chunked_ce_threshold", 0):
        x2 = Tensor(logits_np)
        x2.stop_gradient = False
        l2 = F.cross_entropy(x2, Tensor(labels_np), weight=Tensor(w_np))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    l1.backward()
    l2.backward()
    np.testing.assert_allclose(np.asarray(x1.grad._data),
                               np.asarray(x2.grad._data),
                               rtol=1e-5, atol=1e-7)
    assert np.abs(np.asarray(x1.grad._data)[2]).max() == 0.0


@pytest.mark.pallas
def test_ce_kill_switch_is_bit_identical_to_pre_kernel_path():
    """FLAGS_pallas_ce off routes hard_nll to the XLA streaming op —
    the EXACT pre-kernel implementation (same function), so fallback
    outputs and gradients are bitwise equal to it."""
    rng = np.random.RandomState(3)
    lg = jnp.asarray(rng.randn(6, 50).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 50, (6,)).astype(np.int32))
    with flag_scope("pallas_ce", False):
        off = cce.hard_nll(lg, lab, chunk=16)
        g_off = jax.grad(lambda l: cce.hard_nll(l, lab, chunk=16).sum())(lg)
    direct = cce._ce_hard(16, lg, lab)
    g_direct = jax.grad(lambda l: cce._ce_hard(16, l, lab).sum())(lg)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(direct))
    np.testing.assert_array_equal(np.asarray(g_off), np.asarray(g_direct))
    assert ("chunked_ce", "flag_off") in pallas_ops.PALLAS_STATS
    # and the kernel path agrees with the fallback to streaming-CE tol
    on = cce.hard_nll(lg, lab, chunk=16)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.pallas
def test_ce_kill_switch_not_defeated_by_eager_op_cache():
    """The Pallas dispatch outcome rides F.cross_entropy's eager-jit
    cache token: flipping FLAGS_pallas_ce between same-signature calls
    must re-dispatch (serving the fallback), not replay the cached
    kernel trace."""
    rng = np.random.RandomState(7)
    logits_np = (rng.randn(8, 64) * 2).astype(np.float32)
    labels_np = rng.randint(0, 64, (8,)).astype(np.int64)
    with flag_scope("chunked_ce_threshold", 8), \
            flag_scope("chunked_ce_chunk", 16):
        x1 = Tensor(logits_np)
        x1.stop_gradient = False
        l_on = F.cross_entropy(x1, Tensor(labels_np))
        with flag_scope("pallas_ce", False):
            x2 = Tensor(logits_np)
            x2.stop_gradient = False
            l_off = F.cross_entropy(x2, Tensor(labels_np))
    # the off-call really took the fallback path (a stale cached kernel
    # trace would never note the flag_off fallback)
    assert ("chunked_ce", "flag_off") in pallas_ops.PALLAS_STATS
    np.testing.assert_allclose(float(l_on), float(l_off), rtol=1e-6)


@pytest.mark.pallas
def test_ce_block_env_override_validated():
    import os
    os.environ["PTPU_CE_BLOCK_N"] = "bogus"
    try:
        with pytest.raises(ValueError, match="PTPU_CE_BLOCK_N"):
            cce.hard_nll(jnp.zeros((4, 32)), jnp.zeros((4,), jnp.int32),
                         chunk=16)
    finally:
        del os.environ["PTPU_CE_BLOCK_N"]


@pytest.mark.parametrize("block_n,chunk,itemsize,want", [
    (128, 8192, 2, 64),      # GPT-2 345M bf16: refused at 128 (16.25 MiB)
    (128, 8192, 4, 64),      # ... and f32 (20.38 MiB)
    (128, 1024, 4, 128),     # small tiles keep the forward's block
    (128, 65536, 4, 8),      # never below the sublane tile
    (24, 65536, 4, 24),      # ... nor off a multiple of 8
])
def test_ce_backward_row_block_fits_scoped_vmem(block_n, chunk, itemsize,
                                                want):
    from paddle_tpu.ops.pallas.chunked_ce import _bwd_block_n
    assert _bwd_block_n(block_n, chunk, itemsize) == want


@pytest.mark.pallas
def test_ce_backward_parity_when_its_row_block_is_halved(monkeypatch):
    """The backward may run a smaller row block than the forward that
    produced its lse residual: same gradients either way."""
    from paddle_tpu.ops.pallas import chunked_ce as kce
    rng = np.random.RandomState(2)
    lg = jnp.asarray((rng.randn(40, 96) * 3).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 96, (40,)).astype(np.int32))
    g_ref = jax.grad(lambda l: _dense_nll(l, lab).sum())(lg)
    # 32-row forward blocks; a budget of one 8-row tile halves twice
    monkeypatch.setenv("PTPU_CE_BLOCK_N", "32")
    monkeypatch.setattr(kce, "_BWD_VMEM_BUDGET", 8 * 32 * 24)
    assert kce._bwd_block_n(32, 32, 4) == 8
    g_got = jax.grad(
        lambda l: kce.chunked_ce_loss(l, lab, 32).sum())(lg)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# paged flash-decode
# ---------------------------------------------------------------------------


def _paged_state(rng, G=1, dtype=jnp.float32, quant=False, layer=0,
                 B=3, MB=4, bs=4, H=2, D=8, P=10):
    """Lane-dense pools ``[2*P, G, bs, (H/G)*D]`` of a two-layer cache +
    tables + positions with slots at different fill levels, written
    through the production write_pages path into ``layer``'s pages.
    Returns ``(pools, tbl, pos, base)``; ``pools`` is ``(k, v)`` or,
    quantized, ``(k, k_scales, v, v_scales)``."""
    from paddle_tpu.serving.kv_cache import write_pages, write_pages_quant
    hg, base = H // G, layer * P
    tbl = np.zeros((B, MB), np.int32)
    tbl[0, :3] = [1, 2, 3]
    tbl[1, :1] = [4]
    tbl[2, :4] = [6, 7, 8, 9]
    tbl = jnp.asarray(tbl)
    pos = jnp.asarray(np.array([9, 2, 14], np.int32))
    pools = []
    for _ in "kv":
        pages = jnp.zeros((2 * P, G, bs, hg * D),
                          jnp.int8 if quant else dtype)
        scales = jnp.zeros((2 * P, G, bs, hg), jnp.float32)
        for b in range(B):
            new = jnp.asarray(rng.randn(1, int(pos[b]) + 1, H, D)
                              .astype(np.float32))
            at = (tbl[b:b + 1], jnp.zeros((1,), jnp.int32), base)
            if quant:
                pages, scales = write_pages_quant(pages, scales, new, *at)
            else:
                pages = write_pages(pages, new, *at)
        pools += [pages, scales] if quant else [pages]
    return tuple(pools), tbl, pos, base


def _dense_decode_ref(q, pools, tbl, pos, base, scale):
    """The XLA fallback's math: gather_pages + masked softmax."""
    from paddle_tpu.serving.kv_cache import gather_pages, gather_pages_quant
    D = q.shape[-1]
    if len(pools) == 4:
        gk = gather_pages_quant(pools[0], pools[1], tbl, D, base)
        gv = gather_pages_quant(pools[2], pools[3], tbl, D, base)
    else:
        gk = gather_pages(pools[0], tbl, D, base).astype(jnp.float32)
        gv = gather_pages(pools[1], tbl, D, base).astype(jnp.float32)
    cols = jnp.arange(gk.shape[1])
    mask = jnp.where(cols[None, :] <= pos[:, None], 0.0, -1e30)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), gk) * scale \
        + mask[:, None, :]
    pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", pr, gv)


@pytest.mark.pallas
@pytest.mark.parametrize("G,dtype,quant,layer,tol", [
    (1, jnp.float32, False, 0, 1e-5),
    (1, jnp.bfloat16, False, 0, 4e-3),   # the OUTPUT rounds to bf16
    (1, jnp.float32, True, 0, 1e-5),
    (2, jnp.float32, False, 0, 1e-5),
    (2, jnp.float32, True, 1, 1e-5),
    (1, jnp.float32, False, 1, 1e-5),
], ids=["f32", "bf16", "int8", "f32-two-groups", "int8-two-groups-layer1",
        "f32-layer1"])
def test_paged_decode_kernel_parity(G, dtype, quant, layer, tol):
    """Both entry points on the lane-dense pool, against the gather
    fallback: one and two head groups, pages of layer 0 and of layer 1
    (table offset by the layer's first page)."""
    from paddle_tpu.ops.pallas.paged_decode import (
        paged_decode_attention, paged_decode_attention_quant)
    rng = np.random.RandomState(0)
    pools, tbl, pos, base = _paged_state(rng, G, dtype, quant, layer)
    q = jnp.asarray(rng.randn(3, 2, 8).astype(np.float32)).astype(dtype)
    scale = 1.0 / np.sqrt(8)
    ref = _dense_decode_ref(q, pools, tbl, pos, base, scale)
    kernel = paged_decode_attention_quant if quant else paged_decode_attention
    got = kernel(q, *pools, tbl + base, pos, scale=scale)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(ref), rtol=tol, atol=tol)
    # under jit (the serving decode program wraps it)
    got_j = jax.jit(lambda *a: kernel(*a, scale=scale))(
        q, *pools, tbl + base, pos)
    np.testing.assert_allclose(np.asarray(got_j.astype(jnp.float32)),
                               np.asarray(ref), rtol=tol, atol=tol)


def _grouped_state(rng, g, dtype, window, B=3, MB=8, bs=4, n_kv=2, D=8):
    """A pool ``[P, 1, bs, n_kv*D]`` whose SCRATCH page holds NaN, a
    table whose entries before a slot's window point at it (as
    ``kv_cache.WindowPages`` leaves them), positions on both sides of
    the window with its first position INSIDE a page, and the query of
    ``g * n_kv`` heads. Returns ``(q, k, v, tbl, pos, first)``."""
    pos = np.array([21, 2, MB * bs - 1][:B], np.int32)
    first = np.maximum(pos - window + 1, 0) if window else np.zeros_like(pos)
    P = B * MB + 1
    tbl = np.zeros((B, MB), np.int32)
    for b in range(B):
        e0, e1 = first[b] // bs, pos[b] // bs
        tbl[b, e0:e1 + 1] = 1 + b * MB + np.arange(e0, e1 + 1)
    pools = []
    for _ in "kv":
        a = rng.randn(P, 1, bs, n_kv * D).astype(np.float32)
        a[0] = np.nan
        pools.append(jnp.asarray(a).astype(dtype))
    q = jnp.asarray(rng.randn(B, g * n_kv, D).astype(np.float32)).astype(dtype)
    return (q, *pools, jnp.asarray(tbl), jnp.asarray(pos),
            jnp.asarray(first) if window else None)


def _grouped_decode_ref(q, k, v, tbl, pos, first, scale):
    """Gather + masked softmax with query head n on K/V head n // g,
    over pools whose scratch page is zeroed (a masked NaN would still
    poison p @ v: the kernel must never read it)."""
    from paddle_tpu.serving.kv_cache import gather_pages
    B, H, D = q.shape
    clean = lambda a: a.at[0].set(0).astype(jnp.float32)
    gk, gv = (gather_pages(clean(a), tbl, D) for a in (k, v))
    n_kv = gk.shape[2]
    cols = jnp.arange(gk.shape[1])[None, :]
    ok = cols <= pos[:, None]
    if first is not None:
        ok &= cols >= first[:, None]
    s = jnp.einsum("bngd,bknd->bngk", q.astype(jnp.float32).reshape(
        B, n_kv, H // n_kv, D), gk) * scale
    pr = jax.nn.softmax(jnp.where(ok[:, None, None], s, -1e30), axis=-1)
    return jnp.einsum("bngk,bknd->bngd", pr, gv).reshape(B, H, D)


@pytest.mark.pallas
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-3)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("g,window", [(16, 0), (16, 10), (4, 0), (4, 10),
                                      (1, 10)])
def test_paged_decode_grouped_heads_and_window_parity(g, window, dtype, tol):
    """Query heads sharing a K/V head (16 and 4 a head; 1 under a
    window) and the sweep from the window's first page — whose first
    visible position lies inside the page (21 - 10 + 1 = 12 is a page's
    first row, 31 - 9 = 22 is not) — against the XLA reference. The
    scratch page holds NaN and every entry before the window points at
    it: an output that is finite never read one."""
    from paddle_tpu.ops.pallas.paged_decode import paged_decode_attention
    rng = np.random.RandomState(g + window)
    q, k, v, tbl, pos, first = _grouped_state(rng, g, dtype, window)
    scale = 1.0 / np.sqrt(8)
    ref = _grouped_decode_ref(q, k, v, tbl, pos, first, scale)
    got = jax.jit(lambda *a: paged_decode_attention(
        *a[:5], scale=scale, first=a[5] if window else None))(
        q, k, v, tbl, pos, first if window else pos)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert np.isfinite(np.asarray(got.astype(jnp.float32))).all()
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(ref), rtol=tol, atol=tol)


#: sha256 of `str(jax.make_jaxpr(paged_decode_attention))` at q (4, 16,
#: 64), pools (40, 1, 16, 1024), table (4, 12), taken from the commit
#: BEFORE kv_group and the window were added (01875443, PR 31): the GPT-2
#: path has to trace to the kernel it was, instruction for instruction
_PR31_JAXPR = {
    "bfloat16": "55fe7efe36f24a1ed3cc504cf6f54a845226f395c68099bb8ca728647bd95f2a",
    "float32": "3086236ad9126a66be363bd006a08f134298395bb0dbbaec0beeaa72b36bf4c2"}


@pytest.mark.pallas
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_decode_one_kv_head_a_query_head_traces_as_before(dtype):
    """`kv_group` 1 and no window: the traced kernel is PR 31's, jaxpr
    for jaxpr (the serve cell `closed64` runs it 24 times a decode
    step). A change of jax's printer would move the hash with no change
    here: take it anew from that commit then."""
    import hashlib
    from paddle_tpu.ops.pallas.paged_decode import paged_decode_attention
    q = jnp.zeros((4, 16, 64), dtype)
    pool = jnp.zeros((40, 1, 16, 1024), dtype)
    text = str(jax.make_jaxpr(
        lambda *a: paged_decode_attention(*a, scale=0.125))(
        q, pool, pool, jnp.zeros((4, 12), jnp.int32),
        jnp.zeros((4,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PR31_JAXPR[jnp.dtype(dtype).name]


@pytest.mark.pallas
@pytest.mark.parametrize("D,g", [(128, 4), (64, 4), (64, 3)],
                         ids=["v2-d128", "v1-d64", "v1-odd-group"])
def test_flash_attention_grouped_kv_heads(D, g):
    """K and V at fewer heads than the query (the K/V blocks' index maps
    divide the head index): the layout-native kernel where a head is a
    whole lane block, the v1 kernel otherwise, against the XLA
    composition with grouped heads — which repeats nothing either."""
    from paddle_tpu.ops.attention import _sdpa_xla
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(D + g)
    B, S, n_kv = 2, 256, 2
    q = jnp.asarray(rng.randn(B, S, g * n_kv, D).astype(np.float32))
    k, v = (jnp.asarray(rng.randn(B, S, n_kv, D).astype(np.float32))
            for _ in "kv")
    got = flash_attention(q, k, v, causal=True)
    want = _sdpa_xla(q, k, v, None, 0.0, True, None)
    # and the composition itself against repeated heads
    rep = lambda a: jnp.repeat(a, g, axis=2)
    np.testing.assert_allclose(
        np.asarray(want),
        np.asarray(_sdpa_xla(q, rep(k), rep(v), None, 0.0, True, None)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="grouped heads"):
        flash_attention(q, k, v, dropout_rate=0.1,
                        dropout_key=jax.random.key(0))


#: the page walk's cases: pools whose pages are 32 KB, so a sweep step
#: takes k = 8 of a 20-entry table row (three groups, the last one short):
#: (pool dtype, head groups, block size); q is [B, 8 * G, 128]
WALKS = {"plain": (jnp.float32, 1, 8), "int8": (jnp.int8, 1, 32),
         "two-groups": (jnp.float32, 2, 8)}
WALK_MB, WALK_HG, WALK_D = 20, 8, 128
#: a slot's position on every edge of a page, of a group of k pages and
#: of the table, from (k, bs, MB)
WALK_EDGES = {"pos-0": lambda k, bs, MB: 0,
              "page-end": lambda k, bs, MB: bs - 1,
              "page-start": lambda k, bs, MB: bs,
              "group-end": lambda k, bs, MB: k * bs - 1,
              "group-start": lambda k, bs, MB: k * bs,
              "table-end": lambda k, bs, MB: MB * bs - 1}


def _walk_case(variant, edges):
    """Random pools ``[N, G, bs, 1024]`` (every page holds finite values,
    owned or not), a slot an edge of ``edges`` owning ``pos // bs + 1``
    distinct pages, then ONE INACTIVE slot: position 0, its whole row
    the scratch page; page ``N - 1`` is nobody's. Returns ``(kernel, q,
    pools, tbl, pos)``."""
    from paddle_tpu.ops.pallas import paged_decode as pd
    dtype, G, bs = WALKS[variant]
    quant = dtype == jnp.int8
    F = WALK_HG * WALK_D
    k = pd._pages_per_step(bs, F, jnp.dtype(dtype).itemsize, WALK_MB)
    assert 1 < k < WALK_MB and WALK_MB % k, "the walk needs several groups"
    pos = np.array([WALK_EDGES[e](k, bs, WALK_MB) for e in edges] + [0],
                   np.int32)
    B, rng = len(pos), np.random.RandomState(3)
    N = 2 + (B - 1) * WALK_MB
    tbl = np.zeros((B, WALK_MB), np.int32)
    for b in range(B - 1):
        n = pos[b] // bs + 1
        tbl[b, :n] = 1 + b * WALK_MB + rng.permutation(WALK_MB)[:n]
    pools = []
    for _ in "kv":
        if quant:
            pools += [jnp.asarray(rng.randint(-127, 128, (N, G, bs, F))
                                  .astype(np.int8)),
                      jnp.asarray(rng.uniform(0.01, 0.03,
                                              (N, G, bs, WALK_HG))
                                  .astype(np.float32))]
        else:
            pools.append(jnp.asarray(rng.randn(N, G, bs, F)
                                     .astype(np.float32)))
    q = jnp.asarray(rng.randn(B, WALK_HG * G, WALK_D).astype(np.float32))
    kernel = (pd.paged_decode_attention_quant if quant
              else pd.paged_decode_attention)
    return kernel, q, tuple(pools), jnp.asarray(tbl), jnp.asarray(pos)


@pytest.mark.pallas
@pytest.mark.parametrize("edge", list(WALK_EDGES) + ["all-in-one-call"])
@pytest.mark.parametrize("variant", list(WALKS))
def test_paged_decode_walk_parity(variant, edge):
    """The sweep follows a slot's live pages, k pages a step: a position
    on every edge of a page and of a page group, a table width k does
    not divide (the last group is clamped and masked), an inactive slot
    on the scratch page beside it, slots of very different lengths in
    one call — against the gather fallback."""
    kernel, q, pools, tbl, pos = _walk_case(
        variant, WALK_EDGES if edge == "all-in-one-call" else [edge])
    scale = 1.0 / np.sqrt(WALK_D)
    ref = _dense_decode_ref(q, pools, tbl, pos, 0, scale)
    got = kernel(q, *pools, tbl, pos, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.pallas
def test_paged_decode_never_reads_past_the_live_pages():
    """Table entries past a slot's last live one never reach the
    arithmetic: pointed at a page of NaN (a masked score is replaced,
    but 0 * NaN in ``p @ v`` would not be), the output is what the
    scratch page there gives, bit for bit, and finite."""
    kernel, q, pools, tbl, pos = _walk_case("plain", WALK_EDGES)
    spare, bs = pools[0].shape[0] - 1, pools[0].shape[2]
    poisoned = tuple(p.at[spare].set(jnp.nan) for p in pools)
    dead = jnp.arange(WALK_MB)[None, :] > (pos // bs)[:, None]
    assert int(dead.sum()) > 0
    scale = 1.0 / np.sqrt(WALK_D)
    want = kernel(q, *pools, tbl, pos, scale=scale)
    got = kernel(q, *poisoned, jnp.where(dead, spare, tbl), pos,
                 scale=scale)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.pallas
def test_paged_decode_bf16_keeps_f32_probabilities():
    """The bf16 path multiplies in bf16 passes but rounds nothing the
    f32 path keeps: on the same (bf16-representable) values both give
    the same result, bit for bit once the f32 one is rounded to the
    bf16 output. (Probabilities rounded to bf16 before ``p @ v``, one
    pass instead of three, differ in 10 to 18 of these 48 elements.)"""
    from paddle_tpu.ops.pallas.paged_decode import paged_decode_attention
    rng = np.random.RandomState(0)
    pools, tbl, pos, base = _paged_state(rng, 1, jnp.bfloat16, False, 0)
    q = jnp.asarray(rng.randn(3, 2, 8).astype(np.float32)) \
        .astype(jnp.bfloat16)
    scale = 1.0 / np.sqrt(8)
    got = paged_decode_attention(q, *pools, tbl + base, pos, scale=scale)
    f32 = paged_decode_attention(
        q.astype(jnp.float32), *(p.astype(jnp.float32) for p in pools),
        tbl + base, pos, scale=scale)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(f32.astype(jnp.bfloat16).astype(jnp.float32)))


def _gpt_paged_decode_logits(pallas_on, scan_on=True, quant="",
                             head_groups=1):
    """One prefill + one batched decode step through GPTModel over the
    paged cache; returns the decode-step hidden states."""
    from paddle_tpu.models.gpt import GPTModel, gpt_tiny
    from paddle_tpu.serving.kv_cache import PagedCacheView, PagedKVCache
    paddle.seed(11)
    cfg = gpt_tiny()
    m = GPTModel(cfg)
    m.eval()
    with flag_scope("serve_kv_quant", quant):
        cache = PagedKVCache(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                             num_pages=10, block_size=4, max_slots=2,
                             max_blocks_per_slot=4,
                             head_groups=head_groups)

    def view(rows):
        if quant:
            return PagedCacheView(cache.k[0], cache.v[0],
                                  cache.table_array(rows),
                                  cache.k[1], cache.v[1])
        return PagedCacheView(cache.k, cache.v, cache.table_array(rows))

    assert cache.alloc_slot(0, 7) and cache.alloc_slot(1, 4)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, (1, n)).astype(np.int32)
               for n in (6, 3)]
    ctx = flag_scope("pallas_paged_decode", pallas_on)
    with ctx, flag_scope("scan_decode", scan_on), paddle.no_grad():
        for slot, ids in enumerate(prompts):
            _, nc = m(paddle.to_tensor(ids), caches=view([slot]),
                      cache_pos=paddle.to_tensor(np.zeros(1, np.int32)))
            if quant:
                cache.update((nc.k._data, nc.k_scale._data),
                             (nc.v._data, nc.v_scale._data))
            else:
                cache.update(nc.k._data, nc.v._data)
        dec = rng.randint(0, cfg.vocab_size, (2, 1)).astype(np.int32)
        hd, _ = m(paddle.to_tensor(dec), caches=view([0, 1]),
                  cache_pos=paddle.to_tensor(np.array([6, 3], np.int32)))
    return np.asarray(hd._data)


#: cache variants the model-level parity runs over: (quant, head groups)
CACHES = [("", 1), ("int8", 1), ("", 2), ("int8", 2)]
CACHE_IDS = ["f32", "int8", "f32-two-groups", "int8-two-groups"]


@pytest.mark.pallas
@pytest.mark.serve
@pytest.mark.parametrize("quant,groups", CACHES, ids=CACHE_IDS)
def test_paged_decode_token_exact_in_gpt_model(quant, groups):
    """Decode through the full GPT paged path (scan layout): kernel-on
    states match the dense fallback to float tolerance and the greedy
    token choice is EXACT."""
    h_off = _gpt_paged_decode_logits(False, quant=quant, head_groups=groups)
    h_on = _gpt_paged_decode_logits(True, quant=quant, head_groups=groups)
    np.testing.assert_allclose(h_on, h_off, rtol=1e-5, atol=1e-5)
    assert (h_on.argmax(-1) == h_off.argmax(-1)).all()


@pytest.mark.pallas
@pytest.mark.serve
@pytest.mark.parametrize("quant,groups", CACHES, ids=CACHE_IDS)
def test_paged_decode_kill_switch_loop_layout(quant, groups):
    """Kill switch off + loop layout = the pre-kernel gather+SDPA path;
    kernel-on loop layout agrees with it, and with the scan layout."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # scan fallback
        h_off = _gpt_paged_decode_logits(False, False, quant, groups)
        h_on = _gpt_paged_decode_logits(True, False, quant, groups)
    assert ("paged_decode", "flag_off") in pallas_ops.PALLAS_STATS
    np.testing.assert_allclose(h_on, h_off, rtol=1e-5, atol=1e-5)
    h_scan = _gpt_paged_decode_logits(True, True, quant, groups)
    np.testing.assert_allclose(h_on, h_scan, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# int8 quantized matmul
# ---------------------------------------------------------------------------


@pytest.mark.pallas
def test_int8_matmul_exact_vs_int_reference():
    """The kernel's integer arithmetic is EXACT: int8 x int8 -> int32
    matches the XLA int dot bit for bit; only the one f32 epilogue
    multiply separates it from the closed form."""
    from paddle_tpu.ops.pallas.quant_matmul import (
        int8_matmul, quantize_per_channel, quantize_per_tensor)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(20, 256).astype(np.float32))
    w = jnp.asarray((rng.randn(256, 128) * 0.05).astype(np.float32))
    w_q, w_s = quantize_per_channel(w)
    x_q, a_s = quantize_per_tensor(x)
    got = int8_matmul(x_q, w_q, w_s, a_s)
    ref = (jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
           .astype(jnp.float32) * (a_s * w_s)[None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.pallas
def test_int8_matmul_within_quantization_error_of_f32():
    from paddle_tpu.ops.pallas.quant_matmul import int8_linear
    from paddle_tpu.ops.pallas.quant_matmul import quantize_per_channel
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 5, 256).astype(np.float32))
    w = jnp.asarray((rng.randn(256, 128) * 0.05).astype(np.float32))
    b = jnp.asarray(rng.randn(128).astype(np.float32))
    w_q, w_s = quantize_per_channel(w)
    y = int8_linear(x, w_q, w_s, bias=b)
    ref = jnp.matmul(x, w) + b
    rel = (np.abs(np.asarray(y) - np.asarray(ref)).max()
           / np.abs(np.asarray(ref)).max())
    assert rel < 0.06, rel


@pytest.mark.pallas
def test_quantized_linear_keeps_weights_int8_through_matmul():
    """slim.QuantizedLinear + FLAGS_pallas_int8: the gemm consumes the
    int8 weights directly (W8A8-dynamic), within quantization error of
    the f32 linear; the static-act mode matches the XLA int8 dot."""
    from paddle_tpu import slim
    from paddle_tpu.nn import Linear
    paddle.seed(0)
    lin = Linear(256, 128)
    x = paddle.to_tensor(
        np.random.RandomState(2).randn(4, 256).astype(np.float32))
    ref = lin(x).numpy()
    q = slim.QuantizedLinear.from_linear(lin)
    assert q.weight_q.numpy().dtype == np.int8
    out = q(x).numpy()
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.05, rel
    # static calibrated act_scale: kernel == the XLA int8 dot fallback
    a_s = float(np.abs(x.numpy()).max() / 127.0)
    q2 = slim.QuantizedLinear.from_linear(lin, act_scale=a_s)
    out_k = q2(x).numpy()
    with flag_scope("pallas_int8", False):
        out_x = q2(x).numpy()
    np.testing.assert_allclose(out_k, out_x, rtol=1e-5, atol=1e-5)


def test_quantized_linear_kill_switch_bit_identical():
    """Flag off (or a CPU backend without the interpreter — the tier-1
    default) = the pre-kernel dequantize-to-float matmul, bit for bit."""
    from paddle_tpu import slim
    from paddle_tpu.nn import Linear
    paddle.seed(1)
    lin = Linear(64, 48)        # not 128-aligned: kernel-ineligible too
    x = paddle.to_tensor(
        np.random.RandomState(3).randn(4, 64).astype(np.float32))
    q = slim.QuantizedLinear.from_linear(lin)
    out = q(x).numpy()
    wq = q.weight_q.numpy()
    s = q.scale.numpy()
    pre_pr = (x.numpy() @ (wq.astype(np.float32) * s)
              + lin.bias.numpy())
    np.testing.assert_allclose(out, pre_pr, rtol=1e-6, atol=1e-6)


@pytest.mark.pallas
def test_int8_shape_fallback_counted():
    from paddle_tpu import slim
    from paddle_tpu.nn import Linear
    paddle.seed(2)
    lin = Linear(100, 48)       # K, N not 128-aligned
    x = paddle.to_tensor(np.ones((2, 100), np.float32))
    slim.QuantizedLinear.from_linear(lin)(x)
    assert ("int8_matmul", "shape") in pallas_ops.PALLAS_STATS


def test_observer_is_the_one_scale_rule():
    """nn.quant.PerChannelAbsMaxObserver == slim._channel_scales ==
    ops.pallas.quantize_per_channel: one quantization grid everywhere."""
    from paddle_tpu import slim
    from paddle_tpu.nn.quant import PerChannelAbsMaxObserver
    from paddle_tpu.ops.pallas.quant_matmul import quantize_per_channel
    rng = np.random.RandomState(4)
    w = (rng.randn(64, 32) * 0.1).astype(np.float32)
    obs = PerChannelAbsMaxObserver(quant_bits=8, quant_axis=1)
    s_obs = obs.observe(w)
    np.testing.assert_allclose(s_obs, slim._channel_scales(w), rtol=1e-7)
    q_k, s_k = quantize_per_channel(jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(s_k), s_obs, rtol=1e-6)
    q_obs, _ = obs.quantize(w)
    np.testing.assert_array_equal(np.asarray(q_k), q_obs)
    # running-absmax accumulation across observe() calls
    s2 = obs.observe(w * 0.5)
    np.testing.assert_allclose(s2, s_obs, rtol=1e-6)


@pytest.mark.pallas
def test_amp_int8_linear_flag_gated():
    """FLAGS_amp_int8_matmul routes eligible F.linear calls under
    autocast through the int8 kernel; the backward is the
    straight-through dense pair, so gradients equal the f32 linear's."""
    from paddle_tpu import amp
    from paddle_tpu.nn import Linear
    paddle.seed(3)
    lin = Linear(128, 128)
    x_np = np.random.RandomState(5).randn(4, 128).astype(np.float32)
    ref = F.linear(paddle.to_tensor(x_np), lin.weight, lin.bias).numpy()

    x1 = paddle.to_tensor(x_np)
    x1.stop_gradient = False
    with flag_scope("amp_int8_matmul", True), \
            amp.auto_cast(level="O1", dtype="float32"):
        y = F.linear(x1, lin.weight, lin.bias)
    rel = np.abs(y.numpy() - ref).max() / np.abs(ref).max()
    assert rel < 0.06, rel
    assert not np.allclose(y.numpy(), ref, atol=1e-7)   # int8 really ran
    y.sum().backward()
    x2 = paddle.to_tensor(x_np)
    x2.stop_gradient = False
    F.linear(x2, lin.weight, lin.bias).sum().backward()
    np.testing.assert_allclose(x1.grad.numpy(), x2.grad.numpy(),
                               rtol=1e-4, atol=1e-5)
    # without the flag: the plain matmul path, bit-identical to ref
    with amp.auto_cast(level="O1", dtype="float32"):
        y_off = F.linear(paddle.to_tensor(x_np), lin.weight, lin.bias)
    np.testing.assert_array_equal(y_off.numpy(), ref)
