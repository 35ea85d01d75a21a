"""Scan-over-layers parity and trace-count tests (ISSUE 2 tentpole).

The decoder/encoder stacks run as ONE jax.lax.scan over layer-stacked
params (nn/scan.py). Contract pinned here:
- scan == loop numerics: forward, backward, and full optimizer steps
  (f32 exact; AMP O1 within bf16 tolerance), incl. under use_recompute
  and a selective checkpoint policy;
- state_dict names and values are unchanged — checkpoints saved from the
  loop stack load into the scanned stack bit-exactly;
- the scan body traces O(1) in the number of layers (the compile-time
  win), pinned via paddle_tpu.utils.CompileCounter so a layer-loop
  re-trace can't silently regress.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.to_static import TrainStep
from paddle_tpu.models.bert import BertForMaskedLM, bert_tiny
from paddle_tpu.models.ernie import ErnieForPretraining, ernie_tiny
from paddle_tpu.models.gpt import (GPTForPretraining, GPTPretrainingCriterion,
                                   gpt_tiny)
from paddle_tpu.optimizer import AdamW


def _gpt_pair(num_layers=3, **kw):
    """Two GPT models with identical weights: loop-stack and scan-stack."""
    paddle.seed(11)
    loop = GPTForPretraining(gpt_tiny(num_layers=num_layers,
                                      scan_layers=False, **kw))
    scan = GPTForPretraining(gpt_tiny(num_layers=num_layers,
                                      scan_layers=True, **kw))
    scan.set_state_dict({k: v.numpy() for k, v in loop.state_dict().items()})
    return loop, scan


def _batch(cfg_vocab=256, B=2, S=16, seed=0):
    rng = np.random.RandomState(seed)
    ids = Tensor(rng.randint(0, cfg_vocab, (B, S)).astype(np.int32))
    labels = Tensor(rng.randint(0, cfg_vocab, (B, S)).astype(np.int32))
    return ids, labels


def test_gpt_scan_forward_backward_parity_f32():
    loop, scan = _gpt_pair()
    ids, labels = _batch()
    crit = GPTPretrainingCriterion()

    l1 = crit(loop(ids), labels)
    l1.backward()
    l2 = crit(scan(ids), labels)
    l2.backward()
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    g1 = {k: np.asarray(p.grad._data) for k, p in loop.named_parameters()}
    g2 = {k: np.asarray(p.grad._data) for k, p in scan.named_parameters()}
    assert set(g1) == set(g2)
    for k in g1:
        np.testing.assert_allclose(g1[k], g2[k], rtol=1e-4, atol=1e-6,
                                   err_msg=k)


@pytest.mark.parametrize("use_recompute,policy", [
    (False, None),
    (True, None),
    (True, "dots_with_no_batch_dims_saveable"),
])
def test_gpt_scan_optimizer_steps_match_loop(use_recompute, policy):
    """Full jitted train steps: scan == loop loss trajectory (f32)."""
    loop, scan = _gpt_pair(use_recompute=use_recompute,
                           recompute_policy=policy)
    ids, labels = _batch(seed=3)
    crit = GPTPretrainingCriterion()

    def loss_fn(layer, i, l):
        return crit(layer(i), l)

    losses = {}
    for tag, m in (("loop", loop), ("scan", scan)):
        paddle.seed(99)          # same TrainStep RNG stream for both
        step = TrainStep(m, loss_fn, AdamW(learning_rate=1e-2))
        losses[tag] = [float(step(ids, labels)) for _ in range(5)]
    np.testing.assert_allclose(losses["loop"], losses["scan"], rtol=2e-5)
    assert losses["scan"][-1] < losses["scan"][0]


def test_gpt_scan_amp_o1_parity():
    """AMP O1: bf16 reassociation differs between the layouts, so parity
    is at bf16 tolerance (one fwd+bwd, not a drifting trajectory)."""
    loop, scan = _gpt_pair()
    ids, labels = _batch(seed=5)
    crit = GPTPretrainingCriterion()

    def loss_fn(layer, i, l):
        with paddle.amp.auto_cast(level="O1"):
            return crit(layer(i), l)

    vals = {}
    for tag, m in (("loop", loop), ("scan", scan)):
        paddle.seed(7)
        step = TrainStep(m, loss_fn, AdamW(learning_rate=1e-3))
        vals[tag] = float(step(ids, labels))
    np.testing.assert_allclose(vals["loop"], vals["scan"], rtol=2e-3)


def test_state_dict_roundtrip_loop_to_scan_bit_exact():
    """Checkpoints from the loop stack load into the scanned stack with
    identical keys and bit-identical arrays (and vice versa)."""
    loop, scan = _gpt_pair(num_layers=4)
    sd_loop = loop.state_dict()
    sd_scan = scan.state_dict()
    assert list(sd_loop.keys()) == list(sd_scan.keys())
    # the per-layer names survive (internal layout contract)
    assert any(k.startswith("gpt.layers.3.") for k in sd_scan)
    for k in sd_loop:
        a = np.asarray(sd_loop[k]._data)
        b = np.asarray(sd_scan[k]._data)
        assert a.dtype == b.dtype and a.shape == b.shape, k
        np.testing.assert_array_equal(a, b, err_msg=k)
    # round-trip through numpy + set_state_dict: loaded values bit-exact
    scan2 = GPTForPretraining(gpt_tiny(num_layers=4, scan_layers=True))
    missing, unexpected = scan2.set_state_dict(
        {k: v.numpy() for k, v in sd_loop.items()})
    assert not missing and not unexpected
    for k, v in scan2.state_dict().items():
        np.testing.assert_array_equal(np.asarray(v._data),
                                      np.asarray(sd_loop[k]._data),
                                      err_msg=k)
    # forward parity between the layouts (float-reassociation tolerance)
    ids, _ = _batch(seed=9)
    with paddle.no_grad():
        np.testing.assert_allclose(loop(ids).numpy(), scan2(ids).numpy(),
                                   rtol=2e-5, atol=2e-6)


def test_scan_body_traces_once_regardless_of_depth():
    """One trace per stack, not per layer: the body-trace count must be
    identical for 2- and 6-layer stacks (CompileCounter pin)."""
    from paddle_tpu.utils import CompileCounter

    crit = GPTPretrainingCriterion()

    def loss_fn(layer, i, l):
        return crit(layer(i), l)

    counts = {}
    for L in (2, 6):
        paddle.seed(0)
        m = GPTForPretraining(gpt_tiny(num_layers=L, scan_layers=True))
        step = TrainStep(m, loss_fn, AdamW(learning_rate=1e-2))
        ids, labels = _batch(seed=L)
        with CompileCounter() as c:
            float(step(ids, labels))
        counts[L] = c.scan_body_traces
        assert c.scan_calls == 1
    assert counts[2] == counts[6] > 0, counts
    # warm call: no new XLA compile, no new body trace
    with CompileCounter() as c:
        float(step(ids, labels))
    assert c.scan_body_traces == 0
    assert c.backend_compiles == 0


def test_bert_and_ernie_scan_matches_loop():
    rng = np.random.RandomState(1)
    ids_np = rng.randint(5, 250, (2, 16)).astype(np.int32)
    pos_np = np.stack([rng.choice(16, 4, replace=False)
                       for _ in range(2)]).astype(np.int32)

    paddle.seed(21)
    b_scan = BertForMaskedLM(bert_tiny(num_layers=3, scan_layers=True))
    b_loop = BertForMaskedLM(bert_tiny(num_layers=3, scan_layers=False))
    b_loop.set_state_dict({k: v.numpy()
                           for k, v in b_scan.state_dict().items()})
    with paddle.no_grad():
        o1 = b_scan(Tensor(ids_np), masked_positions=Tensor(pos_np)).numpy()
        o2 = b_loop(Tensor(ids_np), masked_positions=Tensor(pos_np)).numpy()
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-6)

    paddle.seed(22)
    e_scan = ErnieForPretraining(ernie_tiny(num_layers=3, scan_layers=True))
    e_loop = ErnieForPretraining(ernie_tiny(num_layers=3, scan_layers=False))
    e_loop.set_state_dict({k: v.numpy()
                           for k, v in e_scan.state_dict().items()})
    with paddle.no_grad():
        m1, s1 = e_scan(Tensor(ids_np), masked_positions=Tensor(pos_np))
        m2, s2 = e_loop(Tensor(ids_np), masked_positions=Tensor(pos_np))
    np.testing.assert_allclose(m1.numpy(), m2.numpy(), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(s1.numpy(), s2.numpy(), rtol=2e-5, atol=2e-6)


def test_encoder_scan_with_attention_mask():
    """The broadcast (non-scanned) mask arg reaches every scanned layer."""
    paddle.seed(33)
    m_scan = BertForMaskedLM(bert_tiny(num_layers=2, scan_layers=True))
    m_loop = BertForMaskedLM(bert_tiny(num_layers=2, scan_layers=False))
    m_loop.set_state_dict({k: v.numpy()
                           for k, v in m_scan.state_dict().items()})
    rng = np.random.RandomState(4)
    ids = Tensor(rng.randint(5, 250, (2, 12)).astype(np.int32))
    mask = np.ones((2, 12), np.float32)
    mask[:, 8:] = 0.0
    with paddle.no_grad():
        o1 = m_scan(ids, attention_mask=Tensor(mask)).numpy()
        o2 = m_loop(ids, attention_mask=Tensor(mask)).numpy()
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-6)
    # the mask actually masks: different mask => different output
    with paddle.no_grad():
        o3 = m_scan(ids).numpy()
    assert np.abs(o1 - o3).max() > 1e-3


def test_per_layer_config_divergence_vetoes_scan():
    """The scan body runs every layer through block[0]'s forward, so a
    hand-tuned NON-parameter setting on one layer (stochastic-depth-style
    dropout rate, a swapped activation lambda) must veto the scan — param
    signatures can't see it. The config verdict is cached per stack:
    in-place edits AFTER first use need invalidate_scan_cache."""
    from paddle_tpu import nn
    from paddle_tpu.nn.scan import can_scan_layers, invalidate_scan_cache

    paddle.seed(50)
    m = GPTForPretraining(gpt_tiny(num_layers=3))
    m.gpt.layers[1].dropout1.p = 0.42       # customized before first use
    assert not can_scan_layers(m.gpt.layers)
    # the model silently falls back to the (correct) loop path
    ids, _ = _batch(seed=12)
    with paddle.no_grad():
        m(ids)
    # in-place edit after the cached verdict: explicit invalidation
    m.gpt.layers[1].dropout1.p = m.gpt.layers[0].dropout1.p
    invalidate_scan_cache(m.gpt.layers)
    assert can_scan_layers(m.gpt.layers)

    # distinct per-layer lambdas share __qualname__ but are different
    # functions — identity comparison must veto
    enc = nn.TransformerEncoder(
        nn.TransformerEncoderLayer(16, 2, 32, dropout=0.0), 2)
    assert can_scan_layers(enc.layers)
    invalidate_scan_cache(enc.layers)
    enc.layers[1].activation = lambda t: t * 0.0
    assert not can_scan_layers(enc.layers)

    # a hand-frozen subset (per-layer train/eval heterogeneity) must veto:
    # the scan body would apply block[0]'s mode to every layer
    enc2 = nn.TransformerEncoder(
        nn.TransformerEncoderLayer(16, 2, 32, dropout=0.1), 2)
    enc2.train()
    assert can_scan_layers(enc2.layers)
    enc2.layers[1].eval()
    assert not can_scan_layers(enc2.layers)


def test_uniform_config_edit_retraces_cached_scan():
    """An IN-PLACE but homogeneity-preserving config edit (every layer's
    dropout p set to 0) must invalidate the cached eager scan trace — the
    config signature rides in the op-cache token."""
    from paddle_tpu import nn
    from paddle_tpu.nn.scan import invalidate_scan_cache

    paddle.seed(60)
    enc = nn.TransformerEncoder(
        nn.TransformerEncoderLayer(16, 2, 32, dropout=0.9), 3)
    enc.enable_scan = True
    x = Tensor(np.random.RandomState(0).randn(2, 8, 16).astype(np.float32))
    enc.train()
    enc(x)                                    # trace cached with p=0.9
    for lyr in enc.layers:
        for d in (lyr.dropout, lyr.dropout1, lyr.dropout2):
            d.p = 0.0
        lyr.self_attn.dropout = 0.0
    invalidate_scan_cache(enc.layers)
    y_cold = enc(x).numpy()                   # must retrace with p=0.0
    enc.eval()
    y_eval = enc(x).numpy()
    np.testing.assert_allclose(y_cold, y_eval, rtol=1e-5, atol=1e-6)


def test_scan_fallback_paths():
    """KV-cache decode and the kill-switch flag fall back to the loop."""
    from paddle_tpu.nn import scan as nnscan

    paddle.seed(44)
    m = GPTForPretraining(gpt_tiny(num_layers=2, scan_layers=True))
    ids = Tensor(np.random.RandomState(0).randint(0, 256, (1, 8))
                 .astype(np.int32))
    out = m.generate(ids, max_new_tokens=4)
    assert out.shape[1] == 12

    nnscan.reset_scan_stats()
    from paddle_tpu.core.flags import flag_scope
    with flag_scope("scan_layers", False):
        with paddle.no_grad():
            m(ids)
        assert nnscan.SCAN_STATS["scan_calls"] == 0
    with paddle.no_grad():
        m(ids)
    assert nnscan.SCAN_STATS["scan_calls"] == 1


def test_scan_with_cache_carries_the_cache_whole():
    """scan_layers_with_cache never scans over the cache: every layer's
    body sees the WHOLE array (shape unchanged), finds its own part by
    its ``scan_in`` slice, and what it writes is what the next layer and
    the caller see; the body is traced once whatever the depth."""
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTModel, gpt_tiny
    from paddle_tpu.nn import scan as nn_scan

    paddle.seed(3)
    m = GPTModel(gpt_tiny(num_layers=4))
    m.eval()
    seen = []

    def body(template, x, cache, extras, scan_in):
        (log,), (row,) = cache, scan_in
        seen.append((log.shape, row.shape))
        # layer l adds 1 + what layer l-1 left beside it into ITS row
        prev = jnp.roll(log._data, 1, axis=0)[row._data]
        return template(x), (paddle.to_tensor(
            log._data.at[row._data].add(1.0 + prev + extras[0]._data)),)

    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 5, 64)
                         .astype(np.float32))
    nn_scan.reset_scan_stats()
    with paddle.no_grad():
        y, (log,) = nn_scan.scan_layers_with_cache(
            m.layers, x, (jnp.zeros((4, 3)),), jnp.full((3,), 0.5),
            body_call=body, scan_in=(jnp.arange(4),))
        ref = x
        for blk in m.layers:
            ref = blk(ref)
    assert nn_scan.SCAN_STATS["body_traces"] == 1
    assert seen == [([4, 3], [])]        # paddle shapes are lists
    np.testing.assert_allclose(np.asarray(log._data)[:, 0],
                               [1.5, 3.0, 4.5, 6.0])
    np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# recompute keeps the flash kernel's residuals (ISSUE 34): the trace cache
# under the resolved policies, and the serving programs left as they were
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", [None, "dots_saveable", "full"])
def test_eager_recompute_scan_replays_its_cached_trace(policy):
    """The resolver hands out ONE predicate a policy name and the scan's
    trace cache keys on its identity: a warm eager step traces no body,
    whichever policy (a predicate built anew each call would retrace
    every step)."""
    from paddle_tpu.nn import scan as nn_scan
    paddle.seed(5)
    m = GPTForPretraining(gpt_tiny(num_layers=2, use_recompute=True,
                                   recompute_policy=policy))
    m.train()
    ids, labels = _batch(seed=1)
    crit = GPTPretrainingCriterion()
    crit(m(ids), labels).backward()
    nn_scan.reset_scan_stats()
    crit(m(ids), labels).backward()
    assert nn_scan.SCAN_STATS["scan_calls"] == 1
    assert nn_scan.SCAN_STATS["body_traces"] == 0


#: sha256 of `str(jaxpr)` of what the serving programs are made of. The
#: primal `_flash` and the engine's programs hold no `jax.checkpoint` and
#: never reach the differentiated forward: naming the flash forward's
#: residuals (PR 34) left them equation for equation what they were at
#: the commit before (35758db6, PR 32), and `decode`, which holds no
#: flash kernel, still is. The flash kernel's BODY is in the jaxpr of
#: whatever calls it, so PR 36 (the causal diagonal's two kinds of tile)
#: moved `flash-bfloat16`, `flash-float32` and `prefill`: those three are
#: taken anew from PR 36's tree. A change of jax's printer would move
#: the hashes with no change here: take them anew from these commits then.
_PINNED_JAXPR = {
    "flash-bfloat16": "c89b58f6acf76ad7f41e1e7e6621a9cbd5943c269dd007e1cb678c0ee43734e9",
    "flash-float32": "d6278090f2808dcddfacb87bf538f1e76c2c15aa65afb90607dbd98eb0a06923",
    "prefill": "e50a8e2696ae29eb7af8fe420dc55ec056dd4b26729ba0b0cf0436a51346aa8a",
    # PR 32's, untouched
    "decode": "0a2cd1db06bc721d0a1b97c3740edb4d66ac1e3f75b7b77fb8a7bbf5522f4aee"}


def _sha(jaxpr) -> str:
    import hashlib
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()


@pytest.mark.pallas
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_inference_traces_as_before(dtype):
    """`flash_attention` with no gradient asked is the primal: no
    log-sum-exp output, no named residual."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.zeros((2, 256, 2, 64), dtype)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, causal=True))(q, q, q)
    assert "name[" not in str(jaxpr)
    assert _sha(jaxpr) == _PINNED_JAXPR["flash-" + dtype]


@pytest.mark.pallas
@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_serving_programs_trace_as_before(monkeypatch, kind):
    """The `closed64` engine's programs at a rehearsal size that the
    flash gate takes (heads of 64, a 256-token bucket; bf16 weights and
    cache as in the cell): the prefill program holds `flash_fwd` and the
    decode program `paged_decode`, and both are the parent's."""
    import jax
    from paddle_tpu import inference
    from paddle_tpu.serving import ServingConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny(hidden_size=128, num_heads=2,
                                       max_position_embeddings=512))
    cfg = inference.Config.from_layer(model, input_spec=[])
    cfg.enable_tpu_bf16()
    eng = inference.create_serving_engine(cfg, ServingConfig(
        max_batch_slots=8, block_size=16, max_context_len=512,
        prefill_buckets=(256,), batch_buckets=(1, 4),
        cache_dtype="bfloat16"))
    try:
        prog, args = (eng._prefill_program(1, 256) if kind == "prefill"
                      else eng._decode_program())
        jaxpr = prog._jitted.trace(*args).jaxpr
    finally:
        eng.shutdown()
    text = str(jaxpr)
    assert ("flash_fwd" if kind == "prefill" else "paged_decode") in text
    assert "checkpoint" not in text and "name[" not in text
    assert _sha(jaxpr) == _PINNED_JAXPR[kind]
