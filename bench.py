"""End-of-round benchmark (driver contract).

Measures BASELINE.md configs on the real chip and prints ONE JSON line to
stdout with the headline metric:

    BERT-base MLM training throughput, tokens/sec/chip (BASELINE config 3,
    the north-star metric), on whatever single accelerator is visible.

Diagnostics (LeNet eager step rate, ResNet-50 img/s, MFU breakdown) go to
stderr so stdout stays a single JSON line.

`vs_baseline`: the reference (lijiaqi0612/Paddle) publishes no in-repo
numbers (BASELINE.md: "published": {}), so CUDA parity is proxied by model
FLOPs utilization: strong fused-kernel CUDA BERT pretraining implementations
sit at ~40% MFU. vs_baseline = our_MFU / 0.40 — >= 1.0 means we match or
beat a well-tuned CUDA baseline chip-for-chip.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


CUDA_PARITY_MFU = 0.40

#: legs that raised: logged where they fail, listed again at the end of
#: main(), and the run then exits 1 — a record with a leg missing must
#: not look like a run that measured everything
FAILED_LEGS: list = []


def leg_failed(name: str, err: Exception) -> None:
    log(f"{name} failed: {err!r}")
    FAILED_LEGS.append(f"{name}: {err!r}")


def device_peak_flops() -> float:
    """Peak dense FLOP/s — the per-chip table lives in
    paddle_tpu.cost_model (one source of truth with TrainStep's MFU
    gauge)."""
    from paddle_tpu.cost_model import device_peak_flops as peak
    v = peak()
    if v is None:
        import jax
        raise RuntimeError(
            f"unknown device kind {jax.devices()[0].device_kind!r}: no "
            "peak FLOP/s in paddle_tpu.cost_model.PEAK_FLOPS, so no MFU "
            "can be stated for it (add the chip with its source, do not "
            "assume a peak)")
    return v


def step_program(step) -> dict:
    """The 'step' program's cost/memory attribution from
    TrainStep.stats() — flops/bytes from lowered.cost_analysis(), the
    peak-HBM estimate from compiled.memory_analysis(). Empty dict when
    the backend publishes no cost model (MFU then falls back to the
    per-model analytic FLOP formulas)."""
    try:
        return dict(step.stats().get("programs", {}).get("step") or {})
    except Exception as e:
        log(f"cost attribution unavailable: {e!r}")
        return {}


def attributed_mfu(step, dt_s: float, fallback_flops_step: float) -> float:
    """MFU from the compiler's own FLOP count for the executed step
    (replaces the hand-maintained per-model constants; the analytic
    formula remains only as the no-cost-model fallback)."""
    prog = step_program(step)
    flops = float(prog.get("flops") or 0.0)
    src = "cost_analysis"
    if not flops:
        flops, src = float(fallback_flops_step), "analytic-fallback"
    mfu = flops / dt_s / device_peak_flops()
    log(f"mfu source: {src} ({flops:.3e} FLOPs/step)")
    return mfu


def peak_hbm_line(name: str, step) -> dict | None:
    """Gated ``<model>_peak_hbm_bytes`` metric line (compare_common-safe:
    absent from old records it simply isn't gated; bytes count as
    lower-is-better in check_bench)."""
    peak = step_program(step).get("peak_hbm_bytes") or 0
    if not peak:
        return None
    log(f"{name}: static peak-HBM estimate {peak / 2**30:.2f} GiB "
        "(train step executable)")
    return metric_line(f"{name}_peak_hbm_bytes", peak, "bytes",
                       vs_baseline=1.0)


def steady_ms(call, iters: int, repeats: int = 3) -> float:
    """Min-of-k steady-state ms per call: ``repeats`` independent loops
    of ``iters`` calls, each ended by one blocking scalar readback, and
    the fastest loop's mean is reported (noise only ever adds time;
    reference gate analogue: tools/check_op_benchmark_result.py
    repeated-run stats). One readback per loop, so its cost is spread
    over ``iters`` calls — callers pass iters~40.

    Whether min-of-3 and these loop lengths suit the chip as it is
    attached today has not been measured; the benchmark PR (ROADMAP
    Speed 0) judges the timing method.
    """
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = call()
        _block(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def _block(out) -> float:
    """Force completion with a scalar readback."""
    if isinstance(out, (tuple, list)):
        out = out[0]
    return float(out._data if hasattr(out, "_data") else out)


def metric_line(metric: str, value: float, unit: str, vs_baseline: float,
                **extra) -> dict:
    d = {"metric": metric, "value": round(float(value), 3), "unit": unit,
         "vs_baseline": round(float(vs_baseline), 3)}
    d.update({k: round(float(v), 4) for k, v in extra.items()})
    return d


def bench_bert_mlm() -> dict:
    """BERT-base MLM jitted train step; returns tokens/sec + MFU."""
    import paddle_tpu as paddle
    # bf16 MXU passes with f32 accumulation — the production policy the
    # MFU math (bf16 peak) assumes; the framework-wide default is
    # "highest" (full f32) for numerics-sensitive eager work
    paddle.set_flags({"tpu_matmul_precision": "default"})
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForMaskedLM
    from paddle_tpu.optimizer import AdamW

    B, S, M = 48, 512, 76          # batch, seq, masked positions (15%)
    # (v5e sweep under AMP O1 + flash v2: B=48 160.4k tok/s > B=96 155k
    # > B=64 152.7k > B=128 142.7k)
    cfg = BertConfig()             # base: L12 H768 A12 vocab 30528
    paddle.seed(42)
    model = BertForMaskedLM(cfg)

    def loss_fn(layer, ids, pos, labels):
        # AMP O1: bf16 activations through matmul-class ops, f32 master
        # params/optimizer — the reference's mixed-precision pretraining
        # recipe (BASELINE config 5 calls for AMP explicitly)
        with paddle.amp.auto_cast(level="O1"):
            scores = layer(ids, masked_positions=pos)
            return layer.loss(scores, labels)

    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01)
    step = TrainStep(model, loss_fn, opt)

    # End-to-end from raw strings: a synthetic wordpiece vocab + corpus
    # through text.FasterTokenizer (host-side; batches are fixed-shape so
    # the timed loop below measures the same compiled step)
    from paddle_tpu.text import FasterTokenizer
    rng = np.random.default_rng(0)
    words = [f"w{i:05d}" for i in range((cfg.vocab_size - 5) // 2)]
    vocab = {t: i for i, t in enumerate(
        ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + words
        + ["##" + w for w in words[:cfg.vocab_size - 5 - len(words)]])}
    tok = FasterTokenizer(vocab)
    sentences = [" ".join(rng.choice(words, S + 16)) for _ in range(B)]
    batch = tok(sentences, max_seq_len=S)
    ids = batch["input_ids"]
    log(f"bert: input ids from FasterTokenizer over {B} raw sentences")
    pos = np.stack([rng.choice(S, M, replace=False) for _ in range(B)]
                   ).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (B, M)).astype(np.int32)

    t0 = time.perf_counter()
    loss = step(ids, pos, labels)
    float(loss)                      # block: compile + first step
    compile_s = time.perf_counter() - t0
    log(f"bert: compile+step1 {compile_s:.1f}s loss={float(loss):.3f}")

    for _ in range(3):               # warmup
        loss = step(ids, pos, labels)
    float(loss)

    dt = steady_ms(lambda: step(ids, pos, labels), iters=40,
                   repeats=3) / 1e3
    tokens_per_sec = B * S / dt

    # step-time attribution via the profiler (VERDICT r2 task 6)
    try:
        from paddle_tpu import profiler as prof
        br = prof.profile_train_step(step, (ids, pos, labels), iters=5)
        log(f"bert breakdown: host {br['host_ms']:.2f} ms, dispatch "
            f"{br['dispatch_ms']:.1f} ms, full step {br['step_ms']:.1f} ms"
            f" (warm compile {br['compile_s']:.2f}s)")
    except Exception as e:
        leg_failed("bert breakdown", e)

    # Fallback FLOPs/token ~= 6*P_matmul + 12*L*h*S (PaLM appendix B) —
    # used only when the backend publishes no cost model; the primary
    # count comes from the compiled step itself via step_program().
    h, L = cfg.hidden_size, cfg.num_layers
    p_block = L * (12 * h * h)                       # qkvo + 2 mlp mats
    p_embed_head = cfg.vocab_size * h                # tied decoder gemm
    flops_token = 6 * (p_block + p_embed_head * M / S) + 12 * L * h * S
    mfu = attributed_mfu(step, dt, flops_token * B * S)
    log(f"bert: {dt*1e3:.1f} ms/step  {tokens_per_sec:,.0f} tok/s  "
        f"MFU={mfu:.3f}")
    return {"tokens_per_sec": tokens_per_sec, "mfu": mfu,
            "ms_per_step": dt * 1e3, "compile_s": compile_s,
            "hbm_line": peak_hbm_line("bert_base_mlm", step)}


def bench_eager_dispatch() -> None:
    """Eager per-op dispatch cost (VERDICT round-1: the vjp-trace per op is
    the eager engine's known hot spot; this tracks it) — diagnostic."""
    try:
        import paddle_tpu as paddle

        x = paddle.to_tensor(np.ones((64, 64), np.float32))
        y_t = paddle.to_tensor(np.ones((64, 64), np.float32))
        x.stop_gradient = False
        y_t.stop_gradient = False
        z = (x * y_t + x).sum()                  # warm jit + tape caches
        float(z)
        n = 200
        # host tape overhead: dispatch-only loop (no readback) — the
        # python-side cost per op (tape node + cached-jit lookup/dispatch);
        # the device round-trip is excluded until the final readback
        t0 = time.perf_counter()
        for _ in range(n):
            z = x * y_t                          # one tape-recorded op
        host_us = (time.perf_counter() - t0) / n * 1e6
        float(z.sum())
        # end-to-end: readback every op — includes the device round-trip
        t0 = time.perf_counter()
        for _ in range(20):
            float((x * y_t).sum())
        e2e_us = (time.perf_counter() - t0) / 20 * 1e6
        log(f"eager dispatch: {host_us:.0f} us/op host tape overhead "
            f"(dispatch-only), {e2e_us:.0f} us/op with per-op readback "
            "(device round-trip included)")
    except Exception as e:
        leg_failed("eager dispatch bench", e)


def bench_lenet_eager():
    """Config 1: LeNet eager (dygraph) step rate."""
    try:
        import paddle_tpu as paddle
        from paddle_tpu.nn import functional as F
        from paddle_tpu.optimizer import Momentum
        from paddle_tpu.vision.models import LeNet

        paddle.seed(0)
        model = LeNet()
        opt = Momentum(learning_rate=0.01, parameters=model.parameters())
        x = paddle.to_tensor(
            np.random.default_rng(0).normal(size=(64, 1, 28, 28))
            .astype(np.float32))
        y = paddle.to_tensor(np.zeros((64,), np.int64))

        def one():
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        one()                                        # warm caches
        # eager leg: short loops, iters=10 as in the 2026-07-30 records
        # (comparability); whether the loop length matters on today's
        # attachment is for the benchmark PR to measure
        ms = steady_ms(one, iters=10, repeats=3)
        log(f"lenet eager: {ms:.1f} ms/step (B=64, min of 3 runs)")
        # BASELINE config 1's bar is correctness/convergence, not a CUDA
        # number; vs_baseline tracks the repo's own r3 watermark so the
        # gate sees eager-engine drift (r3: 113.3 ms/step on this chip)
        return metric_line("lenet_eager_ms_per_step", ms, "ms",
                           vs_baseline=113.3 / ms)
    except Exception as e:       # the other legs still run; main() exits 1
        leg_failed("lenet eager bench", e)
        return None


def bench_resnet50():
    """Config 2: ResNet-50 jitted img/s.

    AMP O1 + B=256 (v5e sweep: f32 B=64 848 img/s, f32 B=128 1080,
    AMP B=128 1519, AMP B=256 1649 — bf16 activations halve HBM traffic
    and unlock the larger batch)."""
    try:
        import paddle_tpu as paddle
        from paddle_tpu.jit.to_static import TrainStep
        from paddle_tpu.nn import functional as F
        from paddle_tpu.optimizer import Momentum
        from paddle_tpu.vision.models import resnet50

        B = 256
        paddle.seed(0)
        model = resnet50(num_classes=1000)

        def loss_fn(layer, xb, yb):
            with paddle.amp.auto_cast(level="O1"):
                return F.cross_entropy(layer(xb), yb)

        opt = Momentum(learning_rate=0.1, parameters=model.parameters(),
                       momentum=0.9, weight_decay=1e-4)
        step = TrainStep(model, loss_fn, opt)
        rng = np.random.default_rng(0)
        # device-resident batch: measures the train step, not host->device
        # transfer (production overlaps H2D via the DataLoader prefetcher)
        import jax.numpy as jnp
        x = jnp.asarray(rng.normal(size=(B, 3, 224, 224))
                        .astype(np.float32))
        y = jnp.asarray(rng.integers(0, 1000, (B,)).astype(np.int32))

        t0 = time.perf_counter()
        float(step(x, y))
        compile_s = time.perf_counter() - t0
        log(f"resnet50: compile+step1 {compile_s:.1f}s")
        for _ in range(3):
            step(x, y)
        float(step(x, y))
        dt = steady_ms(lambda: step(x, y), iters=40, repeats=3) / 1e3
        imgs = B / dt
        # fallback: ResNet-50 fwd ≈ 4.1 GFLOP/img at 224² (fwd+bwd ≈
        # 3×fwd); CUDA parity proxy for convnets is ~0.30 MFU
        # (well-tuned fp16 A100 ResNet sits near 25-35% of dense peak)
        mfu = attributed_mfu(step, dt, B * 3 * 4.1e9)
        log(f"resnet50: {dt*1e3:.1f} ms/step  {imgs:,.0f} img/s "
            f"MFU={mfu:.3f} (B={B}, min of 3 runs)")
        return [metric_line("resnet50_train_imgs_per_sec", imgs, "img/s",
                            vs_baseline=mfu / 0.30, mfu=mfu),
                metric_line("resnet50_compile_step1_s", compile_s, "s",
                            vs_baseline=1.0),
                peak_hbm_line("resnet50", step)]
    except Exception as e:
        leg_failed("resnet50 bench", e)
        return None


def bench_gpt2_pp_tp() -> None:
    """Config 4 proper: GPT-2 345M over a pp×mp mesh — the SPMD pipeline
    (scan+ppermute stages) composed with tensor parallelism. Runs whenever
    ≥4 devices are visible; on the single-chip bench harness it logs a
    skip (the schedule itself is validated by tests/test_spmd_pipeline.py
    and the driver's dryrun_multichip on a virtual mesh)."""
    try:
        import jax
        n = len(jax.devices())
        if n < 4:
            log(f"gpt2-345M PP+TP: skipped ({n} device(s) visible; needs a "
                "pp×mp mesh of ≥4 chips — dryrun_multichip config A "
                "exercises this path on a virtual mesh)")
            return
        import paddle_tpu as paddle
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import fleet
        from paddle_tpu.jit.to_static import TrainStep
        from paddle_tpu.models.gpt import (GPTForPretrainingPipe,
                                           GPTPretrainingCriterion,
                                           gpt2_medium)
        from paddle_tpu.optimizer import AdamW

        pp, mp = 2, 2
        dp = n // (pp * mp)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": dp, "pp_degree": pp,
                                   "mp_degree": mp}
        fleet.init(is_collective=True, strategy=strategy)
        mesh = fleet.get_hybrid_communicate_group().mesh

        B, S, M = 8 * dp, 1024, 8
        cfg = gpt2_medium()
        paddle.seed(0)
        model = GPTForPretrainingPipe(cfg, num_microbatches=M)
        model = fleet.distributed_model(model)
        crit = GPTPretrainingCriterion()

        def loss_fn(layer, ids, labels):
            with paddle.amp.auto_cast(level="O1"):
                return crit(layer(ids), labels)

        step = TrainStep(model, loss_fn,
                         AdamW(learning_rate=1e-4, weight_decay=0.01),
                         mesh=mesh, data_spec=P("dp"), zero_axis="dp")
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        labels = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        t0 = time.perf_counter()
        l0 = float(step(ids, labels))
        log(f"gpt2-345M PP+TP: compile+step1 {time.perf_counter()-t0:.1f}s "
            f"loss={l0:.2f} mesh(dp={dp},pp={pp},mp={mp})")
        for _ in range(2):
            step(ids, labels)
        float(step(ids, labels))
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(ids, labels)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        log(f"gpt2-345M PP+TP: {dt*1e3:.1f} ms/step  {B*S/dt:,.0f} tok/s "
            f"({B*S/dt/n:,.0f} tok/s/chip, B={B}, S={S}, M={M} microbatches)")
    except Exception as e:
        leg_failed("gpt2-345M PP+TP bench", e)


def gpt_flops_per_token(h=1024, L=24, V=50304, S=1024) -> float:
    """Analytic training FLOPs/token (6P + attention term, PaLM appendix
    B) — the no-cost-model fallback for attributed_mfu."""
    p_block = L * 12 * h * h
    return 6 * (p_block + V * h) + 12 * L * h * S


def bench_gpt2_345m():
    """Config 4: GPT-2 345M causal LM, single chip (AMP O1); the PP+TP
    variant needs multi-chip hardware.

    No activation recompute: with the bf16 activation stream + flash v2
    the B=8/S=1024 activations fit HBM, and the v5e sweep shows recompute
    only loses (B=8 no-remat 35.2k tok/s / 0.37 model-MFU vs B=16 remat
    28.0k); recompute stays for memory-bound multi-chip configs."""
    try:
        import paddle_tpu as paddle
        from paddle_tpu.jit.to_static import TrainStep
        from paddle_tpu.models.gpt import (GPTForPretraining,
                                           GPTPretrainingCriterion,
                                           gpt2_medium)
        from paddle_tpu.optimizer import AdamW

        B, S = 8, 1024
        cfg = gpt2_medium(use_recompute=False)
        paddle.seed(0)
        model = GPTForPretraining(cfg)
        model.train()
        crit = GPTPretrainingCriterion()

        def loss_fn(layer, ids, labels):
            with paddle.amp.auto_cast(level="O1"):
                return crit(layer(ids), labels)

        step = TrainStep(model, loss_fn,
                         AdamW(learning_rate=1e-4,
                               parameters=model.parameters(),
                               weight_decay=0.01))
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        labels = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        t0 = time.perf_counter()
        l0 = float(step(ids, labels))
        compile_s = time.perf_counter() - t0
        log(f"gpt2-345M: compile+step1 {compile_s:.1f}s loss={l0:.2f}")
        for _ in range(2):
            step(ids, labels)
        float(step(ids, labels))
        dt = steady_ms(lambda: step(ids, labels), iters=40,
                       repeats=3) / 1e3
        tok = B * S / dt
        mfu = attributed_mfu(step, dt,
                             gpt_flops_per_token(S=S) * B * S)
        log(f"gpt2-345M: {dt*1e3:.1f} ms/step  {tok:,.0f} tok/s  "
            f"MFU={mfu:.3f} (B={B}, S={S}, AMP O1, min of 3 runs)")
        return [metric_line("gpt2_345m_tokens_per_sec_per_chip", tok,
                            "tokens/s", vs_baseline=mfu / CUDA_PARITY_MFU,
                            mfu=mfu),
                peak_hbm_line("gpt2_345m", step),
                # NOTE: compile+step1 collapses on a warm persistent
                # cache — cross-record gating of *_compile_step1_s is only
                # apples-to-apples between equally-cold runs (the driver
                # benches in fresh containers; see docs/PERF_TRANSFORMER.md)
                metric_line("gpt2_345m_compile_step1_s", compile_s, "s",
                            vs_baseline=1.0, mfu=mfu)]
    except Exception as e:
        leg_failed("gpt2-345M bench", e)
        return None


def bench_ernie():
    """Config 5 (single-chip leg): ERNIE-base pretraining — MLM + SOP
    heads, AMP O1. The 1.5B hybrid-parallel shape runs in
    dryrun_multichip leg C (needs the v5e-16 mesh); this leg tracks the
    per-chip kernel efficiency of the same model family."""
    try:
        import paddle_tpu as paddle
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.jit.to_static import TrainStep
        from paddle_tpu.models.ernie import ErnieForPretraining, ernie_base
        from paddle_tpu.optimizer import AdamW

        B, S, M = 48, 512, 76
        cfg = ernie_base()
        paddle.seed(0)
        model = ErnieForPretraining(cfg)
        model.train()

        def loss_fn(layer, ids, pos, labels, sop):
            with paddle.amp.auto_cast(level="O1"):
                mlm, sop_sc = layer(ids, masked_positions=pos)
                return layer.loss(mlm, sop_sc, labels, sop)

        step = TrainStep(model, loss_fn,
                         AdamW(learning_rate=1e-4,
                               parameters=model.parameters(),
                               weight_decay=0.01))
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        pos = np.stack([rng.choice(S, M, replace=False)
                        for _ in range(B)]).astype(np.int32)
        labels = rng.integers(0, cfg.vocab_size, (B, M)).astype(np.int32)
        sop = rng.integers(0, 2, (B,)).astype(np.int32)

        t0 = time.perf_counter()
        l0 = float(step(ids, pos, labels, sop))
        compile_s = time.perf_counter() - t0
        log(f"ernie-base: compile+step1 {compile_s:.1f}s loss={l0:.2f}")
        for _ in range(3):
            step(ids, pos, labels, sop)
        float(step(ids, pos, labels, sop))
        dt = steady_ms(lambda: step(ids, pos, labels, sop), iters=40,
                       repeats=3) / 1e3
        tok = B * S / dt
        h, L = cfg.hidden_size, cfg.num_layers
        p_block = L * 12 * h * h
        flops_token = (6 * (p_block + cfg.vocab_size * h * M / S)
                       + 12 * L * h * S)
        mfu = attributed_mfu(step, dt, flops_token * B * S)
        log(f"ernie-base: {dt*1e3:.1f} ms/step  {tok:,.0f} tok/s  "
            f"MFU={mfu:.3f} (B={B}, S={S}, AMP O1, min of 3 runs)")
        return [metric_line("ernie_base_pretrain_tokens_per_sec_per_chip",
                            tok, "tokens/s",
                            vs_baseline=mfu / CUDA_PARITY_MFU, mfu=mfu),
                metric_line("ernie_base_compile_step1_s", compile_s, "s",
                            vs_baseline=1.0, mfu=mfu),
                peak_hbm_line("ernie_base", step)]
    except Exception as e:
        leg_failed("ernie bench", e)
        return None


def bench_serve(quick: bool = False) -> list:
    """``--serve``: GPT-2 345M decode under the synthetic open-loop load
    generator (paddle_tpu.serving, docs/SERVING.md) — the BENCH_serve
    record: serving tokens/s plus p50/p99 per-dispatch decode latency
    and p50 TTFT, gated by tools/check_bench.py like every other metric
    line (ms = lower-is-better, tokens/s = higher-is-better).

    ``--quick`` swaps in gpt_tiny (CPU smoke: same code path, metric
    names carry the model so tiny numbers never gate 345M records)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTForPretraining, gpt2_medium,
                                       gpt_tiny)
    from paddle_tpu.serving import (LoadSpec, SamplingParams,
                                    ServingConfig, ServingEngine,
                                    run_open_loop)

    paddle.seed(42)
    if quick:
        name, cfg = "gpt_tiny", gpt_tiny()
        serve_cfg = ServingConfig(max_batch_slots=4, block_size=8,
                                  max_context_len=128,
                                  prefill_buckets=(16, 32),
                                  batch_buckets=(1, 2, 4))
        spec = LoadSpec(num_requests=6, rate_rps=8.0,
                        prompt_len_range=(8, 24), max_new_range=(4, 12),
                        vocab_size=cfg.vocab_size, seed=0,
                        sampling=SamplingParams())
    else:
        name, cfg = "gpt2_345m", gpt2_medium()
        serve_cfg = ServingConfig(max_batch_slots=8, block_size=16,
                                  max_context_len=512,
                                  prefill_buckets=(128, 256),
                                  batch_buckets=(1, 2, 4))
        spec = LoadSpec(num_requests=16, rate_rps=2.0,
                        prompt_len_range=(64, 224),
                        max_new_range=(16, 48),
                        vocab_size=cfg.vocab_size, seed=0,
                        sampling=SamplingParams())
    from paddle_tpu.testing import chaos
    model = GPTForPretraining(cfg)
    engine = ServingEngine(model, serve_cfg)
    t0 = time.perf_counter()
    # warm the serving signatures the load mix will hit BEFORE traffic:
    # production keeps executables resident; cold compiles would land in
    # the first requests' TTFT and gate-noise every record
    n_prog = engine.warmup()
    log(f"serve[{name}]: {n_prog} serving programs warm in "
        f"{time.perf_counter() - t0:.1f}s "
        f"(buckets {serve_cfg.prefill_buckets} x "
        f"{serve_cfg.batch_buckets} + decode)")
    summary = run_open_loop(engine, spec)
    log(f"serve[{name}]: {summary['requests_completed']} requests, "
        f"{summary['tokens_generated']} tokens, "
        f"{summary['tokens_per_sec']:.1f} tok/s, "
        f"decode p50 {summary['decode_step_p50_s']*1e3:.1f} ms / "
        f"p99 {summary['decode_step_p99_s']*1e3:.1f} ms, "
        f"ttft p50 {summary['ttft_p50_s']*1e3:.1f} ms, "
        f"mean occupancy {summary['mean_decode_occupancy']:.2f}, "
        f"preemptions {summary['preemptions']}")
    if chaos.active():
        # `bench.py --serve --chaos <spec>` wires the injector through
        # the serving bench (sites serve.*; run_open_loop survives
        # shed/watchdog outcomes and counts them)
        log(f"serve[{name}] chaos fires: {chaos.fired()}")
    avail, shed = serve_resilience_metrics(summary)
    log(f"serve[{name}]: availability {avail:.1f}%, shed rate "
        f"{shed:.1f}% (rejected {summary['requests_rejected']}, "
        f"failed {summary['requests_failed']}, watchdog trips "
        f"{summary['watchdog_trips']})")
    trace_overhead = serve_trace_overhead(engine, spec)
    log(f"serve[{name}]: tracing overhead {trace_overhead:.1f}% "
        "(tokens/s at FLAGS_trace_sample=1.0 vs off, same engine)")
    endpoint_overhead = serve_metrics_endpoint_overhead(engine, spec)
    log(f"serve[{name}]: /metrics endpoint overhead "
        f"{endpoint_overhead:.1f}% (tokens/s with a 1 Hz scraper "
        "attached vs without, same engine)")
    throughput_lines = serve_throughput_features(model, name, serve_cfg,
                                                 quick=quick)
    fleet_lines = serve_fleet_metrics(model, name, serve_cfg,
                                      quick=quick)
    mt_lines = serve_multitenant_metrics(model, name, serve_cfg,
                                         quick=quick)
    swap_lines = serve_lifecycle_metrics(model, name, serve_cfg,
                                         quick=quick)
    return throughput_lines + fleet_lines + mt_lines + swap_lines + [
        metric_line(f"serve_{name}_tokens_per_sec",
                    summary["tokens_per_sec"], "tokens/s",
                    vs_baseline=1.0,
                    occupancy=summary["mean_decode_occupancy"]),
        metric_line(f"serve_{name}_decode_p50_ms",
                    summary["decode_step_p50_s"] * 1e3, "ms",
                    vs_baseline=1.0),
        metric_line(f"serve_{name}_decode_p99_ms",
                    summary["decode_step_p99_s"] * 1e3, "ms",
                    vs_baseline=1.0),
        metric_line(f"serve_{name}_ttft_p50_ms",
                    summary["ttft_p50_s"] * 1e3, "ms", vs_baseline=1.0),
        metric_line("serve_availability_pct", avail, "%",
                    vs_baseline=1.0),
        metric_line("serve_shed_rate", shed, "shed%", vs_baseline=1.0),
        # overhead% gates on ABSOLUTE points in check_bench (healthy
        # baseline ~0, where a relative gate is undefined) — the
        # measured form of the docs' tracing-overhead claim
        metric_line("serve_trace_overhead_pct", trace_overhead,
                    "overhead%", vs_baseline=1.0),
        # same unit/shape as the tracing line: the live telemetry
        # plane's scrape endpoint must stay ~free or the flag matrix's
        # "attach Prometheus to production" advice is fiction
        metric_line("serve_metrics_endpoint_overhead_pct",
                    endpoint_overhead, "overhead%", vs_baseline=1.0),
    ]


def serve_throughput_features(model, name, serve_cfg, quick: bool) -> list:
    """ISSUE 15 legs: the chat-style shared-prefix workload under mmpp
    bursty arrivals, served twice on the SAME seed — once with every
    throughput feature off (the oracle) and once with the radix prefix
    cache + chunked prefill + speculative decoding ON. Records
    ``serve_prefix_hit_pct`` (hit%), ``serve_spec_accept_pct``
    (accept%), ``serve_tokens_per_sec_chip`` and ``serve_ttft_p99_ms``
    from the flags-ON run, and REFUSES to record unless the greedy
    outputs of the two runs are token-identical (the acceptance
    criterion is an oracle pin, not a vibe)."""
    import dataclasses

    import jax
    import numpy as np
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.serving import (LoadSpec, SamplingParams,
                                    ServingEngine, run_open_loop)

    if quick:
        # chat shape: a dominant shared system prompt plus a short
        # user tail — the regime the prefix cache exists for. One
        # warm-cache request per prefix precedes the measured run
        # (production caches are warm; a 8-request cold window would
        # measure tree fill, not serving).
        chat = LoadSpec(num_requests=10, rate_rps=20.0,
                        prompt_len_range=(4, 12), max_new_range=(6, 12),
                        vocab_size=model.cfg.vocab_size, seed=7,
                        sampling=SamplingParams(), arrival="mmpp",
                        burstiness=2.0, shared_prefix_len=32,
                        prefix_pool_size=2, prefix_zipf=1.2)
        chunk = 16
    else:
        chat = LoadSpec(num_requests=24, rate_rps=4.0,
                        prompt_len_range=(16, 64),
                        max_new_range=(16, 48),
                        vocab_size=model.cfg.vocab_size, seed=7,
                        sampling=SamplingParams(), arrival="mmpp",
                        burstiness=2.0, shared_prefix_len=256,
                        prefix_pool_size=4, prefix_zipf=1.2)
        chunk = 128
    # parity prompts: a shared-prefix pair plus a self-repetitive tail
    # (the regime speculation accelerates) — run through BOTH engines
    rng = np.random.default_rng(11)
    pre = rng.integers(0, model.cfg.vocab_size, (32,)).tolist()
    parity_prompts = [pre + rng.integers(0, model.cfg.vocab_size,
                                         (8,)).tolist(),
                      pre + rng.integers(0, model.cfg.vocab_size,
                                         (5,)).tolist(),
                      [3, 4, 5, 3, 4, 5, 3, 4]]

    def phase(flags_on: bool):
        import contextlib
        ctx = []
        if flags_on:
            ctx = [flag_scope("serve_prefix_cache", True),
                   flag_scope("serve_prefill_chunk", chunk),
                   flag_scope("serve_spec_k", 4)]
        with contextlib.ExitStack() as stack:
            for c in ctx:
                stack.enter_context(c)
            eng = ServingEngine(model, dataclasses.replace(serve_cfg))
            eng.warmup()
        outs = [o[-8:].tolist() for o in eng.generate(
            parity_prompts, max_new_tokens=8)]
        # warm the prefix tree the way production is warm: a short
        # burst of the SAME-seed workload (the pool prefixes derive
        # from the seed, so a different seed would warm the WRONG
        # prefixes) before the measured window; the flags-OFF engine
        # runs the same warm requests, so both phases measure
        # identical offered work on a steady-state engine
        run_open_loop(eng, dataclasses.replace(
            chat, num_requests=chat.prefix_pool_size, rate_rps=1e6))
        # measured window: deltas around the chat run, not the
        # engine-cumulative summary (which spans the warm phases)
        tok0 = eng._stats["tokens_generated"]
        n_ttft0 = len(eng._lat["ttft"])
        t0 = time.perf_counter()
        summary = run_open_loop(eng, chat)
        wall = max(time.perf_counter() - t0, 1e-9)
        tps = (eng._stats["tokens_generated"] - tok0) / wall
        ttft = eng._lat["ttft"][n_ttft0:]
        ttft99 = (float(np.percentile(np.asarray(ttft), 99)) * 1e3
                  if ttft else 0.0)
        eng.shutdown()
        return summary, outs, tps, ttft99

    s_off, outs_off, tps_off, ttft99_off = phase(False)
    s_on, outs_on, tps_on, ttft99_on = phase(True)
    if outs_on != outs_off:
        log("serve[chat]: PARITY FAILURE — greedy outputs with the "
            "throughput features ON diverge from the flags-off oracle; "
            "refusing to record the feature legs")
        log(f"  off: {outs_off}\n  on:  {outs_on}")
        return []
    hit = s_on["prefix_hit_pct"] or 0.0
    accept = s_on["spec_accept_pct"] or 0.0
    n_chips = max(1, jax.device_count())
    log(f"serve[chat/{name}]: mmpp shared-prefix workload, features "
        f"ON vs OFF on seed {chat.seed}: tokens/s {tps_off:.1f} -> "
        f"{tps_on:.1f} ({(tps_on / max(tps_off, 1e-9) - 1) * 100:+.1f}%), "
        f"ttft p99 {ttft99_off:.1f} -> {ttft99_on:.1f} ms; prefix hit "
        f"{hit:.1f}% ({s_on['prefix_hit_tokens']} tokens), spec accept "
        f"{accept:.1f}% ({s_on['spec_accepted']}/{s_on['spec_proposed']}"
        f", {s_on['spec_rolled_back']} rolled back), "
        f"{s_on['prefill_chunks']} chunks, greedy outputs token-"
        "identical to the oracle")
    return [
        metric_line("serve_prefix_hit_pct", hit, "hit%",
                    vs_baseline=1.0),
        metric_line("serve_spec_accept_pct", accept, "accept%",
                    vs_baseline=1.0,
                    proposed=s_on["spec_proposed"]),
        metric_line("serve_tokens_per_sec_chip", tps_on / n_chips,
                    "tokens/s", vs_baseline=1.0,
                    vs_flags_off=round(tps_on / max(tps_off, 1e-9), 3)),
        metric_line("serve_ttft_p99_ms", ttft99_on, "ms",
                    vs_baseline=1.0,
                    vs_flags_off_ms=round(ttft99_off, 1)),
    ]


def serve_fleet_metrics(model, name, serve_cfg, quick: bool) -> list:
    """ISSUE 16 legs: the tenanted shared-prefix workload served once by
    a single replica and once by an N-replica fleet behind the
    prefix-affine :class:`~paddle_tpu.serving.FleetRouter`, both on the
    SAME seed. Records ``serve_fleet_tokens_per_sec`` (aggregate, the
    per-host busy-time model), ``serve_fleet_scaling_eff_pct``
    (aggregate vs N x single-replica, weak-scaling points),
    ``serve_fleet_prefix_hit_pct`` (affinity must keep fleet hit%
    within a few points of one engine) and
    ``serve_router_overhead_p99_ms`` (route-decision latency) and
    ``serve_fleet_monitor_overhead_pct`` (ISSUE 18: fleet tokens/s
    with a 1 Hz FleetFederator attached vs without, absolute points,
    clamped at 0) — and
    REFUSES to record unless the fleet's greedy outputs are
    token-identical to a single engine's (router parity is an oracle
    pin, same contract as the feature legs above)."""
    import dataclasses

    import numpy as np
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.serving import (FleetRouter, LoadSpec, RouterConfig,
                                    SamplingParams, ServingEngine,
                                    run_fleet_open_loop)

    n_fleet = 2 if quick else 4
    if quick:
        rep_cfg = dataclasses.replace(serve_cfg)
        # load heavy enough that EACH fleet replica keeps its batch
        # slots occupied (otherwise the leg measures batching occupancy
        # loss, not router scaling), with enough distinct tenants that
        # the affinity keys hash-spread across the ring
        fleet_spec = LoadSpec(num_requests=48, rate_rps=240.0,
                              prompt_len_range=(4, 12),
                              max_new_range=(6, 12),
                              vocab_size=model.cfg.vocab_size, seed=13,
                              sampling=SamplingParams(),
                              shared_prefix_len=16, prefix_pool_size=4,
                              prefix_zipf=1.05, tenants=16)
    else:
        # smaller per-replica footprint than the single-engine bench:
        # four 345M KV pools at max_context 512 would measure the
        # host's allocator, not the router
        rep_cfg = dataclasses.replace(serve_cfg, max_batch_slots=4,
                                      max_context_len=256)
        fleet_spec = LoadSpec(num_requests=48, rate_rps=24.0,
                              prompt_len_range=(16, 64),
                              max_new_range=(8, 24),
                              vocab_size=model.cfg.vocab_size, seed=13,
                              sampling=SamplingParams(),
                              shared_prefix_len=64, prefix_pool_size=4,
                              prefix_zipf=1.05, tenants=16)
    rng = np.random.default_rng(11)
    pre = rng.integers(0, model.cfg.vocab_size, (16,)).tolist()
    parity_prompts = [pre + rng.integers(0, model.cfg.vocab_size,
                                         (6,)).tolist(),
                      pre + rng.integers(0, model.cfg.vocab_size,
                                         (4,)).tolist(),
                      [3, 4, 5, 3, 4, 5, 3, 4]]

    def build_fleet(n):
        # prefix cache ON in every replica (kill-switch flags read at
        # engine init), so fleet hit% measures affinity, not a cold
        # cache
        with flag_scope("serve_prefix_cache", True):
            reps = {}
            for i in range(n):
                eng = ServingEngine(model, dataclasses.replace(rep_cfg))
                eng.warmup()
                reps[f"r{i}"] = eng
            # saturation threshold above the default: the bench drives
            # a deliberate overload burst, and spilling every queued
            # request off its affinity replica would measure p2c, not
            # the prefix-affine design point (p2c has its own tests)
            return FleetRouter(reps, RouterConfig(
                seed=3, saturation_queue_depth=12))

    def phase(n):
        router = build_fleet(n)
        try:
            # measured window FIRST — run_fleet_open_loop's summary is
            # cumulative, and the parity prompts are deliberately
            # affinity-skewed (shared prefix → one replica), which
            # would poison the busy-time scaling accounting. Greedy
            # parity is cache-state-independent, so gating after the
            # measured run checks the same thing.
            summary = run_fleet_open_loop(router, fleet_spec)
            outs = [o[-8:].tolist() for o in router.generate(
                parity_prompts, max_new_tokens=8)]
        finally:
            router.shutdown()
        return summary, outs

    def federated_phase(n):
        # the ISSUE 18 fleet plane attached in its production shape:
        # federator at 1 Hz over the (shared, in-process) registry with
        # its admin plane bound — measured against the bare fleet run
        # above; startup/teardown stay outside the measured window
        from paddle_tpu.monitor.fleet import (FederatorConfig,
                                              FleetFederator,
                                              local_registry_target)
        router = build_fleet(n)
        fed = FleetFederator([local_registry_target()],
                             FederatorConfig(interval_s=1.0),
                             router=router, port=0)
        fed.start()
        try:
            summary = run_fleet_open_loop(router, fleet_spec)
        finally:
            fed.close()
            router.shutdown()
        return summary

    s_one, outs_one = phase(1)
    s_fleet, outs_fleet = phase(n_fleet)
    if outs_fleet != outs_one:
        log("serve[fleet]: PARITY FAILURE — fleet-routed greedy "
            "outputs diverge from the single-engine oracle; refusing "
            "to record the fleet legs")
        log(f"  single: {outs_one}\n  fleet:  {outs_fleet}")
        return []
    single_tps = max(s_one["aggregate_tokens_per_sec"], 1e-9)
    agg = s_fleet["aggregate_tokens_per_sec"]
    eff = 100.0 * agg / (n_fleet * single_tps)
    p99_ms = s_fleet["route_overhead_p99_s"] * 1e3
    s_fed = federated_phase(n_fleet)
    fed_tps = s_fed["aggregate_tokens_per_sec"]
    monitor_overhead = max(0.0, 100.0 * (agg - fed_tps)
                           / max(agg, 1e-9))
    log(f"serve[fleet/{name}]: federator attached at 1 Hz: "
        f"{fed_tps:.1f} tok/s vs {agg:.1f} bare "
        f"({monitor_overhead:.1f}% overhead)")
    log(f"serve[fleet/{name}]: {n_fleet} replicas on seed "
        f"{fleet_spec.seed}: aggregate {agg:.1f} tok/s vs single "
        f"{single_tps:.1f} ({eff:.1f}% weak-scaling eff), fleet "
        f"prefix hit {s_fleet['fleet_prefix_hit_pct']:.1f}% vs single "
        f"{s_one['fleet_prefix_hit_pct']:.1f}%, routed "
        f"{s_fleet['routed_affine']} affine / "
        f"{s_fleet['routed_balanced']} balanced, route p99 "
        f"{p99_ms:.2f} ms, availability "
        f"{s_fleet['availability_pct']:.1f}%, greedy outputs "
        "token-identical to the single-engine oracle")
    return [
        metric_line("serve_fleet_tokens_per_sec", agg, "tokens/s",
                    vs_baseline=1.0, replicas=n_fleet),
        metric_line("serve_fleet_scaling_eff_pct", eff, "weak%",
                    vs_baseline=1.0),
        metric_line("serve_fleet_prefix_hit_pct",
                    s_fleet["fleet_prefix_hit_pct"], "hit%",
                    vs_baseline=1.0,
                    vs_single=round(s_one["fleet_prefix_hit_pct"], 1)),
        metric_line("serve_router_overhead_p99_ms", p99_ms, "ms",
                    vs_baseline=1.0),
        metric_line("serve_fleet_availability_pct",
                    s_fleet["availability_pct"], "%", vs_baseline=1.0),
        # overhead% gates on ABSOLUTE points in check_bench (healthy
        # values hover near 0, so a ratio gate would flap on noise)
        metric_line("serve_fleet_monitor_overhead_pct",
                    monitor_overhead, "overhead%", vs_baseline=1.0,
                    federated_tokens_per_sec=round(fed_tps, 1)),
    ]


def serve_lifecycle_metrics(model, name, serve_cfg, quick: bool) -> list:
    """ISSUE 20 leg: the zero-downtime weight-push drill. A 2-replica
    hot-swap-armed fleet serves the bursty ``mmpp`` arrival shape while
    the live tree is re-pushed through
    :meth:`~paddle_tpu.serving.ServingEngine.swap_weights` THREE times
    (at the quarter points of the offered schedule, every replica each
    time — the identity candidate makes greedy outputs swap-invariant,
    so any lost token is the cutover's fault, not the weights').
    Records ``serve_swap_availability_pct`` (swap%: absolute points,
    higher-is-better in check_bench — it lives at ~100 where a relative
    band would hide a 9-point outage) and REFUSES to record unless all
    3 swaps cut over on every replica, availability held >= 99.9%, and
    the request accounting closed exactly (offered == completed +
    failed + rejected, zero in flight, zero duplicate ids — the
    zero-lost/zero-dup contract from docs/SERVING.md "Model
    lifecycle")."""
    import dataclasses
    import shutil
    import tempfile

    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.distributed import checkpoint as dckpt
    from paddle_tpu.serving import (FleetRouter, LoadSpec, RouterConfig,
                                    SamplingParams, ServerOverloaded,
                                    ServingEngine, build_requests)

    n_reps = 2
    if quick:
        rep_cfg = dataclasses.replace(serve_cfg)
        spec = LoadSpec(num_requests=48, rate_rps=240.0,
                        prompt_len_range=(4, 12), max_new_range=(6, 12),
                        vocab_size=model.cfg.vocab_size, seed=17,
                        sampling=SamplingParams(), arrival="mmpp",
                        burstiness=3.0, mmpp_switch=0.2,
                        shared_prefix_len=16, prefix_pool_size=4,
                        prefix_zipf=1.05, tenants=8)
    else:
        rep_cfg = dataclasses.replace(serve_cfg, max_batch_slots=4,
                                      max_context_len=256)
        spec = LoadSpec(num_requests=48, rate_rps=24.0,
                        prompt_len_range=(16, 64),
                        max_new_range=(8, 24),
                        vocab_size=model.cfg.vocab_size, seed=17,
                        sampling=SamplingParams(), arrival="mmpp",
                        burstiness=3.0, mmpp_switch=0.2,
                        shared_prefix_len=64, prefix_pool_size=4,
                        prefix_zipf=1.05, tenants=8)
    with flag_scope("serve_hot_swap", True):
        reps = {}
        for i in range(n_reps):
            eng = ServingEngine(model, dataclasses.replace(rep_cfg))
            eng.warmup()
            reps[f"r{i}"] = eng
        router = FleetRouter(reps, RouterConfig(
            seed=3, saturation_queue_depth=12))
    push_dir = tempfile.mkdtemp(prefix="bench_swap_")
    schedule = build_requests(spec)
    quarters = [len(schedule) // 4, len(schedule) // 2,
                (3 * len(schedule)) // 4]
    swaps_done = 0
    rejected = 0
    try:
        # the pushed candidate: the live tree itself, re-saved as a
        # committed manifest checkpoint (identity swap — the strongest
        # isolation of cutover mechanics from weight quality)
        dckpt.save(dict(reps["r0"].params), push_dir,
                   asynchronous=False)
        t0 = time.perf_counter()
        i = 0
        while i < len(schedule) or any(
                r.alive and r.engine.scheduler.has_work
                for r in router.replicas.values()):
            now = time.perf_counter() - t0
            while i < len(schedule) and schedule[i][0] <= now:
                try:
                    router.submit(schedule[i][1])
                except ServerOverloaded:
                    rejected += 1
                i += 1
            if swaps_done < len(quarters) and i >= quarters[swaps_done]:
                # live push: every replica, no drain, traffic running
                for rep in router.replicas.values():
                    info = rep.engine.swap_weights(push_dir)
                    if not info.get("pending"):
                        rep.engine.commit_swap()
                swaps_done += 1
            if not router.step_all() and i < len(schedule):
                wait = schedule[i][0] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
        epochs = {n: r.engine.metrics_summary()["weights_epoch"]
                  for n, r in router.replicas.items()}
        summary = router.summary()
    finally:
        router.shutdown()
        shutil.rmtree(push_dir, ignore_errors=True)
    avail = summary["availability_pct"]
    lost = (summary["requests_offered"] - summary["requests_completed"]
            - summary["requests_failed"] - summary["requests_rejected"])
    problems = []
    if any(e != len(quarters) for e in epochs.values()):
        problems.append(f"epochs {epochs} != {len(quarters)} everywhere")
    if avail < 99.9:
        problems.append(f"availability {avail:.2f}% < 99.9%")
    if lost or summary["requests_in_flight"]:
        problems.append(f"{lost} lost / "
                        f"{summary['requests_in_flight']} in flight")
    if summary["duplicate_request_ids"]:
        problems.append(f"{summary['duplicate_request_ids']} duplicate "
                        "request ids")
    if problems:
        log(f"serve[lifecycle]: SWAP DRILL FAILURE — {'; '.join(problems)}"
            "; refusing to record the hot-swap leg")
        return []
    log(f"serve[lifecycle/{name}]: {swaps_done} live swaps x {n_reps} "
        f"replicas under mmpp load: availability {avail:.2f}%, "
        f"{summary['requests_completed']} completed / "
        f"{summary['requests_failed']} failed / {rejected} rejected, "
        f"accounting closed (0 lost, 0 dup), final epochs {epochs}")
    return [
        # swap% gates on ABSOLUTE points, drop = regression
        # (check_bench _ABS_POINT_HIGHER_UNITS)
        metric_line("serve_swap_availability_pct", avail, "swap%",
                    vs_baseline=1.0, swaps=swaps_done,
                    replicas=n_reps),
    ]


def serve_multitenant_metrics(model, name, serve_cfg, quick: bool) -> list:
    """ISSUE 17 legs: the multi-tenant LoRA + int8-quantized-KV serving
    shape. One flags-off oracle engine and one multi-tenant engine
    (``FLAGS_serve_kv_quant=int8``, a LoRAManager pool with one adapter
    per tenant/id in the traffic, a per-tenant admission quota) serve
    the SAME seeded tenanted workload. Records
    ``serve_kv_bytes_per_token`` (bytes/token, lower-is-better; refused
    unless int8 lands at or below 0.55x the bf16 full-precision
    footprint) and ``serve_lora_adapters_per_chip`` (adapters,
    higher-is-better; refused unless the multi-tenant decode p99 held
    the fixed budget of 1.5x the oracle's p99) — and REFUSES to record
    anything unless zero-adapter greedy decode under quant is
    token-identical to the flags-off oracle (same contract as the
    feature/fleet legs)."""
    import dataclasses

    import jax
    import numpy as np
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.serving import (LoadSpec, SamplingParams,
                                    ServingEngine, run_open_loop)

    cfg = model.cfg
    n_tenants, per_tenant = (3, 2) if quick else (4, 2)
    rank = 4 if quick else 8
    if quick:
        spec = LoadSpec(num_requests=12, rate_rps=40.0,
                        prompt_len_range=(4, 12), max_new_range=(4, 10),
                        vocab_size=cfg.vocab_size, seed=23,
                        sampling=SamplingParams(), shared_prefix_len=8,
                        prefix_pool_size=2, tenants=n_tenants,
                        adapter_pool=per_tenant)
    else:
        spec = LoadSpec(num_requests=24, rate_rps=6.0,
                        prompt_len_range=(16, 64),
                        max_new_range=(8, 24),
                        vocab_size=cfg.vocab_size, seed=23,
                        sampling=SamplingParams(), shared_prefix_len=32,
                        prefix_pool_size=2, tenants=n_tenants,
                        adapter_pool=per_tenant)
    rng = np.random.default_rng(29)
    parity_prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
                      for n in (9, 6, 12)]

    def phase(multitenant: bool):
        if multitenant:
            eng_cfg = dataclasses.replace(
                serve_cfg, lora_adapters=n_tenants * per_tenant,
                lora_rank=rank,
                tenant_quota=max(2, serve_cfg.max_batch_slots // 2))
            with flag_scope("serve_kv_quant", "int8"):
                eng = ServingEngine(model, eng_cfg)
            # one LoRA adapter per tenant/id the traffic can name,
            # hot-swapped in through the manager (tiny magnitudes: the
            # leg measures serving capacity, not adapter quality)
            wrng = np.random.default_rng(31)
            L, E, r = cfg.num_layers, cfg.hidden_size, rank
            O = 3 * cfg.hidden_size
            for t in range(n_tenants):
                for k in range(per_tenant):
                    eng.lora.load_adapter(
                        f"tenant{t}/adapter{k}",
                        weights=(wrng.standard_normal((L, r, E))
                                 .astype(np.float32) * 1e-3,
                                 wrng.standard_normal((L, r, O))
                                 .astype(np.float32) * 1e-3))
        else:
            eng = ServingEngine(model, dataclasses.replace(serve_cfg))
        eng.warmup()
        # zero-adapter greedy parity probe: base requests on the
        # multi-tenant engine ride the zero adapter (delta exactly 0.0),
        # so only the int8 KV path separates the two engines here
        outs = [o[-8:].tolist() for o in eng.generate(
            parity_prompts, max_new_tokens=8)]
        # the oracle has no LoRA manager, so its copy of the workload
        # drops the adapter ids; adapter_pool draws from a side RNG, so
        # prompts, lengths and arrival times stay byte-identical
        summary = run_open_loop(
            eng, spec if multitenant
            else dataclasses.replace(spec, adapter_pool=0))
        summary["kv_bytes_per_token"] = eng.cache.kv_bytes_per_token()
        eng.shutdown()
        return summary, outs

    s_off, outs_off = phase(False)
    s_mt, outs_mt = phase(True)
    if outs_mt != outs_off:
        log("serve[multitenant]: PARITY FAILURE — zero-adapter greedy "
            "outputs under FLAGS_serve_kv_quant=int8 diverge from the "
            "flags-off oracle; refusing to record the multi-tenant legs")
        log(f"  off: {outs_off}\n  on:  {outs_mt}")
        return []
    lines = []
    n_chips = max(1, jax.device_count())
    # footprint bound vs FULL-PRECISION bf16 pages (the documented
    # acceptance bound, independent of this engine's configured cache
    # dtype): int8 pages + f32 per-(position, head) scales
    bf16_bytes = 2 * cfg.num_layers * cfg.num_heads * \
        (cfg.hidden_size // cfg.num_heads) * 2
    bq, boff = s_mt["kv_bytes_per_token"], s_off["kv_bytes_per_token"]
    log(f"serve[multitenant/{name}]: kv bytes/token {boff} -> {bq} "
        f"({bq / max(boff, 1):.2f}x vs flags-off, "
        f"{bq / max(bf16_bytes, 1):.2f}x vs bf16 full precision)")
    if bq <= 0.55 * bf16_bytes:
        lines.append(metric_line(
            "serve_kv_bytes_per_token", bq, "bytes/token",
            vs_baseline=1.0, flags_off_bytes=boff,
            vs_bf16=round(bq / max(bf16_bytes, 1), 3)))
    else:
        log("serve[multitenant]: int8 KV footprint exceeds 0.55x bf16 "
            "— refusing to record serve_kv_bytes_per_token")
    # adapters-per-chip at a FIXED p99 budget: the count only records
    # while the multi-tenant decode p99 holds 1.5x the oracle's
    p99_off = s_off["decode_step_p99_s"] or 0.0
    p99_mt = s_mt["decode_step_p99_s"] or 0.0
    budget = 1.5 * p99_off
    n_adapters = n_tenants * per_tenant
    log(f"serve[multitenant/{name}]: {n_adapters} adapters over "
        f"{n_tenants} tenants, decode p99 {p99_mt * 1e3:.1f} ms vs "
        f"budget {budget * 1e3:.1f} ms (1.5x oracle), "
        f"{s_mt['requests_completed']}/{spec.num_requests} completed, "
        f"quota deferrals {s_mt.get('quota_deferred', 0)}")
    if p99_off > 0 and p99_mt <= budget:
        lines.append(metric_line(
            "serve_lora_adapters_per_chip", n_adapters / n_chips,
            "adapters", vs_baseline=1.0,
            p99_ms=round(p99_mt * 1e3, 2),
            budget_ms=round(budget * 1e3, 2)))
    else:
        log("serve[multitenant]: decode p99 blew the fixed budget — "
            "refusing to record serve_lora_adapters_per_chip")
    return lines


def serve_trace_overhead(engine, spec) -> float:
    """Measured tokens/s cost of structured tracing at sample rate 1.0
    (every request traced — the worst case; production head-samples at
    FLAGS_trace_sample=0.01): two open-loop phases on the SAME warm
    engine (no recompiles — tracing is host-side only), tracing off
    then on, compared on wall-clock tokens/s. Returns max(0, %slower);
    sub-noise differences clamp to 0."""
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.monitor import trace as trace_mod
    from paddle_tpu.serving import run_open_loop

    def phase(traced: bool) -> float:
        tok0 = engine._stats["tokens_generated"]
        t0 = time.perf_counter()
        if traced:
            with flag_scope("trace", True), \
                    flag_scope("trace_sample", 1.0):
                run_open_loop(engine, spec)
        else:
            run_open_loop(engine, spec)
        dt = max(time.perf_counter() - t0, 1e-9)
        return (engine._stats["tokens_generated"] - tok0) / dt

    tps_off = phase(False)
    tps_on = phase(True)
    trace_mod.get_tracer().reset()     # bench must not hold the ring
    if tps_off <= 0:
        return 0.0
    return max(0.0, 100.0 * (tps_off - tps_on) / tps_off)


def serve_metrics_endpoint_overhead(engine, spec) -> float:
    """Measured tokens/s cost of the live telemetry plane's scrape
    endpoint: two open-loop phases on the SAME warm engine — without a
    server, then with an embedded AdminServer and a 1 Hz ``/metrics``
    scraper attached (the Prometheus-attached production shape,
    docs/OBSERVABILITY.md scrape-interval guidance). Returns
    max(0, %slower); sub-noise differences clamp to 0 (the overhead%
    gate in tools/check_bench.py rides ABSOLUTE points)."""
    import threading
    import urllib.request
    from paddle_tpu.monitor.server import AdminServer
    from paddle_tpu.serving import run_open_loop

    def phase(scraped: bool) -> float:
        # server bind + scraper-thread startup happen OUTSIDE the timed
        # region: the metric is the steady-state cost of being scraped,
        # not the one-time cost of starting the plane
        srv = th = None
        stop = threading.Event()
        if scraped:
            srv = AdminServer(port=0).start()
            url = srv.url + "/metrics"

            def scraper():
                while not stop.is_set():
                    try:
                        with urllib.request.urlopen(url, timeout=2) as r:
                            r.read()
                    except Exception:
                        pass            # the load phase is the subject;
                    stop.wait(1.0)      # a flaky scrape must not abort it

            th = threading.Thread(target=scraper, daemon=True)
            th.start()
        tok0 = engine._stats["tokens_generated"]
        t0 = time.perf_counter()
        try:
            run_open_loop(engine, spec)
        finally:
            dt = max(time.perf_counter() - t0, 1e-9)
            if scraped:
                stop.set()
                th.join(timeout=2.0)
                srv.close()
        return (engine._stats["tokens_generated"] - tok0) / dt

    tps_off = phase(False)
    tps_on = phase(True)
    if tps_off <= 0:
        return 0.0
    return max(0.0, 100.0 * (tps_off - tps_on) / tps_off)


def serve_resilience_metrics(summary: dict) -> tuple:
    """(availability_pct, shed_rate_pct) of an open-loop serving run:
    availability = requests that completed / requests offered; shed rate
    = requests refused or dropped by admission control (client-side
    rejections + policy sheds + queued expiries) / offered. Failed/
    drained requests count against availability but are not "shed" —
    they were admitted."""
    offered = max(int(summary.get("num_requests") or 0), 1)
    completed = int(summary.get("requests_completed") or 0)
    # only QUEUED expiries are shed; an in-flight expiry was admitted
    # and decoded, so it counts against availability alone
    shed = (int(summary.get("requests_rejected") or 0)
            + int(summary.get("requests_shed") or 0)
            + int(summary.get("requests_expired_queued") or 0))
    return 100.0 * completed / offered, 100.0 * shed / offered


def bench_kernels(quick: bool = False) -> list:
    """``--kernels``: kernel-level microbench of the ops.pallas layer
    (docs/PERF_KERNELS.md) — the BENCH_kernels record. Each kernel is
    timed at the DISPATCH level, so the numbers measure whatever path
    production would serve here: the Pallas body on TPU, the XLA
    fallback elsewhere (``kernel_live`` on each line says which; a
    record made on a CPU times XLA's CPU backend and says nothing about
    the chip). ``kernel_*_ms``
    gates lower-is-better, ``kernel_*_gbps`` (bytes the op must move /
    wall time — the bandwidth-bound figure of merit) higher-is-better.

    ``--quick``: tiny shapes, smoke only, no record."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn import chunked_ce as cce
    from paddle_tpu.ops import pallas as pallas_ops

    rng = np.random.RandomState(0)
    lines = []

    def gbps(nbytes, ms):
        return nbytes / (ms * 1e-3) / 1e9

    # -- fused chunked CE: fwd+bwd over [N, V] logits ----------------------
    N, V = (256, 2048) if quick else (2048, 32768)
    chunk = min(V, 8192)
    logits = jnp.asarray(rng.randn(N, V).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))
    live = float(pallas_ops.kernel_enabled("chunked_ce", note=False))
    step = jax.jit(jax.value_and_grad(
        lambda l: cce.hard_nll(l, labels, chunk=chunk).sum()))
    step(logits)[0].block_until_ready()          # compile outside the clock
    ms = steady_ms(lambda: step(logits)[0], iters=2 if quick else 5)
    # bytes the op must move: logits read fwd + read bwd + dlogits write
    by = 3 * N * V * 4
    log(f"kernels[ce]: [{N}, {V}] fwd+bwd {ms:.1f} ms, "
        f"{gbps(by, ms):.1f} GB/s (live={live:.0f})")
    lines += [
        metric_line("kernel_chunked_ce_ms", ms, "ms", vs_baseline=1.0,
                    kernel_live=live),
        metric_line("kernel_chunked_ce_gbps", gbps(by, ms), "GB/s",
                    vs_baseline=1.0, kernel_live=live),
    ]

    # -- paged flash-decode: one decode step over the paged KV pool --------
    B, H, D, bs, MB = (2, 4, 16, 4, 4) if quick else (8, 16, 64, 16, 32)
    P = B * MB + 1                               # page 0 = scratch
    # lane-dense pools [pages, head groups, rows, H*D] (serving.kv_cache)
    kp = jnp.asarray(rng.randn(P, 1, bs, H * D).astype(np.float32))
    vp = jnp.asarray(rng.randn(P, 1, bs, H * D).astype(np.float32))
    tbl = jnp.asarray(
        1 + np.arange(B * MB, dtype=np.int32).reshape(B, MB))
    pos = jnp.full((B,), MB * bs - 1, jnp.int32)  # slots fully grown
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    scale = 1.0 / float(np.sqrt(D))
    live = float(pallas_ops.kernel_enabled("paged_decode", note=False))
    if live:
        from paddle_tpu.ops.pallas.paged_decode import paged_decode_attention
        fn = jax.jit(lambda *a: paged_decode_attention(*a, scale=scale))
    else:
        from paddle_tpu.serving.kv_cache import gather_pages

        def _fallback(q_, kp_, vp_, tbl_, pos_):
            gk = gather_pages(kp_, tbl_, D)
            gv = gather_pages(vp_, tbl_, D)
            cols = jnp.arange(gk.shape[1])
            mask = jnp.where(cols[None, :] <= pos_[:, None], 0.0, -1e30)
            s = (jnp.einsum("bhd,bkhd->bhk", q_, gk) * scale
                 + mask[:, None, :])
            pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
            return jnp.einsum("bhk,bkhd->bhd", pr, gv).astype(q_.dtype)

        fn = jax.jit(_fallback)
    fn(q, kp, vp, tbl, pos).block_until_ready()
    ms = steady_ms(lambda: fn(q, kp, vp, tbl, pos).ravel()[0],
                   iters=5 if quick else 20)
    # bytes the step must move: every live K/V page read once
    by = 2 * B * MB * bs * H * D * 4
    log(f"kernels[paged_decode]: B={B} ctx={MB * bs} H={H} D={D} "
        f"{ms:.2f} ms, {gbps(by, ms):.1f} GB/s (live={live:.0f})")
    lines += [
        metric_line("kernel_paged_decode_ms", ms, "ms", vs_baseline=1.0,
                    kernel_live=live),
        metric_line("kernel_paged_decode_gbps", gbps(by, ms), "GB/s",
                    vs_baseline=1.0, kernel_live=live),
    ]

    # -- batched LoRA gather-matmul (bgmv): per-slot adapter deltas --------
    B, S, r, E = (4, 1, 4, 64) if quick else (8, 1, 16, 1024)
    O, A = 3 * E, 9                              # row 0 = zero adapter
    x = jnp.asarray(rng.randn(B, S, E).astype(np.float32))
    ap = jnp.asarray(rng.randn(A, r, E).astype(np.float32) * 0.05)
    bp = jnp.asarray(rng.randn(A, r, O).astype(np.float32) * 0.05)
    ids = jnp.asarray(rng.randint(0, A, (B,)).astype(np.int32))
    live = float(pallas_ops.kernel_enabled("bgmv", note=False))
    if live:
        from paddle_tpu.ops.pallas.bgmv import bgmv as _bgmv
    else:
        from paddle_tpu.ops.pallas.bgmv import bgmv_xla as _bgmv
    fnb = jax.jit(_bgmv)
    fnb(x, ap, bp, ids).block_until_ready()
    ms = steady_ms(lambda: fnb(x, ap, bp, ids).ravel()[0],
                   iters=5 if quick else 20)
    # bytes the op must move: x + the B gathered adapter rows + out
    by = (B * S * E + B * r * (E + O) + B * S * O) * 4
    log(f"kernels[bgmv]: B={B} r={r} E={E} O={O} {ms:.3f} ms, "
        f"{gbps(by, ms):.1f} GB/s (live={live:.0f})")
    lines += [
        metric_line("kernel_bgmv_ms", ms, "ms", vs_baseline=1.0,
                    kernel_live=live),
        metric_line("kernel_bgmv_gbps", gbps(by, ms), "GB/s",
                    vs_baseline=1.0, kernel_live=live),
    ]

    # -- int8 quantized matmul vs the f32 gemm -----------------------------
    M, K, Nn = (64, 256, 256) if quick else (512, 2048, 2048)
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    w = jnp.asarray((rng.randn(K, Nn) * 0.05).astype(np.float32))
    from paddle_tpu.ops.pallas.quant_matmul import (int8_linear,
                                                    quantize_per_channel)
    w_q, w_s = quantize_per_channel(w)
    live = float(pallas_ops.kernel_enabled("int8_matmul", note=False))
    if live:
        fn8 = jax.jit(lambda a: int8_linear(a, w_q, w_s))
    else:
        # the pre-kernel slim weight-only path: dequantize into the gemm
        fn8 = jax.jit(lambda a: jnp.matmul(
            a, w_q.astype(a.dtype) * w_s.astype(a.dtype)))
    fnf = jax.jit(lambda a: jnp.matmul(a, w))
    fn8(x).block_until_ready()
    fnf(x).block_until_ready()
    ms8 = steady_ms(lambda: fn8(x).ravel()[0], iters=5 if quick else 20)
    msf = steady_ms(lambda: fnf(x).ravel()[0], iters=5 if quick else 20)
    # weight-traffic win: int8 weights + int8 acts + f32 out
    by = M * K + K * Nn + M * Nn * 4
    log(f"kernels[int8_matmul]: [{M}x{K}]@[{K}x{Nn}] int8 {ms8:.2f} ms "
        f"vs f32 {msf:.2f} ms ({msf / ms8:.2f}x, live={live:.0f})")
    lines += [
        metric_line("kernel_int8_matmul_ms", ms8, "ms", vs_baseline=1.0,
                    kernel_live=live, f32_ms=msf),
        metric_line("kernel_int8_matmul_gbps", gbps(by, ms8), "GB/s",
                    vs_baseline=1.0, kernel_live=live),
    ]
    return lines


def bench_moe_dispatch(T: int, D: int, E: int = 8, top_k: int = 2,
                       cf: float = 2.0, tag: str = "",
                       iters: int = 10) -> tuple:
    """MoE dispatch+combine microbench at [T, D], E experts: wall time
    AND compiler-attributed bytes_accessed for BOTH implementations —
    the acceptance evidence that the sort path lowers the dispatch's
    memory traffic vs the einsum oracle (ISSUE 10). Returns
    (metric_lines, sort_ms)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.cost_model import normalize_cost_analysis
    from paddle_tpu.incubate.moe import (einsum_combine, einsum_dispatch,
                                         moe_capacity, sort_combine,
                                         sort_dispatch, topk_routing)

    C = moe_capacity(T, cf, E)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    r = topk_routing(logits, top_k, C)

    def run(mode):
        if mode == "sort":
            fn = lambda a, rr: sort_combine(          # noqa: E731
                sort_dispatch(a, rr, E, C), rr, C)
        else:
            fn = lambda a, rr: einsum_combine(        # noqa: E731
                einsum_dispatch(a, rr, E, C), rr, C)
        jitted = jax.jit(fn)
        lowered = jitted.lower(x, r)
        cost = normalize_cost_analysis(lowered.compile().cost_analysis())
        by = float(cost.get("bytes accessed") or 0.0)
        jitted(x, r).block_until_ready()
        ms = steady_ms(lambda: jitted(x, r).ravel()[0], iters=iters)
        return ms, by

    ms_s, by_s = run("sort")
    ms_e, by_e = run("einsum")
    name = tag or f"{T}x{D}"
    log(f"moe dispatch[{name}]: E={E} k={top_k} C={C} — sort "
        f"{ms_s:.2f} ms / {by_s / 2**20:.1f} MiB accessed vs einsum "
        f"{ms_e:.2f} ms / {by_e / 2**20:.1f} MiB "
        f"({by_e / max(by_s, 1.0):.1f}x less traffic)")
    if by_s and by_e and by_s >= by_e:
        log(f"MOE GATE: sort dispatch bytes_accessed ({by_s:.3e}) did "
            f"NOT improve on einsum ({by_e:.3e}) at E={E} [{name}]")
    lines = [
        metric_line(f"moe_dispatch_sort_ms_{name}", ms_s, "ms",
                    vs_baseline=ms_e / max(ms_s, 1e-9)),
        metric_line(f"moe_dispatch_einsum_ms_{name}", ms_e, "ms",
                    vs_baseline=1.0),
        metric_line(f"moe_dispatch_sort_bytes_{name}", by_s, "bytes",
                    vs_baseline=by_e / max(by_s, 1.0)),
        metric_line(f"moe_dispatch_einsum_bytes_{name}", by_e, "bytes",
                    vs_baseline=1.0),
    ]
    return lines, ms_s


def _bench_moe_gpt(name: str, cfg, B: int, S: int, warm: int, iters: int,
                   repeats: int = 2) -> list:
    """Train-throughput + routing-health record for one MoE GPT config:
    tokens/s/chip from the jitted TrainStep, drop%/balance harvested
    from ONE eager forward's router stats (traced steps cannot publish),
    plus the dispatch microbench at this config's token shape."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       GPTPretrainingCriterion)
    from paddle_tpu.optimizer import AdamW

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(layer, ids, labels):
        with paddle.amp.auto_cast(level="O1"):
            return crit(layer(ids), labels) + layer.moe_loss()

    step = TrainStep(model, loss_fn,
                     AdamW(learning_rate=1e-4,
                           parameters=model.parameters(),
                           weight_decay=0.01))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    t0 = time.perf_counter()
    l0 = float(step(ids, labels))
    compile_s = time.perf_counter() - t0
    log(f"moe[{name}]: compile+step1 {compile_s:.1f}s loss={l0:.3f} "
        f"(E={cfg.moe_experts}, every={cfg.moe_every}, "
        f"{len(cfg.moe_layer_indices())} MoE layers)")
    for _ in range(warm):
        step(ids, labels)
    float(step(ids, labels))
    dt = steady_ms(lambda: step(ids, labels), iters=iters,
                   repeats=repeats) / 1e3
    tok = B * S / dt

    # routing health from one eager forward (same weights, no jit): the
    # scan side outputs are concrete there, so the per-layer router
    # gauges land in the registry for monitor_report --moe
    from paddle_tpu.core.tensor import no_grad
    model.eval()
    with no_grad():
        model(paddle.to_tensor(ids))
    n_pub = model.gpt.publish_moe_telemetry()
    stats = model.gpt.moe_layer_stats()
    arr = np.asarray(stats._data)          # [L_moe, 5+E]
    drop_pct = 100.0 * float(arr[:, 2].mean())
    balance = 100.0 * float(arr[:, 4].mean())
    entropy = float(arr[:, 3].mean())
    log(f"moe[{name}]: {dt * 1e3:.1f} ms/step {tok:,.0f} tok/s — "
        f"drop {drop_pct:.1f}%, balance {balance:.1f}, entropy "
        f"{entropy:.2f} nats over {n_pub} layers")
    dlines, _ = bench_moe_dispatch(
        B * S, cfg.hidden_size, E=cfg.moe_experts, top_k=cfg.moe_top_k,
        cf=cfg.moe_capacity_factor, tag=name,
        iters=max(2, iters))
    return [
        metric_line(f"moe_{name}_tokens_per_sec_per_chip", tok,
                    "tokens/s", vs_baseline=1.0),
        metric_line(f"moe_{name}_drop_pct", drop_pct, "drop%",
                    vs_baseline=1.0),
        metric_line(f"moe_{name}_balance", balance, "balance",
                    vs_baseline=balance / 100.0, entropy=entropy),
    ] + dlines


def bench_moe(quick: bool = False) -> list:
    """``--moe``: the MoE record (BENCH_moe.json) — sort-vs-einsum
    dispatch microbench (ms + cost-model bytes_accessed at E=8), the
    gpt2-tiny-8E smoke and (full runs) the gpt2-345M-8E record:
    tokens/s/chip, dispatch ms, drop % (lower-is-better absolute
    points), balance (higher-is-better absolute points) — all gated by
    tools/check_bench.py. Routing-health gauges land in the registry
    dump for ``tools/monitor_report.py --moe``."""
    from paddle_tpu.models.gpt import gpt2_medium, gpt_tiny

    lines = []
    tiny = gpt_tiny(num_layers=4, moe_experts=8)
    lines += _bench_moe_gpt("gpt2_tiny_8e", tiny, B=8, S=64,
                            warm=2, iters=5 if quick else 10)
    if quick:
        return lines
    # gpt2-345M-8E: MoE FFN every 2nd layer (the GShard/Switch
    # interleave), 8 experts at ffn_size hidden. On the CPU bench
    # container this is the committed floor record (tiny batch, few
    # iters); the TPU driver round re-records at full shapes.
    cfg = gpt2_medium(moe_experts=8, moe_every=2)
    lines += _bench_moe_gpt("gpt2_345m_8e", cfg, B=2, S=512,
                            warm=1, iters=2, repeats=1)
    return lines


def run_moe_mode(quick: bool) -> None:
    """--moe: emit ONLY the MoE metric lines, dump the registry (router
    gauges for monitor_report --moe) and write/self-gate BENCH_moe.json
    (full runs) — same contract as --serve/--kernels."""
    import os
    metrics = bench_moe(quick=quick)
    for m in metrics:
        print(json.dumps(m), flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        from paddle_tpu.monitor import get_registry
        mpath = os.path.join(here, "BENCH_monitor.jsonl")
        get_registry().dump_jsonl(mpath, extra={"source": "bench_moe"})
        log(f"monitor: registry dumped to {mpath} "
            "(render: python tools/monitor_report.py --moe)")
    except Exception as e:
        log(f"monitor dump skipped: {e!r}")
    if quick:
        log("moe: --quick run, BENCH_moe.json not written")
        return
    write_gated_record("BENCH_moe.json", metrics)


def _recsys_dedup_parity(dim: int = 16, tol: float = 1e-6) -> float:
    """Pin the dedup lookup (fwd + sparse grads) against the naive
    per-id gather oracle (FLAGS_recsys_dedup off): same rows, same
    post-push table state. Returns the max abs diff; raises over
    ``tol`` — a record must never commit on a broken lookup."""
    import numpy as np
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.recsys import ShardedEmbeddingTable

    rng = np.random.default_rng(7)
    ids = rng.integers(0, 64, size=256)          # heavy duplication
    grads = rng.normal(size=(ids.size, dim)).astype(np.float32)
    diffs = []
    states = []
    for dedup in (True, False):
        with flag_scope("recsys_dedup", dedup):
            tab = ShardedEmbeddingTable(64, dim, optimizer="adagrad",
                                        lr=0.1, seed=11)
            rows = tab.pull(ids)
            tab.push(ids, grads)
            states.append((rows, tab.state_dict()))
    (r_d, s_d), (r_n, s_n) = states
    diffs.append(float(np.abs(r_d - r_n).max()))
    diffs.append(float(np.abs(s_d["data"] - s_n["data"]).max()))
    diffs.append(float(np.abs(s_d["g2"] - s_n["g2"]).max()))
    worst = max(diffs)
    if worst > tol:
        raise RuntimeError(
            f"recsys dedup parity broken: max diff {worst:.3e} "
            f"(fwd/data/g2 = {diffs})")
    return worst


def bench_recsys(quick: bool = False) -> list:
    """``--recsys``: the giant-embedding DLRM record (BENCH_recsys.json;
    docs/RECSYS.md) — criteo-synthetic DLRM training through
    hot-tier-exceeding tiered tables (examples/s, embedding GB/s
    touched, dedup ratio, per-tier hit rates) plus the online ranking
    leg (deadline-bounded lookups under the recsys serving engine).
    The dedup lookup is parity-pinned against the naive per-id gather
    before any metric is recorded, and the record refuses to commit
    unless the tier spill/promotion counters are nonzero (the table
    must actually exceed its hot budget)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import recsys
    from paddle_tpu.models.dlrm import DLRM, DLRMConfig
    from paddle_tpu.recsys import (CriteoSynthetic, RecsysEngine,
                                   RecsysRequest, RecsysServingConfig,
                                   TieredEmbeddingTable)

    paddle.seed(42)
    worst = _recsys_dedup_parity()
    log(f"recsys: dedup-vs-naive parity max diff {worst:.2e} "
        "(fwd + sparse grads, adagrad state)")
    if quick:
        name = "dlrm_tiny"
        cfg = DLRMConfig(num_dense=4, num_sparse=4, vocab_sizes=4096,
                         embedding_dim=16, bottom_mlp=(32,),
                         top_mlp=(32,))
        B, steps, hot, host = 256, 10, 96, 512
        serve_requests, K = 12, 32
    else:
        name = "dlrm_criteo_small"
        cfg = DLRMConfig(num_dense=13, num_sparse=8,
                         vocab_sizes=200_000, embedding_dim=32,
                         bottom_mlp=(64, 32), top_mlp=(64, 32))
        B, steps, hot, host = 512, 25, 512, 2048
        serve_requests, K = 32, 64
    # tables sized to EXCEED the hot-tier budget (vocab >> hot_rows) and
    # the host cache (host_rows < touched rows on full runs): training
    # must spill and promote, or the tiering claim is untested
    tables = [TieredEmbeddingTable(v, cfg.embedding_dim, hot_rows=hot,
                                   host_rows=host, admit_after=2,
                                   lr=0.05, seed=f, name=f"slot{f}")
              for f, v in enumerate(cfg.vocab_list())]
    for t in tables:
        recsys.register_table(t.name, t)
    model = DLRM(cfg, tables=tables)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    gen = CriteoSynthetic(num_dense=cfg.num_dense,
                          num_sparse=cfg.num_sparse,
                          vocab_sizes=cfg.vocab_sizes, alpha=1.05,
                          batch_size=B, seed=0)

    def train_step(i):
        dense, ids, labels = gen.batch(i)
        loss = model.loss(dense, ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return float(loss)

    first_loss = train_step(0)
    train_step(1)                      # warm the eager op caches
    b0 = sum(t.bytes_pulled + t.bytes_pushed for t in tables)
    t0 = time.perf_counter()
    last_loss = None
    for i in range(2, 2 + steps):
        last_loss = train_step(i)
    dt = max(time.perf_counter() - t0, 1e-9)
    touched = sum(t.bytes_pulled + t.bytes_pushed for t in tables) - b0
    examples_s = B * steps / dt
    mbps = touched / dt / 1e6
    dedup = float(np.mean([t.dedup_ratio for t in tables]))
    agg = {"hbm_hits": 0, "host_hits": 0, "ssd_reads": 0,
           "lazy_inits": 0, "promotions": 0, "demotions": 0}
    for t in tables:
        for k in agg:
            agg[k] += t.stats[k]
        t.publish_tier_metrics()
    total_hits = (agg["hbm_hits"] + agg["host_hits"] + agg["ssd_reads"]
                  + agg["lazy_inits"])
    hbm_pct = 100.0 * agg["hbm_hits"] / max(total_hits, 1)
    host_pct = 100.0 * agg["host_hits"] / max(total_hits, 1)
    if not (agg["promotions"] and agg["demotions"]):
        raise RuntimeError(
            f"recsys: tier spill/promotion counters are zero ({agg}) — "
            "the table did not exceed its hot budget; the tiering leg "
            "measured nothing")
    log(f"recsys[{name}]: {examples_s:.0f} examples/s "
        f"({steps} steps x B={B}, loss {first_loss:.3f} -> "
        f"{last_loss:.3f}), embedding {mbps:.2f} MB/s touched, dedup "
        f"ratio {dedup:.2f}, tier hits hbm {hbm_pct:.1f}% / host "
        f"{host_pct:.1f}% (promotions {agg['promotions']}, demotions "
        f"{agg['demotions']})")

    # online ranking: deadline-bounded lookups through the SAME (now
    # warm) tables under admission control — the serving half
    eng = RecsysEngine(model, RecsysServingConfig(max_batch=4))
    rng = np.random.default_rng(1)
    for _ in range(serve_requests):
        eng.submit(RecsysRequest(
            rng.normal(size=cfg.num_dense).astype(np.float32),
            gen.sample_ids(rng, K), deadline_s=30.0))
    eng.run()
    s = eng.metrics_summary()
    offered = max(s["requests_submitted"] + s["requests_rejected"], 1)
    avail = 100.0 * s["requests_completed"] / offered
    lookup_p99_ms = (s["lookup_p99_s"] or 0.0) * 1e3
    log(f"recsys[serve]: {s['requests_completed']}/{offered} ranked, "
        f"{s['candidates_per_sec']:.0f} candidates/s, lookup p99 "
        f"{lookup_p99_ms:.2f} ms, e2e p99 {(s['e2e_p99_s'] or 0)*1e3:.1f}"
        " ms")
    recsys.publish_table_hbm()
    return [
        metric_line(f"recsys_{name}_examples_per_sec", examples_s,
                    "examples/s", vs_baseline=1.0),
        metric_line(f"recsys_{name}_embedding_mbps", mbps, "MB/s",
                    vs_baseline=1.0),
        metric_line(f"recsys_{name}_dedup_ratio", dedup, "ratio",
                    vs_baseline=1.0),
        # hit% gates on ABSOLUTE points, higher-is-better (check_bench):
        # a tier-hit-rate collapse is a perf cliff even when examples/s
        # survives on a fast host
        metric_line("recsys_tier_hit_hbm_pct", hbm_pct, "hit%",
                    vs_baseline=1.0),
        metric_line("recsys_tier_hit_host_pct", host_pct, "hit%",
                    vs_baseline=1.0),
        metric_line("recsys_serve_candidates_per_sec",
                    s["candidates_per_sec"] or 0.0, "examples/s",
                    vs_baseline=1.0),
        metric_line("recsys_serve_lookup_p99_ms", lookup_p99_ms, "ms",
                    vs_baseline=1.0),
        metric_line("recsys_serve_availability_pct", avail, "%",
                    vs_baseline=1.0),
    ]


def run_recsys_mode(quick: bool) -> None:
    """--recsys: emit ONLY the recsys metric lines, dump the registry
    (tier hit/occupancy gauges for monitor_report --recsys) and
    write/self-gate BENCH_recsys.json (full runs) — the --moe/--serve
    contract."""
    import os
    metrics = bench_recsys(quick=quick)
    for m in metrics:
        print(json.dumps(m), flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        from paddle_tpu.monitor import get_registry
        mpath = os.path.join(here, "BENCH_monitor.jsonl")
        get_registry().dump_jsonl(mpath, extra={"source": "bench_recsys"})
        log(f"monitor: registry dumped to {mpath} "
            "(render: python tools/monitor_report.py --recsys)")
    except Exception as e:
        log(f"monitor dump skipped: {e!r}")
    if quick:
        log("recsys: --quick run, BENCH_recsys.json not written")
        return
    write_gated_record("BENCH_recsys.json", metrics)


def bench_multichip(quick: bool = False) -> list:
    """``--multichip``: the DP×TP×PP record on an 8-device VIRTUAL mesh
    (docs/PARALLELISM.md methodology) — weak-scaling efficiency across
    mesh shapes, plus 1F1B schedule quality (bubble fraction measured
    from the implemented timetable, exposed-comm fraction) and the
    per-op comm_overlap_ms gauges tools/monitor_report.py --comms
    renders. Writes/self-gates BENCH_multichip.json.

    Weak scaling on a virtual mesh: all N device programs share the host
    cores, so the single-device run of the SAME global batch is the
    zero-overhead reference — eff = t_single / t_mesh isolates the
    partitioning + schedule + collective overhead that becomes the
    weak-scaling loss on a real mesh (where t_single(N·B) ≈ N·t(B), the
    textbook T(1,B)/T(N,N·B)). Model is the GPT-2 architecture at test
    scale (gpt_tiny, 8 layers) so records stay comparable across rounds
    on the CPU container; mesh shapes follow ISSUE 9: dp8 (8×1×1),
    dp2×mp2×pp2, mp2×pp4, and the pp-only 1F1B legs XLA:CPU can run the
    real schedule on (pp2/pp4 over a device prefix)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import env as dist_env, fleet
    from paddle_tpu.distributed.meta_parallel.spmd_pipeline import (
        bubble_fraction, pipeline_comm_model, schedule_timetable)
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.models.gpt import GPTForPretrainingPipe, gpt_tiny
    from paddle_tpu.optimizer import AdamW

    B, S, M = (8, 32, 4) if quick else (16, 64, 4)
    iters = 3 if quick else 10
    cfg = gpt_tiny(num_layers=8)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    mask = np.ones((B, S), np.float32)

    def run_shape(dp, mp, pp, schedule):
        """Steady ms/step of the full train step (fwd+bwd+AdamW through
        pretraining_loss) on a dp×mp×pp mesh; dp=mp=pp=0 = the
        single-device reference on the same global batch."""
        fleet.reset()
        dist_env.reset()
        if dp:
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": dp, "pp_degree": pp,
                                       "mp_degree": mp}
            fleet.init(is_collective=True, strategy=strategy)
            mesh = fleet.get_hybrid_communicate_group().mesh
        else:
            mesh = None
        paddle.seed(7)
        model = GPTForPretrainingPipe(cfg, num_microbatches=M,
                                      schedule=schedule)
        if mesh is not None:
            model = fleet.distributed_model(model)
        opt = AdamW(learning_rate=1e-3, weight_decay=0.01)

        def loss_fn(layer, i, l, m):
            base = layer._layers if hasattr(layer, "_layers") else layer
            return base.pretraining_loss(i, l, m)

        kw = dict(mesh=mesh, data_spec=P("dp")) if mesh is not None else {}
        step = TrainStep(model, loss_fn, opt, **kw)
        args = (Tensor(ids), Tensor(labels), Tensor(mask))
        t0 = time.perf_counter()
        l0 = float(np.asarray(step(*args)._data))
        compile_s = time.perf_counter() - t0
        step(*args)
        ms = steady_ms(lambda: step(*args), iters=iters, repeats=2)
        return ms, compile_s, l0, mesh

    log(f"multichip: gpt2-arch tiny (L={cfg.num_layers}, "
        f"H={cfg.hidden_size}) B={B} S={S} M={M} on "
        f"{len(jax.devices())} virtual devices")
    t_single, c_s, l_single, _ = run_shape(0, 0, 0, None)
    log(f"multichip[single]: {t_single:.1f} ms/step "
        f"(compile {c_s:.1f}s, loss={l_single:.4f})")

    shapes = [
        ("dp8", 8, 1, 1, "fill_drain"),
        ("dp2mp2pp2", 2, 2, 2, "fill_drain"),
        ("mp2pp4", 1, 2, 4, "fill_drain"),
        ("pp2_1f1b", 1, 1, 2, "1f1b"),
        ("pp4_1f1b", 1, 1, 4, "1f1b"),
    ]
    lines, gates = [], []
    reg = None
    try:
        from paddle_tpu.monitor import get_registry
        reg = get_registry()
    except Exception as e:
        log(f"multichip: registry unavailable ({e!r})")

    for name, dp, mp, pp, sched in shapes:
        t_mesh, c_s, l_mesh, mesh = run_shape(dp, mp, pp, sched)
        eff = 100.0 * t_single / t_mesh if t_mesh > 0 else 0.0
        d_loss = abs(l_mesh - l_single)
        log(f"multichip[{name}]: {t_mesh:.1f} ms/step, weak-scaling eff "
            f"{eff:.1f}% (compile {c_s:.1f}s, loss Δ={d_loss:.2e} vs "
            f"single-device)")
        if d_loss > 2e-3 * max(abs(l_single), 1e-6):
            gates.append(f"{name}: loss parity broken "
                         f"(Δ={d_loss:.2e} vs single-device)")
        if eff < 85.0:
            # the ≥85% acceptance bar is the 1F1B pipeline legs; the
            # other shapes are diagnostic (tiny per-device work makes
            # partitioning overhead loom large at test scale) and gate
            # cross-round via check_bench's weak% unit instead
            if "1f1b" in sched:
                gates.append(f"{name}: weak-scaling eff {eff:.1f}% < 85%")
            else:
                log(f"multichip note: {name} below the 85% target "
                    "(diagnostic shape; gated round-over-round only)")
        lines.append(metric_line(f"multichip_weak_scaling_eff_{name}",
                                 eff, "weak%", vs_baseline=eff / 85.0))
        if "1f1b" not in sched or pp < 2:
            continue

        # schedule quality: bubble measured from the IMPLEMENTED
        # timetable predicates (schedule_timetable replays the traced
        # branch conditions) vs the canonical closed form + 5pts
        tt = schedule_timetable("1f1b", pp, M)
        bubble = 100.0 * tt["bubble_fraction"]
        bound = 100.0 * bubble_fraction("1f1b", pp, M) + 5.0
        if bubble > bound:
            gates.append(f"{name}: bubble {bubble:.1f}% > canonical+5pts "
                         f"({bound:.1f}%)")
        exposed_pct = max(0.0, 100.0 - eff)
        log(f"multichip[{name}]: bubble {bubble:.1f}% "
            f"(canonical bound {bound:.1f}%), exposed-comm "
            f"{exposed_pct:.1f}% of step")
        lines.append(metric_line(f"multichip_{name}_bubble_pct", bubble,
                                 "bubble%", vs_baseline=1.0))
        lines.append(metric_line(f"multichip_{name}_exposed_comm_pct",
                                 exposed_pct, "exposed%",
                                 vs_baseline=1.0))

        # per-op overlap gauges (monitor_report --comms): serial = the
        # schedule's per-step ppermute traffic dispatched back-to-back
        # eagerly, exposed = the measured step-time residual vs the
        # single-device reference, overlapped = what XLA's async
        # scheduling hid
        if reg is None:
            continue
        try:
            mb = B // M
            boundary = jnp.zeros((mb, S, cfg.hidden_size), jnp.float32)
            perm = [(i, i + 1) for i in range(pp - 1)]
            pfn = jax.jit(dist_env.shard_map(
                lambda h: jax.lax.ppermute(h, "pp", perm), mesh=mesh,
                in_specs=P(), out_specs=P(), axis_names={"pp"},
                check_vma=False))
            pfn(boundary).block_until_ready()
            one_ms = steady_ms(lambda: pfn(boundary).ravel()[0],
                               iters=iters, repeats=2)
            model_ops = pipeline_comm_model(
                "1f1b", pp, M, int(boundary.nbytes))["ops"]
            serial_ms = one_ms * model_ops / 2.0   # perm pair per slot
            exposed_ms = max(0.0, t_mesh - t_single)
            overlapped_ms = max(0.0, serial_ms - exposed_ms)
            g = reg.gauge(
                "comm_overlap_ms",
                "per-op comm time of a pipelined step: serial = "
                "back-to-back eager dispatch of the schedule's traffic, "
                "exposed = measured step residual, overlapped = hidden "
                "by async scheduling (bench.py --multichip)")
            for phase, v in (("serial", serial_ms),
                             ("exposed", exposed_ms),
                             ("overlapped", overlapped_ms)):
                g.set(v, op="ppermute", mesh=name, schedule="1f1b",
                      phase=phase)
            log(f"multichip[{name}]: ppermute serial {serial_ms:.2f} ms "
                f"vs exposed {exposed_ms:.2f} ms "
                f"({overlapped_ms:.2f} ms hidden)")
        except Exception as e:
            log(f"multichip[{name}]: overlap gauges skipped: {e!r}")

    # -- expert-parallel leg (ISSUE 10): MoE GPT over an ep-only mesh,
    # the only shape whose manual-ep all_to_alls XLA:CPU can compile —
    # weak-scaling eff + the all_to_all overlap gauges ------------------
    try:
        lines += _multichip_moe_ep_leg(B, S, iters, reg)
    except Exception as e:
        leg_failed("multichip[ep8_moe] leg", e)
        gates.append(f"ep8_moe: leg failed ({e!r})")

    for gname in gates:
        log("MULTICHIP GATE: " + gname)
    if not gates:
        log("multichip gate ok: all shapes ≥ 85% weak-scaling eff, "
            "1F1B bubble within canonical+5pts, loss parity held")
    return lines


def _multichip_moe_ep_leg(B: int, S: int, iters: int, reg) -> list:
    """The ``ep8_moe`` leg: gpt2-arch tiny with 8 experts in EVERY layer
    (homogeneous MoE stack, scan-over-layers) trained over an ep-only
    8-device mesh — the explicit shard_map + all_to_all expert-parallel
    program. Measures weak-scaling eff vs the SAME model single-device,
    and publishes ``comm_overlap_ms{op=all_to_all}`` gauges: serial =
    the model's per-step all_to_all traffic dispatched back-to-back
    through the EAGER collective (which also lands the measured
    baseline in the comm_latency series the PR 9 relabel created),
    exposed = the step-time residual, overlapped = hidden."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import collective as coll, env as dist_env, fleet
    from paddle_tpu.distributed.spmd import make_mesh
    from paddle_tpu.incubate.moe import MOE_STATS, reset_moe_stats
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       GPTPretrainingCriterion, gpt_tiny)
    from paddle_tpu.optimizer import AdamW

    cfg = gpt_tiny(num_layers=4, moe_experts=8)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    crit = GPTPretrainingCriterion()

    def loss_fn(layer, i, l):
        return crit(layer(i), l) + layer.moe_loss()

    def run(mesh):
        fleet.reset()
        dist_env.reset()
        if mesh is not None:
            dist_env.set_mesh(mesh)
        paddle.seed(7)
        model = GPTForPretraining(cfg)
        kw = dict(mesh=mesh, data_spec=P("ep")) if mesh is not None else {}
        step = TrainStep(model, loss_fn,
                         AdamW(learning_rate=1e-3,
                               parameters=model.parameters()), **kw)
        args = (Tensor(ids), Tensor(labels))
        t0 = time.perf_counter()
        l0 = float(np.asarray(step(*args)._data))
        compile_s = time.perf_counter() - t0
        step(*args)
        ms = steady_ms(lambda: step(*args), iters=iters, repeats=2)
        return ms, compile_s, l0

    t_single, c_s, l_single = run(None)
    log(f"multichip[ep8_moe single]: {t_single:.1f} ms/step "
        f"(compile {c_s:.1f}s, loss={l_single:.4f})")
    reset_moe_stats()
    mesh = make_mesh({"ep": 8})
    t_mesh, c_s, l_mesh = run(mesh)
    eff = 100.0 * t_single / t_mesh if t_mesh > 0 else 0.0
    d_loss = abs(l_mesh - l_single)
    log(f"multichip[ep8_moe]: {t_mesh:.1f} ms/step, weak-scaling eff "
        f"{eff:.1f}% (compile {c_s:.1f}s, loss Δ={d_loss:.2e} vs "
        f"single-device — per-shard aux-loss semantics), "
        f"ep_dispatches={MOE_STATS['ep_dispatches']} "
        f"fallbacks={MOE_STATS['fallbacks']}")
    lines = [metric_line("multichip_weak_scaling_eff_ep8_moe", eff,
                         "weak%", vs_baseline=eff / 85.0)]
    exposed_pct = max(0.0, 100.0 - eff)
    lines.append(metric_line("multichip_ep8_moe_exposed_comm_pct",
                             exposed_pct, "exposed%", vs_baseline=1.0))

    # all_to_all overlap gauges: serial = eager all_to_all dispatches of
    # the model's per-step exchange traffic (2 directions x chunks x
    # MoE layers), measured through distributed.alltoall so the
    # comm_latency_seconds{op=all_to_all} baseline series populates too
    from paddle_tpu.incubate.moe import moe_capacity, resolve_a2a_chunks
    n = 8
    E, D = cfg.moe_experts, cfg.hidden_size
    C_loc = moe_capacity(B * S // n, cfg.moe_capacity_factor, E)
    # the ONE chunk-resolution rule _ep_program executes, so the serial
    # baseline counts the exchanges the model really issues
    chunks = resolve_a2a_chunks(C_loc)
    cs = C_loc // chunks
    # one exchange moves [E, cs, D] per shard = stacked [n, n, ...] blocks
    rows = max(1, (E // n) * cs)
    block = jnp.zeros((n, n, rows, D), jnp.float32)
    g = coll.get_group(0)
    coll.alltoall(block, group=g)              # build/warm the wrapper
    one_ms = steady_ms(
        lambda: coll.alltoall(block, group=g)[0].ravel()[0],
        iters=iters, repeats=2)
    # per OPTIMIZER step: 2 forward exchanges per chunk per MoE layer,
    # and the backward re-issues each one (an all_to_all's transpose is
    # an all_to_all) — 4 x chunks x layers total
    a2a_per_step = 4 * chunks * len(cfg.moe_layer_indices())
    serial_ms = one_ms * a2a_per_step
    exposed_ms = max(0.0, t_mesh - t_single)
    overlapped_ms = max(0.0, serial_ms - exposed_ms)
    if reg is not None:
        try:
            gz = reg.gauge(
                "comm_overlap_ms",
                "per-op comm time of a pipelined step: serial = "
                "back-to-back eager dispatch of the schedule's traffic, "
                "exposed = measured step residual, overlapped = hidden "
                "by async scheduling (bench.py --multichip)")
            for phase, v in (("serial", serial_ms),
                             ("exposed", exposed_ms),
                             ("overlapped", overlapped_ms)):
                gz.set(v, op="all_to_all", mesh="ep8_moe", schedule="moe",
                       phase=phase)
        except Exception as e:
            log(f"multichip[ep8_moe]: overlap gauges skipped: {e!r}")
    log(f"multichip[ep8_moe]: all_to_all serial {serial_ms:.2f} ms "
        f"({a2a_per_step} exchanges/step @ {one_ms:.3f} ms eager) vs "
        f"exposed {exposed_ms:.2f} ms ({overlapped_ms:.2f} ms hidden)")
    fleet.reset()
    dist_env.reset()
    return lines


def run_multichip_mode(quick: bool) -> None:
    """--multichip: needs the 8-device virtual CPU mesh; re-exec into a
    correctly-flagged subprocess when this process already initialized a
    different backend (e.g. a single real TPU chip)."""
    import os
    import subprocess

    import jax
    if len(jax.devices()) < 8 or jax.default_backend() != "cpu":
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        env["JAX_PLATFORMS"] = "cpu"
        log("multichip: re-exec on an 8-device virtual CPU mesh")
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--multichip"]
            + (["--quick"] if quick else []), env=env).returncode
        sys.exit(rc)
    metrics = bench_multichip(quick=quick)
    for m in metrics:
        print(json.dumps(m), flush=True)
    try:
        from paddle_tpu.monitor import get_registry
        mpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_monitor.jsonl")
        get_registry().dump_jsonl(mpath, extra={"source": "bench_multichip"})
        log(f"monitor: registry dumped to {mpath} "
            "(render: python tools/monitor_report.py --comms)")
    except Exception as e:
        log(f"monitor dump skipped: {e!r}")
    if quick:
        log("multichip: --quick run, BENCH_multichip.json not written")
        return
    write_gated_record("BENCH_multichip.json", metrics)


def write_gated_record(rec_name: str, metrics: list) -> None:
    """Write/self-gate a standalone bench record (BENCH_serve.json,
    BENCH_kernels.json): gate the fresh metrics against the existing
    record, park it at ``.prev`` — EVEN when the gate errored (corrupt
    record, import error): a regressed or broken run must never silently
    become the next baseline — then write the fresh record."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    rec = os.path.join(here, rec_name)
    tag = rec_name.rsplit(".", 1)[0]
    try:
        sys.path.insert(0, os.path.join(here, "tools"))
        import check_bench
        if os.path.exists(rec):
            with open(rec) as f:
                old = check_bench._metric_list(json.load(f))
            for p in check_bench.compare_common(old, metrics):
                log(f"{tag} GATE: " + p)
    except Exception as e:
        log(f"{tag} gate skipped: {e!r}")
    try:
        if os.path.exists(rec):
            os.replace(rec, rec + ".prev")
    except OSError as e:
        log(f"could not park previous record: {e!r}")
    with open(rec, "w") as f:
        json.dump(metrics, f, indent=1)
    log(f"{tag}: record written to {rec} "
        f"(gate: python tools/check_bench.py {rec_name}.prev {rec_name})")


def run_kernels_mode(quick: bool) -> None:
    """--kernels: emit ONLY the kernel metric lines (one JSON per line)
    and write/self-gate the BENCH_kernels.json record (full runs),
    parking the previous record at .prev — same contract as --serve."""
    metrics = bench_kernels(quick=quick)
    for m in metrics:
        print(json.dumps(m), flush=True)
    if quick:
        log("kernels: --quick run, BENCH_kernels.json not written")
        return
    write_gated_record("BENCH_kernels.json", metrics)


def run_serve_mode(quick: bool) -> None:
    """--serve: emit ONLY the serving metric lines (one JSON per line),
    write/self-gate the BENCH_serve.json record (full runs), and dump
    the monitor registry (per-request latency histograms, queue gauges —
    tools/monitor_report.py --serve renders it)."""
    import os
    metrics = bench_serve(quick=quick)
    for m in metrics:
        print(json.dumps(m), flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        from paddle_tpu.monitor import get_registry
        mpath = os.path.join(here, "BENCH_monitor.jsonl")
        get_registry().dump_jsonl(mpath, extra={"source": "bench_serve"})
        log(f"monitor: registry dumped to {mpath} "
            "(render: python tools/monitor_report.py --serve)")
    except Exception as e:
        log(f"monitor dump skipped: {e!r}")
    if quick:
        log("serve: --quick run, BENCH_serve.json not written")
        return
    write_gated_record("BENCH_serve.json", metrics)


def bench_train_goodput(quick: bool) -> list:
    """--train: goodput-ledger + model-health overhead on a small MLP
    TrainStep. Warm step time with the ledger on and health telemetry
    OFF vs ``FLAGS_train_health_every=1`` (per-layer grad/param/update
    side-outputs compiled INTO the step program — the contract is that
    the cost is compiled arithmetic, not extra dispatches), gated as
    absolute points. Also emits the run's ``train_goodput_pct`` under
    the higher-is-better ``goodput%`` unit so a leak of wall-clock into
    a badput bucket trips check_bench even when step time survives."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.monitor import goodput as goodput_mod

    iters = 10 if quick else 40
    paddle.set_flags({"train_goodput": True, "train_health_every": 0})
    paddle.seed(7)
    model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                          nn.Linear(128, 64), nn.ReLU(),
                          nn.Linear(64, 8))
    step = TrainStep(model, lambda l, a, b: F.cross_entropy(l(a), b),
                     paddle.optimizer.Adam(
                         learning_rate=1e-3,
                         parameters=model.parameters()))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 64)).astype(np.float32)
    y = rng.integers(0, 8, (32,)).astype(np.int64)

    float(step(x, y))                    # compile + step 1
    for _ in range(3):
        loss = step(x, y)
    float(loss)
    ms_off = steady_ms(lambda: step(x, y), iters=iters)

    # health at every step is the worst-case telemetry load; production
    # cadence (every-N) can only cost less readback, same program
    paddle.set_flags({"train_health_every": 1})
    float(step(x, y))                    # health program compile
    for _ in range(3):
        loss = step(x, y)
    float(loss)
    ms_on = steady_ms(lambda: step(x, y), iters=iters)
    paddle.set_flags({"train_health_every": 0})

    overhead = max(0.0, (ms_on - ms_off) / ms_off * 100.0)
    led = goodput_mod.active_ledger()
    snap = led.snapshot() if led is not None else {}
    gp = float(snap.get("goodput_pct", 0.0))
    log(f"train: warm step health-off {ms_off:.3f} ms, health-every-1 "
        f"{ms_on:.3f} ms -> overhead {overhead:.1f} points "
        f"(goodput {gp:.1f}% of {snap.get('elapsed_s', 0.0):.1f}s)")
    for b, s in sorted((snap.get("buckets") or {}).items(),
                       key=lambda kv: -kv[1]):
        if s:
            log(f"train:   {b:<20} {s:8.2f}s")
    return [metric_line("train_goodput_pct", gp, "goodput%",
                        vs_baseline=1.0),
            metric_line("train_goodput_overhead_pct", overhead,
                        "overhead%", vs_baseline=1.0,
                        ms_off=ms_off, ms_on=ms_on)]


def run_train_mode(quick: bool) -> None:
    """--train: emit ONLY the goodput metric lines (one JSON per line),
    write/self-gate the BENCH_train.json record (full runs), and dump
    the monitor registry (goodput gauge/badput counters + per-layer
    health gauges — tools/monitor_report.py --goodput renders it) —
    same contract as --serve."""
    import os
    metrics = bench_train_goodput(quick=quick)
    for m in metrics:
        print(json.dumps(m), flush=True)
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        from paddle_tpu.monitor import get_registry
        mpath = os.path.join(here, "BENCH_monitor.jsonl")
        get_registry().dump_jsonl(mpath, extra={"source": "bench_train"})
        log(f"monitor: registry dumped to {mpath} "
            "(render: python tools/monitor_report.py --goodput)")
    except Exception as e:
        log(f"monitor dump skipped: {e!r}")
    if quick:
        log("train: --quick run, BENCH_train.json not written")
        return
    write_gated_record("BENCH_train.json", metrics)


def main() -> None:
    _run()
    if FAILED_LEGS:
        log(f"{len(FAILED_LEGS)} leg(s) failed:")
        for leg in FAILED_LEGS:
            log("  " + leg)
        sys.exit(1)


def _run() -> None:
    import jax
    # rbg keys: dropout mask generation is ~10x cheaper than threefry on
    # TPU and BERT training draws masks for every layer every step
    jax.config.update("jax_default_prng_impl", "rbg")

    import paddle_tpu as paddle
    # all benches measure the production policy: bf16 MXU, f32 accumulate
    paddle.set_flags({"tpu_matmul_precision": "default"})
    # telemetry on for the whole run: TrainStep step timings + compile/
    # recompile counters land in the monitor registry, dumped as JSONL
    # next to the BENCH_*.json records at the end (registry writes are
    # host-side dict updates — noise floor, not a timed-loop distortion)
    paddle.set_flags({"monitor": True})
    log(f"devices: {jax.devices()}")
    log(f"compilation cache: {jax.config.jax_compilation_cache_dir} "
        "(compile+step1 timings below collapse on warm runs)")
    if "--chaos" in sys.argv:
        # deterministic fault injection for recovery drills: e.g.
        #   bench.py --quick --chaos grad.nonfinite@3
        # (site spec grammar: paddle_tpu/testing/chaos.py; fires land in
        # the flight-recorder recovery timeline)
        from paddle_tpu.core.flags import get_flag
        from paddle_tpu.testing import chaos
        i = sys.argv.index("--chaos")
        spec = sys.argv[i + 1] if i + 1 < len(sys.argv) else ""
        if not spec or spec.startswith("-"):
            sys.exit("--chaos needs a spec: site[@N|:p][*k][,...] — "
                     "sites: " + ", ".join(sorted(chaos.SITES)))
        seed = int(get_flag("chaos_seed"))
        chaos.configure(spec, seed=seed)
        paddle.set_flags({"flight_recorder": True})
        log(f"chaos armed: {spec} (seed={seed}; flight recorder on)")
    full = "--quick" not in sys.argv
    if "--serve" in sys.argv:
        # serving bench is its own record (BENCH_serve): the training
        # metric lines and the last-line-headline contract stay untouched
        run_serve_mode(quick=not full)
        return
    if "--kernels" in sys.argv:
        # kernel microbench is its own record too (BENCH_kernels)
        run_kernels_mode(quick=not full)
        return
    if "--multichip" in sys.argv:
        # DP×TP×PP weak-scaling / schedule-quality record
        # (BENCH_multichip) on the 8-device virtual mesh
        run_multichip_mode(quick=not full)
        return
    if "--moe" in sys.argv:
        # MoE dispatch + gpt-8E record (BENCH_moe)
        run_moe_mode(quick=not full)
        return
    if "--recsys" in sys.argv:
        # giant-embedding DLRM training + online ranking record
        # (BENCH_recsys)
        run_recsys_mode(quick=not full)
        return
    if "--train" in sys.argv:
        # training goodput ledger + model-health overhead record
        # (BENCH_train)
        run_train_mode(quick=not full)
        return
    metrics = []

    def add(result):
        """Benches return one metric line, a list (throughput +
        compile_step1), or None (failed diagnostic leg)."""
        if isinstance(result, list):
            metrics.extend(m for m in result if m is not None)
        elif result is not None:
            metrics.append(result)

    if full:
        bench_eager_dispatch()
        add(bench_lenet_eager())
        add(bench_resnet50())
        add(bench_gpt2_345m())
        bench_gpt2_pp_tp()
        add(bench_ernie())
    r = bench_bert_mlm()
    # compile + HBM lines BEFORE the throughput line: the headline (BERT
    # tokens/s) metric must stay the LAST printed JSON line for
    # last-line parsers
    if r.get("hbm_line"):
        metrics.append(r["hbm_line"])
    metrics.append(metric_line(
        "bert_base_mlm_compile_step1_s", r["compile_s"], "s",
        vs_baseline=1.0, mfu=r["mfu"]))
    metrics.append(metric_line(
        "bert_base_mlm_tokens_per_sec_per_chip", r["tokens_per_sec"],
        "tokens/s", vs_baseline=r["mfu"] / CUDA_PARITY_MFU, mfu=r["mfu"]))
    # one JSON line per BASELINE config; the headline (BERT) line LAST so
    # a last-line parser still sees the north-star metric.
    # tools/check_bench.py gates these against the previous round's record.
    for m in metrics:
        if m is not None:
            print(json.dumps(m), flush=True)

    # metrics-registry dump NEXT TO the BENCH_*.json records: perf numbers
    # now travel with their recompile counts, cache hit rates, step-time
    # histograms and comms counters (tools/monitor_report.py renders it).
    # File output only — stdout keeps its one-JSON-line-per-metric
    # contract, so check_bench.compare_common gating is unaffected.
    try:
        import os as _os
        from paddle_tpu.monitor import get_registry
        from paddle_tpu.monitor.memory import publish_census
        from paddle_tpu.utils.compilation import publish_compile_counts
        publish_compile_counts()
        publish_census()      # live-buffer bytes by category, for the
        # tools/monitor_report.py --memory section
        mpath = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                              "BENCH_monitor.jsonl")
        get_registry().dump_jsonl(mpath, extra={"source": "bench"})
        log(f"monitor: registry dumped to {mpath} "
            "(render: python tools/monitor_report.py)")
    except Exception as e:                       # telemetry must never
        log(f"monitor dump skipped: {e!r}")      # sink the metrics

    # self-gate against the newest driver record so a regression is
    # visible in this run's own log (the CLI gate remains for CI use)
    try:
        import glob
        import os
        recs = sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r*.json")))
        if recs:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            import check_bench
            with open(recs[-1]) as f:
                old = check_bench._metric_list(json.load(f))
            # intersection-only: a --quick run (or a failed diagnostic
            # leg) intentionally skips benchmarks — those must not log as
            # "metric disappeared" regressions in the self-gate
            problems = check_bench.compare_common(
                old, [m for m in metrics if m is not None])
            for p in problems:
                log("BENCH GATE vs " + os.path.basename(recs[-1]) + ": "
                    + p)
            if old and not problems:
                log(f"bench gate ok vs {os.path.basename(recs[-1])}: "
                    "no metric regressed beyond 10%")
    except Exception as e:                       # the gate must never sink
        log(f"bench gate skipped: {e!r}")        # the metrics themselves


if __name__ == "__main__":
    main()
