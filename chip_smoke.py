#!/usr/bin/env python3
"""chip_smoke.py — does paddle_tpu still start on the chip?

The device and kernel check, at the shapes GPT-2 345M (16 heads of 64,
vocab 50304) trains and serves with:

  device   jax sees a TPU; versions, device kind, compile cache in force
  kernels  each main-path Pallas kernel COMPILED (never interpreted)
           against its XLA reference: flash attention forward and
           backward in bf16 and the f32 forward, chunked cross-entropy,
           paged decode (a K/V head a query head; 16 query heads a K/V
           head under a window; 32 absorbed queries over ONE latent pool)

That a TrainStep, a ServingEngine or a four-chip mesh still runs on the
chip, and still agrees with a plain reference, is what the benchmark's
cells show (`python3 benchmark/run.py --workload <cell>`, BENCHMARK.json).

Each phase is fatal: an exception or a failed check prints a traceback
and exits non-zero with no result line. Only a run that passed every
phase on a TPU prints, as the LAST line of stdout,

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`--rehearse` proves paths, arguments and control flow in a sandbox with
no chip: small shapes on the CPU with the kernels interpreted. It skips
what only a chip can show and NEVER prints the result line.

One process: nothing here starts another that needs the chip. Wall and
compile seconds and HBM peaks are printed per phase as set-up facts, not
as metrics.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

SEED = 0

#: the whole script must end inside the driver's 1200 s; a phase that
#: hangs is killed with every thread's traceback instead of holding the
#: chip
DEADLINE_S = 1150

# -- the kernels' shapes: what a 345M train step and a prefill hand them --
REAL = dict(
    flash_train=(8, 1024, 16, 64), flash_prefill=(4, 256, 16, 64),
    ce_logits=(8192, 50304), ce_chunk=8192,
    paged=dict(slots=8, heads=16, head_dim=64, block=16, blocks=32),
    # command_a_plus_ep8's window layers: 128 query heads on 8 K/V heads,
    # a window of 4,096 over a table of 28,672 positions, 385 pages a slot
    paged_window=dict(slots=24, heads=128, kv_heads=8, head_dim=128,
                      block=16, blocks=1792, window=4096),
    # xing4_29b_ep1's decode step: 64 slots, 32 heads' absorbed queries of
    # 512 + 64 in rows of 640 lanes, a table of 18,432 positions; the
    # longest slot here 8,192 (the float32 reference gathers every one)
    paged_latent=dict(slots=64, heads=32, rank=512, rope=64, width=640,
                      block=16, blocks=1152, longest=8192),
)
REHEARSE = dict(
    flash_train=(1, 256, 2, 64), flash_prefill=(1, 256, 2, 64),
    ce_logits=(16, 384), ce_chunk=128,
    paged=dict(slots=2, heads=2, head_dim=8, block=4, blocks=4),
    paged_window=dict(slots=3, heads=4, kv_heads=2, head_dim=8, block=4,
                      blocks=16, window=8),
    paged_latent=dict(slots=3, heads=4, rank=16, rope=4, width=20, block=4,
                      blocks=8, longest=24),
)

# -- tolerances, per dtype ---------------------------------------------------
# Errors are max|got - ref| / max|ref| against a float32 reference at
# highest matmul precision. bf16 has 8 bits of mantissa (eps 7.8e-3): the
# bf16 cases carry the rounding of their inputs and of their outputs, a
# few eps — no kernel rounds a tile in between (flash and paged decode
# multiply bf16 operands in one exact pass and send their f32 probability
# and ds tiles through as three bf16 parts); the f32 kernels differ from
# XLA only in the order of f32 sums and the exp implementation.
TOL = {"bfloat16": 4e-2, "float32": 2e-3}
#: chunked-CE loss: both sides accumulate in f32 from the same logits
CE_LOSS_TOL = 1e-4


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# bookkeeping: compile seconds, persistent-cache hits, HBM
# ---------------------------------------------------------------------------

class Ledger:
    """Listens to jax's monitoring events for the whole process."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snap(self):
        return (self.compile_s, self.cache_hits, self.cache_misses)


def hbm(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        if "bytes_in_use" not in st:
            return "hbm n/a"
        parts.append(f"{st['bytes_in_use'] / 2**30:.2f}/"
                     f"{st.get('peak_bytes_in_use', 0) / 2**30:.2f}")
    return "hbm GiB in-use/peak-so-far " + " ".join(parts)


def run_phase(name: str, fn, ledger: Ledger, devices) -> None:
    import jax
    say(f"[{name}] start")
    t0, s0 = time.perf_counter(), ledger.snap()
    fn()
    # everything enqueued has run before the phase is called good
    jax.effects_barrier()
    s1 = ledger.snap()
    say(f"[{name}] ok wall {time.perf_counter() - t0:.1f}s "
        f"compile {s1[0] - s0[0]:.1f}s "
        f"cache hits {s1[1] - s0[1]} misses {s1[2] - s0[2]} "
        f"{hbm(devices)}")


def rel_err(got, ref) -> float:
    """max|got - ref| / max|ref| in f32, reduced on the device."""
    import jax.numpy as jnp
    g, r = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(g - r)) / (jnp.max(jnp.abs(r)) + 1e-12))


def kernel_names(text: str) -> set:
    """The package's named pallas_calls present in a compiled program."""
    check("tpu_custom_call" in text, "no tpu_custom_call in the program")
    names = ("flash_fwd", "flash_bwd", "chunked_ce_lse",
             "chunked_ce_dlogits", "paged_decode", "paged_mla_decode")
    return {n for n in names if n in text}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(args):
    import importlib.metadata as md

    import jax
    import jaxlib
    dev = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}")
    say(f"device platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(jax.devices())}")
    say(f"compile cache: {jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    if args.rehearse:
        return
    check(dev.platform == "tpu",
          f"no accelerator: jax reports platform {dev.platform!r}")


# ---------------------------------------------------------------------------
# kernels: compiled kernel vs XLA reference, on the chip
# ---------------------------------------------------------------------------

def phase_kernels(args, cfg):
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.nn.chunked_ce import _ce_hard
    from paddle_tpu.ops.attention import _sdpa_xla
    from paddle_tpu.serving.kv_cache import gather_pages

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    ce = importlib.import_module("paddle_tpu.ops.pallas.chunked_ce")
    pd = importlib.import_module("paddle_tpu.ops.pallas.paged_decode")
    keys = iter(jax.random.split(jax.random.key(SEED), 64))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def run_kernel(fn, *a, want=()):
        """Compile ``fn`` ahead of time, hold its text to the kernels it
        must contain (on the chip: a kernel that interprets or falls
        back leaves no tpu_custom_call), and run that executable."""
        compiled = jax.jit(fn).lower(*a).compile()
        if not args.rehearse:
            names = kernel_names(compiled.as_text())
            check(set(want) <= names,
                  f"kernels {set(want) - names} missing from the program")
        return compiled(*a)

    def report(what, errs, tol):
        say(f"  {what} err {' '.join(f'{e:.2e}' for e in errs)} "
            f"(tol {tol:g})")
        check(all(np.isfinite(e) and e <= tol for e in errs),
              f"{what} off its XLA reference: {errs} > {tol}")

    # -- flash attention: forward and the three gradients -----------------
    def flash_case(shape, dtype, grads):
        q, k, v, w = (normal(shape, 0.5) for _ in range(4))

        def kern(q, k, v):
            return fa.flash_attention(q, k, v, causal=True)

        def ref(q, k, v):
            with jax.default_matmul_precision("highest"):
                return _sdpa_xla(q, k, v, None, 0.0, True, None)

        def with_grads(attn):
            def f(q, k, v):
                out = attn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * w), out
            return jax.value_and_grad(f, (0, 1, 2), has_aux=True)

        lo = tuple(x.astype(dtype) for x in (q, k, v))
        if grads:
            (_, out_k), g_k = run_kernel(with_grads(kern), *lo,
                                         want=("flash_fwd", "flash_bwd"))
            (_, out_r), g_r = jax.jit(with_grads(ref))(q, k, v)
        else:
            out_k = run_kernel(kern, *lo, want=("flash_fwd",))
            out_r, g_k, g_r = jax.jit(ref)(q, k, v), (), ()
        name = jnp.dtype(dtype).name
        report(f"flash_attention {shape} {name} causal "
               f"{'out dq dk dv' if grads else 'out'}",
               [rel_err(out_k, out_r)]
               + [rel_err(a, b) for a, b in zip(g_k, g_r)], TOL[name])

    flash_case(cfg["flash_train"], jnp.bfloat16, grads=True)
    flash_case(cfg["flash_prefill"], jnp.float32, grads=False)

    # -- chunked cross-entropy: loss and dlogits --------------------------
    N, V = cfg["ce_logits"]
    chunk = cfg["ce_chunk"]
    for dtype in (jnp.bfloat16, jnp.float32):
        name = jnp.dtype(dtype).name
        logits = normal((N, V), 3.0).astype(dtype)
        labels = jax.random.randint(next(keys), (N,), 0, V, jnp.int32)
        g = jax.random.uniform(next(keys), (N,), jnp.float32, 0.5, 1.5)

        def with_grad(per_row_loss):
            def f(lg, lab, g):
                per_row = per_row_loss(lg, lab)
                return jnp.sum(per_row * g), per_row
            return jax.value_and_grad(f, has_aux=True)

        (_, loss_k), d_k = run_kernel(
            with_grad(lambda lg, lab: ce.chunked_ce_loss(lg, lab, chunk)),
            logits, labels, g,
            want=("chunked_ce_lse", "chunked_ce_dlogits"))
        (_, loss_r), d_r = jax.jit(with_grad(
            lambda lg, lab: _ce_hard(chunk, lg, lab)))(logits, labels, g)
        report(f"chunked_ce {(N, V)} {name} loss (abs)",
               [float(jnp.max(jnp.abs(loss_k - loss_r)))], CE_LOSS_TOL)
        # dlogits is written in the logits dtype: one rounding of it
        report(f"chunked_ce {(N, V)} {name} dlogits",
               [rel_err(d_k, d_r)], TOL[name] if dtype == jnp.bfloat16
               else 1e-4)
        del logits, d_k, d_r

    # -- paged decode vs gather_pages + masked softmax ---------------------
    p = cfg["paged"]
    B, H, D, bs, MB = (p["slots"], p["heads"], p["head_dim"], p["block"],
                       p["blocks"])
    P = B * MB + 1                               # page 0 is the scratch
    table = np.zeros((B, MB), np.int32)
    # slots at different fill levels: one token, page edges, full
    pos = np.resize(np.array([0, bs - 1, bs, MB * bs // 3, MB * bs // 2,
                              MB * bs - bs - 1, MB * bs - 1, 2 * bs + 5]),
                    B).astype(np.int32)
    for b in range(B):
        n = pos[b] // bs + 1
        table[b, :n] = 1 + b * MB + np.arange(n)
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    scale = 1.0 / float(np.sqrt(D))

    def paged_ref(q, k, v, table, pos):
        gk, gv = gather_pages(k, table, D), gather_pages(v, table, D)
        cols = jnp.arange(gk.shape[1])
        mask = jnp.where(cols[None, :] <= pos[:, None], 0.0, -1e30)
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhd,bkhd->bhk", q, gk) * scale
            pr = jax.nn.softmax(s + mask[:, None, :], axis=-1)
            return jnp.einsum("bhk,bkhd->bhd", pr, gv)

    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        lo = (normal((B, H, D)).astype(dtype),
              normal((P, 1, bs, H * D)).astype(dtype),   # lane-dense pools
              normal((P, 1, bs, H * D)).astype(dtype))
        out_k = run_kernel(
            lambda q, k, v, t, p: pd.paged_decode_attention(
                q, k, v, t, p, scale=scale),
            *lo, table, pos, want=("paged_decode",))
        # the reference reads the same rounded inputs, in f32
        out_r = jax.jit(paged_ref)(*(x.astype(jnp.float32) for x in lo),
                                   table, pos)
        report(f"paged_decode q{(B, H, D)} pool{(P, 1, bs, H * D)} {name}",
               [rel_err(out_k, out_r)], TOL[name])

    # -- grouped K/V heads under a window: the sweep starts at the window's
    #    first page, entries before it point at the scratch page ----------
    p = cfg["paged_window"]
    B, H, Hkv, D, bs, MB, W = (p["slots"], p["heads"], p["kv_heads"],
                               p["head_dim"], p["block"], p["blocks"],
                               p["window"])
    live = (W + bs - 1) // bs + 1                # entries a window touches
    P = B * live + 1
    L = MB * bs
    # inside the window, at its edge, past it with the first visible
    # position inside a page and on a page's first row, the table's end
    pos = np.resize(np.array([0, W - 1, W, W + bs // 2, 2 * W + bs - 1,
                              L - 1, W // 2, L // 2 + 3]), B).astype(np.int32)
    first = np.maximum(pos - W + 1, 0)
    table = np.zeros((B, MB), np.int32)
    for b in range(B):
        e0, e1 = first[b] // bs, pos[b] // bs
        table[b, e0:e1 + 1] = 1 + b * live + np.arange(e1 - e0 + 1)
    e0 = jnp.asarray(first // bs)
    table, pos, first = (jnp.asarray(a) for a in (table, pos, first))
    scale = 1.0 / float(np.sqrt(D))

    def window_ref(q, k, v, table, pos, first):
        # the window's entries alone: a dense copy of the table's 28,672
        # positions a slot would not fit
        ent = jnp.minimum(e0[:, None] + jnp.arange(live)[None, :], MB - 1)
        tbl = jnp.take_along_axis(table, ent, axis=1)
        gk, gv = gather_pages(k, tbl, D), gather_pages(v, tbl, D)
        cols = (e0 * bs)[:, None] + jnp.arange(live * bs)[None, :]
        ok = (cols <= pos[:, None]) & (cols >= first[:, None])
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bngd,bknd->bngk",
                           q.reshape(B, Hkv, H // Hkv, D), gk) * scale
            pr = jax.nn.softmax(jnp.where(ok[:, None, None], s, -1e30), -1)
            return jnp.einsum("bngk,bknd->bngd", pr, gv).reshape(B, H, D)

    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        lo = (normal((B, H, D)).astype(dtype),
              normal((P, 1, bs, Hkv * D)).astype(dtype),
              normal((P, 1, bs, Hkv * D)).astype(dtype))
        out_k = run_kernel(
            lambda q, k, v, t, p, f: pd.paged_decode_attention(
                q, k, v, t, p, scale=scale, first=f),
            *lo, table, pos, first, want=("paged_decode",))
        out_r = jax.jit(window_ref)(*(x.astype(jnp.float32) for x in lo),
                                    table, pos, first)
        report(f"paged_decode q{(B, H, D)} on {Hkv} K/V heads, window {W}, "
               f"pool{(P, 1, bs, Hkv * D)} {name}",
               [rel_err(out_k, out_r)], TOL[name])

    # -- dense latent attention: ONE pool, its rows the keys of every head
    #    and, their first `rank` lanes, the values ---------------------------
    p = cfg["paged_latent"]
    B, H, r, dr, Wd, bs, MB, longest = (
        p["slots"], p["heads"], p["rank"], p["rope"], p["width"], p["block"],
        p["blocks"], p["longest"])
    live = longest // bs
    P = B * live + 1
    # a page's first and last row, a page's edge, chunk edges, the longest
    pos = np.resize(np.array([0, bs - 1, bs, longest // 16 - 1,
                              longest // 4 - 1, longest // 2 + 3,
                              longest - bs - 1, longest - 1]),
                    B).astype(np.int32)
    table = np.zeros((B, MB), np.int32)
    for b in range(B):
        n = pos[b] // bs + 1
        table[b, :n] = 1 + b * live + np.arange(n)
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    # the cell's 192^-0.5 * m^2 = 0.1447 to a percent (the nope dims are
    # a quarter of the rank there)
    scale = float((r // 4 + dr) ** -0.5 * 2.0)
    pad = jnp.arange(Wd) < r + dr

    def latent_ref(q, pool, table, pos):
        rows = pool[table[:, :live]].reshape(B, live * bs, Wd)
        seen = jnp.arange(live * bs)[None, :] <= pos[:, None]
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhw,bkw->bhk", q, rows) * scale
            pr = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1)
            return jnp.einsum("bhk,bkr->bhr", pr, rows[..., :r])

    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        lo = (jnp.where(pad, normal((B, H, Wd)), 0.0).astype(dtype),
              jnp.where(pad, normal((P, 1, bs, Wd)), 0.0).astype(dtype))
        out_k = run_kernel(
            lambda q, pool, t, p: pd.paged_mla_decode(
                q, pool, t, p, scale=scale, value_width=r),
            *lo, table, pos, want=("paged_mla_decode",))
        out_r = jax.jit(latent_ref)(*(x.astype(jnp.float32) for x in lo),
                                    table, pos)
        report(f"paged_mla_decode q{(B, H, Wd)} values {r} "
               f"pool{(P, 1, bs, Wd)} {name}",
               [rel_err(out_k, out_r)], TOL[name])


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="small shapes on the CPU, kernels interpreted; "
                         "never prints the result line")
    args = ap.parse_args()

    faulthandler.enable()
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FLAGS_pallas_interpret"] = "1"
    cfg = REHEARSE if args.rehearse else REAL

    import jax
    import paddle_tpu  # noqa: F401  (places the compile cache at import)

    ledger = Ledger()
    devices = jax.devices()[:1]
    t0 = time.perf_counter()
    run_phase("device", lambda: phase_device(args), ledger, devices)
    run_phase("kernels", lambda: phase_kernels(args, cfg), ledger, devices)
    say(f"all phases ok in {time.perf_counter() - t0:.1f}s; persistent "
        f"cache hits {ledger.cache_hits} misses {ledger.cache_misses}")
    faulthandler.cancel_dump_traceback_later()

    if args.rehearse:
        say("rehearsal only: no result line")
        return 0
    dev = jax.devices()[0]
    check(dev.platform == "tpu", "result line is for a TPU run only")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
