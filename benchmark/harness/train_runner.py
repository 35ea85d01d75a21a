"""Runner `train`: `paddle.jit.TrainStep` fed by `paddle.io.DataLoader`.

The runner loops and times. What is particular to a model family — its
dataset, what an item is, its loss, its evaluation function, the counts
its kernels' readers need — comes from `benchmark/models/<family>.py`.

Set-up (counted in `setup_s`): the model with weights from `--seed`, the
step program compiled or loaded from the cache (on a ZeRO mesh the
second step re-lowers: that too), `warm_steps` blocking steps, then the
system's agreement with the plain reference on the weights as the step
holds them. Window: steps dispatched back to back with at most
`inflight` not yet finished, for `--seconds`; the last one ends by
`block_until_ready`, and the rate is taken over all steps and all of
the time up to there.
"""

from __future__ import annotations

import collections
import multiprocessing
import time

from . import trace_reduce
from .result import (BenchFailure, Run, Timed, annotate, hbm_account, hbm_read,
                     rel_err, say)


def check_against_reference(run: Run, model, step, reference, data, devices):
    """Evaluation-mode loss and outputs of the system, in the compute
    type it trains in, against the float32 reference on the same
    weights, for `probe_sequences` seeded samples.

    The weights are the step's own after the warm steps, WHERE it holds
    them: in a mesh cell sharded over the chips, so the forward that is
    compared runs the tensor-parallel products with their all-reduces
    and flash attention per shard, on weights that have been through the
    partitioned update. The reference runs on one chip, on a copy of the
    same weights gathered there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.jit.functional import param_arrays
    tol = run.system["correct"]
    samples = [data[len(data) - 1 - i] for i in range(int(tol["probe_sequences"]))]
    batch = [np.stack(col) for col in zip(*samples)]
    step.sync_to_layer()
    params = param_arrays(model)
    spread = sorted({len(a.sharding.device_set) for a in params.values()})
    sys_fn = run.model.eval_loss_and_outputs(model, run.system["amp_level"])
    loss_s, out_s = sys_fn(params, *batch)
    loss_s, out_s = float(loss_s), np.asarray(out_s, np.float32)
    gathered = {k: jax.device_put(a, devices[0]) for k, a in params.items()}
    loss_r, out_r = reference.forward_and_loss(
        gathered, *(jnp.asarray(b) for b in batch))
    d_loss = abs(loss_s - float(loss_r))
    d_out = rel_err(jnp.asarray(out_s), out_r)
    run.notes["reference"] = {
        "loss_system": loss_s, "loss_reference": float(loss_r),
        "loss_abs_diff": d_loss, "outputs_rel_err": d_out,
        "chips_a_weight_spans": spread}
    if run.chips > 1:
        run.check("reference_across_chips", spread[-1] == run.chips,
                  f"the compared forward ran on weights spanning {spread} chips")
    run.check("reference_loss", np.isfinite(d_loss) and d_loss <= tol["loss_atol"],
              f"|{loss_s:.5f} - {float(loss_r):.5f}| = {d_loss:.2e} "
              f"(tol {tol['loss_atol']:g})")
    run.check("reference_outputs",
              np.isfinite(d_out) and d_out <= tol["outputs_rel_tol"],
              f"max|diff|/max|ref| = {d_out:.2e} "
              f"(tol {tol['outputs_rel_tol']:g})")


def _stop_loader(it) -> None:
    """Stop the workers now, and leave `__del__` nothing to do: at
    interpreter exit it would `kill()` a native queue the collector has
    already destroyed, and the process would die of a segmentation
    fault after printing its result (PERF.md, Open questions)."""
    it.inner._shutdown()
    it.inner._native_q = None


def run(run: Run, ledger, reference) -> None:
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.ops import pallas as pallas_ops

    mix, sysc, fam = run.mix, run.system, run.model
    devices = jax.devices()[:run.chips]
    pallas_ops.reset_pallas_stats()

    t = time.perf_counter()
    model = fam.build_model(run.config, run.seed, rehearse=run.rehearse,
                            **sysc.get("model_overrides", {}))
    jax.block_until_ready([p._data for p in model.parameters()])
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    data = fam.dataset(run.config, mix, run.seed, rehearse=run.rehearse)
    items_per_step = fam.items_per_step(run.config, mix, rehearse=run.rehearse)
    say(f"  model built in {time.perf_counter() - t:.1f}s: {n_params / 1e6:.1f}M "
        f"parameters, {items_per_step} {run.config['item']}s a step, "
        f"{sysc.get('model_overrides', {})}")

    kw = {}
    if sysc.get("mesh"):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(sysc["mesh"])
        fleet.init(is_collective=True, strategy=strategy)
        mesh = fleet.get_hybrid_communicate_group().mesh
        if mesh.devices.size != run.chips:
            raise BenchFailure(f"mesh spans {mesh.devices.size} devices, the "
                               f"cell asks for {run.chips}")
        say(f"  mesh {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")
        kw = dict(mesh=mesh, data_spec=P(tuple(sysc["data_spec"])),
                  zero_axis=sysc.get("zero_axis"))
    o = sysc["optimizer"]
    opt = getattr(paddle.optimizer, o["name"])(
        learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
        parameters=model.parameters())
    step = paddle.jit.TrainStep(model, fam.make_loss_fn(sysc["amp_level"]),
                                opt, **kw)

    loader = paddle.io.DataLoader(data, batch_size=int(mix["batch"]), shuffle=False,
                                  num_workers=int(mix["num_workers"]))
    it = iter(loader)
    try:
        workers = it.inner.workers
        run.check("loader_workers", len(workers) == int(mix["num_workers"])
                  and all(w.is_alive() for w in workers),
                  f"{len(workers)} live workers")
        for i in range(int(sysc["warm_steps"])):
            t = time.perf_counter()
            loss = float(step(*next(it)))
            say(f"  warm step {i + 1} loss {loss:.4f} "
                f"({time.perf_counter() - t:.2f}s wall, blocking)")
        t = time.perf_counter()
        check_against_reference(run, model, step, reference, data, devices)
        say(f"  reference check took {time.perf_counter() - t:.1f}s")
        _window(run, ledger, step, it, items_per_step, devices)
    finally:
        _stop_loader(it)
    run.check("loader_stopped", not multiprocessing.active_children(),
              "no worker left behind")

    (prog,) = step.aot_programs()
    expect = sysc["expect"]
    run.notes["aot"] = {"builds": prog.builds, "heals": prog.heals}
    run.check("aot_program", prog.compiled is not None
              and prog.heals <= expect["max_heals"],
              f"builds {prog.builds} heals {prog.heals} "
              f"(at most {expect['max_heals']})")
    if not run.rehearse:
        text = prog.compiled.as_text()
        missing = [k for k in expect["kernels"] if k not in text]
        run.check("kernels_in_program", not missing,
                  f"expected {expect['kernels']}, missing {missing}")
    fallbacks = {f"{k[0]}:{k[1]}": v for k, v in pallas_ops.PALLAS_STATS.items()}
    allowed = set(expect["fallbacks"])
    run.notes["pallas_fallbacks"] = fallbacks
    if not run.rehearse:
        run.check("no_unexpected_fallback", set(fallbacks) <= allowed,
                  f"recorded {fallbacks}, the cell allows {sorted(allowed)}")
    run.counts.update(fam.step_counts(run.config, mix, rehearse=run.rehearse))
    run.counts["items_per_step"] = items_per_step


def _window(run: Run, ledger, step, it, items_per_step: int, devices) -> None:
    import numpy as np
    sysc = run.system
    depth = int(sysc["inflight"])
    trace_s = min(float(sysc["trace_seconds"]), run.seconds) if run.traced else 0.0
    tracer = trace_reduce.WindowTrace(run.trace_dir) if run.traced else None
    losses, inflight = [], collections.deque()
    snap0 = ledger.snap()
    hbm0 = hbm_read(devices)
    run.e2e["setup_s"] = time.perf_counter() - run.t_start
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= run.seconds:
            break
        if tracer and not tracer.started and now >= run.seconds - trace_s:
            tracer.start()
        with Timed(run, "bench.input_wait"):
            batch = next(it)
        with Timed(run, "bench.step_call"):
            loss = step(*batch)
        losses.append(loss)
        inflight.append(loss)
        if len(inflight) > depth:
            with annotate(run, "bench.wait_step"):
                inflight.popleft()._data.block_until_ready()
    with annotate(run, "bench.wait_step"):
        losses[-1]._data.block_until_ready()
    t1 = time.perf_counter()
    hbm_account(run, devices, hbm0, [p.compiled for p in step.aot_programs()])
    if tracer and tracer.started:
        run.trace = tracer.stop()
    snap1 = ledger.snap()

    vals = [float(l) for l in losses]
    n = len(vals)
    run.attempted = n
    run.failed = sum(1 for v in vals if not np.isfinite(v))
    run.e2e["train_throughput"] = n * items_per_step / (t1 - t0)
    run.counts["steps"] = n
    run.counts["window_s"] = t1 - t0
    k = max(1, min(10, n // 2))
    first, last = float(np.mean(vals[:k])), float(np.mean(vals[-k:]))
    run.notes["losses"] = {"steps": n, "first": vals[:3], "last": vals[-3:],
                           f"mean_first_{k}": first, f"mean_last_{k}": last}
    run.check("losses_finite", run.failed == 0, f"{n} steps")
    run.check("loss_falls", n >= 2 and last < first,
              f"mean of the last {k} {last:.4f} < mean of the first {k} {first:.4f}")
    run.check("no_compile_in_window", snap1 == snap0,
              f"(compiles, cache hits, cache misses) {snap0} -> {snap1}")
