"""Aux-subsystem tests: static Executor, GradScaler dynamic loop, profiler,
NaN/Inf debug under jit (SURVEY §5; VERDICT round-1 'test-free surface').

reference analogues: test_executor_and_use_program_cache.py,
test_grad_scaler.py / test_amp_*.py dynamic-loss-scaling asserts,
test_profiler.py, test_nan_inf.py.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn, static


def test_static_executor_runs_callable_jitted():
    lin = nn.Linear(4, 2)

    def program(x):
        return lin(x)

    exe = static.Executor()
    x = np.random.RandomState(0).randn(3, 4).astype(np.float32)
    (out,) = exe.run(program, feed={"x": paddle.to_tensor(x)})
    with paddle.no_grad():
        ref = lin(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_static_compiled_program_caches():
    calls = []

    def program(x):
        calls.append(1)            # traced once per signature
        return x * 2

    cp = static.CompiledProgram(program)
    exe = static.Executor()
    x = np.ones((2, 2), np.float32)
    a = exe.run(cp, feed={"x": x})
    b = exe.run(cp, feed={"x": x + 1})
    assert len(calls) == 1         # second run hit the jit cache
    np.testing.assert_allclose(a[0], 2 * x)
    np.testing.assert_allclose(b[0], 2 * (x + 1))


def test_static_executor_rejects_non_callable():
    with pytest.raises(TypeError, match="callables"):
        static.Executor().run(object())


def test_grad_scaler_dynamic_scale_update():
    from paddle_tpu.amp import GradScaler

    model = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    scaler = GradScaler(init_loss_scaling=1024.0, incr_every_n_steps=2,
                        incr_ratio=2.0, decr_ratio=0.5)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))

    # two good steps -> scale doubles once (incr_every_n_steps=2)
    for _ in range(2):
        loss = scaler.scale(model(x).sum())
        loss.backward()
        scaler.step(opt)
        scaler.update()
        opt.clear_grad()
    assert scaler.get_loss_scaling() == 2048.0

    # a NaN gradient step: update is skipped and the scale halves
    w_before = np.asarray(model.weight._data).copy()
    bad = model(x).sum() * float("nan")
    scaler.scale(bad).backward()
    scaler.step(opt)
    scaler.update()
    opt.clear_grad()
    assert scaler.get_loss_scaling() == 1024.0
    np.testing.assert_allclose(np.asarray(model.weight._data), w_before)


def test_profiler_event_table():
    from paddle_tpu import profiler as prof

    prof.start_profiler()
    with prof.RecordEvent("my_region"):
        _ = paddle.to_tensor(np.ones((4, 4), np.float32)) * 2
    prof.stop_profiler()
    table = prof.summary()
    assert "my_region" in table and "Calls" in table


def test_trainstep_nan_check_under_jit():
    from paddle_tpu.jit.to_static import TrainStep

    model = nn.Linear(4, 2)

    def loss_fn(layer, x, y):
        return F.mse_loss(layer(x), y)

    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = TrainStep(model, loss_fn, opt)
    x = np.ones((2, 4), np.float32)
    y = np.zeros((2, 2), np.float32)
    paddle.set_flags({"check_nan_inf": True})
    try:
        float(step(x, y))                     # clean step passes
        x_bad = x.copy()
        x_bad[0, 0] = np.nan
        with pytest.raises(RuntimeError, match="NaN/Inf detected"):
            step(x_bad, y)
    finally:
        paddle.set_flags({"check_nan_inf": False})


@pytest.fixture
def _cache_config():
    """Hand the test jax's cache-dir config cleared of the suite's own
    directory, and put that back afterwards."""
    import jax
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("placed", ["env", "config", "default"])
def test_compilation_cache_is_placed_from_outside(placed, tmp_path,
                                                  monkeypatch,
                                                  _cache_config):
    """The persistent compile cache (FLAGS_compilation_cache, default
    on) goes where the environment says: $JAX_COMPILATION_CACHE_DIR wins
    over everything and code sets no other; without it a directory
    already given to jax.config is kept; else ONE fixed directory inside
    the checkout — never ~/.cache or $XDG_CACHE_HOME, whose path differs
    between machines and is part of the cache key."""
    import os

    import jax
    from paddle_tpu.core import flags
    assert flags.get_flag("compilation_cache") is True
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert flags.DEFAULT_COMPILATION_CACHE_DIR == want
    if placed == "env":
        want = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path / "set_in_code"))
    elif placed == "config":
        want = str(tmp_path / "set_in_code")
        jax.config.update("jax_compilation_cache_dir", want)
    else:
        # the default lives in the checkout; do not create it from a test
        monkeypatch.setattr(flags, "DEFAULT_COMPILATION_CACHE_DIR",
                            str(tmp_path / "checkout" / ".jax_cache"))
        want = flags.DEFAULT_COMPILATION_CACHE_DIR
    assert flags.apply_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
    assert not (tmp_path / "xdg").exists()
    assert not (tmp_path / "home").exists()


def test_compilation_cache_flag_off_and_no_dir_flag(_cache_config):
    """Disabling returns None and touches nothing; the old
    FLAGS_compilation_cache_dir override is gone (the environment
    variable is the one way to place the cache)."""
    import jax
    from paddle_tpu.core.flags import (apply_compilation_cache, flag_scope,
                                       get_flags)
    with flag_scope("compilation_cache", False):
        assert apply_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
    with pytest.raises(KeyError, match="compilation_cache_dir"):
        get_flags("compilation_cache_dir")


def test_profiler_eager_op_table():
    """Per-op eager aggregation: profiled eager ops appear in summary()
    with counts (reference: per-op RecordEvent in imperative/tracer.cc)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import profiler

    x = paddle.to_tensor(np.ones((8, 8), np.float32))
    profiler.start_profiler()
    try:
        y = x * 2 + 1
        z = y.sum()
        float(z)
    finally:
        profiler.stop_profiler()
    table = profiler.summary()
    assert "op::" in table
    # hook removed after stop: no further accumulation
    before = table
    _ = x * 3
    assert profiler.summary() == before


def test_profiler_trace_save(tmp_path):
    """Trace capture writes an XPlane trace dir (device_tracer.cc:464
    analogue) usable with TensorBoard."""
    import os

    import jax
    import jax.numpy as jnp
    from paddle_tpu import profiler

    d = str(tmp_path / "trace")
    profiler.start_profiler(log_dir=d)
    try:
        jax.jit(lambda a: (a @ a).sum())(jnp.ones((64, 64))).block_until_ready()
    finally:
        profiler.stop_profiler()
    files = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert files, "no trace files written"


def test_profile_train_step_breakdown():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, profiler
    from paddle_tpu.jit.to_static import TrainStep
    from paddle_tpu.optimizer import SGD

    paddle.seed(0)
    model = nn.Linear(8, 4)

    def loss_fn(layer, x, y):
        return ((layer(x) - y) ** 2).mean()

    step = TrainStep(model, loss_fn, SGD(learning_rate=0.1))
    rng = np.random.default_rng(0)
    batch = (paddle.to_tensor(rng.standard_normal((16, 8)).astype(np.float32)),
             paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32)))
    br = profiler.profile_train_step(step, batch, iters=3, warmup=1)
    assert set(br) == {"compile_s", "host_ms", "dispatch_ms", "step_ms",
                       "device_ms_est"}
    assert br["compile_s"] > 0 and br["step_ms"] > 0
    assert br["device_ms_est"] >= 0


def test_profiler_chrome_trace_export(tmp_path):
    """reference: platform/device_tracer.cc GenProfile chrome timeline."""
    import json

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import profiler as prof

    prof.start_profiler()
    with prof.RecordEvent("outer_block"):
        x = paddle.to_tensor(np.ones((8, 8), np.float32))
        (x * x).sum().numpy()
    prof.stop_profiler()
    path = prof.export_chrome_tracing(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    names = {e["name"] for e in data["traceEvents"]}
    assert "outer_block" in names
    assert any(n.startswith("op::") for n in names)
    for e in data["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0
