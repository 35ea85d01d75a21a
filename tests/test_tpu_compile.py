"""The main-path kernels, compiled for a described TPU v5e — no chip.

libtpu's compiler is installed with jaxlib and compiles for a topology
that is described and not attached, so what Mosaic or XLA:TPU would
refuse on the chip (VMEM over the scoped limit, a dot form Mosaic cannot
parse, a misaligned slice) is refused here, in a CPU test run, at the
GPT-2 345M shapes the training and serving paths use. Interpret-mode
parity tests cannot see any of that: two of these kernels passed every
one of them and had never compiled (ISSUE 22).

The serving programs compile here too, whole, at the serve cell's
engine settings: what XLA:TPU does to the page pools between the
program's arguments and the kernel (a relayout copy, a per-layer slice)
is invisible to every CPU parity test and was 81% of the cell's device
time before ISSUE 27.

Nothing here runs a kernel — results are chip_smoke.py's job. The
topology is described inside a module-scoped fixture and nowhere at
import: only one process may load libtpu, and under pytest-xdist every
worker imports every test file.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.core.flags import flag_scope

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32

# GPT-2 345M: 16 heads of 64, vocab 50304; training B=8 S=1024, serving
# 8 slots over 16-token lane-dense pages [pages, 1, 16, 16*64] with a
# 512-token context (256 pages + the scratch page), LoRA rank 16 over 5
# adapters
TRAIN_QKV = (8, 1024, 16, 64)
MESH_SHARD_QKV = (8, 1024, 10, 64)   # 774M on sharding2 x mp2, one shard
PREFILL_QKV = (4, 256, 16, 64)
PREFILL768_QKV = (4, 768, 16, 64)    # the serve cell's long bucket: block 384
BERT_QKV = (48, 512, 12, 64)
LOGITS = (8192, 50304)
CE_CHUNK = 8192                      # FLAGS_chunked_ce_chunk default
POOL, TABLE, SLOTS = (257, 1, 16, 1024), (8, 32), 8


def _kernel(name):
    # the package re-exports same-named functions over its submodules
    return importlib.import_module("paddle_tpu.ops.pallas." + name)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> a shaped argument placed on the first
    described device (there is no device to hold an array)."""
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return shaped


@pytest.fixture
def build_for_chip():
    """``build_for_chip(lower, *shaped_args)`` -> the ``Compiled`` of
    ``lower(*shaped_args).compile()``.

    The kernels compile, not interpret (``FLAGS_pallas_interpret`` off,
    whatever marker a neighbour test carried), and the persistent cache
    is off around the compile: an executable built for a described chip
    is written to it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def run(lower, *args):
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            with flag_scope("pallas_interpret", False):
                return lower(*args).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()

    return run


@pytest.fixture
def compile_for_chip(build_for_chip):
    """``compile_for_chip(fn, *shaped_args)`` -> the compiled text."""
    return lambda fn, *args: build_for_chip(jax.jit(fn).lower,
                                            *args).as_text()


def _sum32(x):
    return x.astype(F32).sum()


def _assert_kernels(text, *names):
    """The compiled program holds Mosaic kernels, under the stable names
    the package gives its pallas_calls (what a device trace shows)."""
    assert "tpu_custom_call" in text
    for name in names:
        assert name in text, name


@pytest.mark.parametrize("shape,dtype,grad,dropout,bias", [
    (TRAIN_QKV, BF16, False, 0.0, False),
    (TRAIN_QKV, BF16, True, 0.0, False),
    (TRAIN_QKV, BF16, True, 0.1, False),
    (TRAIN_QKV, F32, True, 0.0, False),
    (PREFILL_QKV, F32, False, 0.0, False),   # the engine serves in f32
    (BERT_QKV, BF16, True, 0.0, True),
    (TRAIN_QKV, BF16, False, 0.1, False),
    (MESH_SHARD_QKV, BF16, False, 0.0, False),
    (MESH_SHARD_QKV, BF16, True, 0.0, False),
    (MESH_SHARD_QKV, BF16, True, 0.1, False),
    (PREFILL768_QKV, BF16, False, 0.0, False),
    (PREFILL768_QKV, BF16, True, 0.0, False),
    (PREFILL768_QKV, BF16, True, 0.1, False),
], ids=["train-fwd", "train-fwd+bwd", "train-fwd+bwd-dropout",
        "train-f32-fwd+bwd", "prefill-f32-fwd", "bert-bias-fwd+bwd",
        "train-fwd-dropout", "mesh-shard-fwd", "mesh-shard-fwd+bwd",
        "mesh-shard-fwd+bwd-dropout", "prefill768-fwd",
        "prefill768-fwd+bwd", "prefill768-fwd+bwd-dropout"])
def test_flash_attention_compiles(chip, compile_for_chip, shape, dtype,
                                  grad, dropout, bias):
    """The shapes the cells run: the one-chip train step, one shard of
    the mesh step (10 heads), the serve cell's two prefill buckets. On
    bf16 operands the products are bf16 dots, contracted over dim 0 of
    both operands in the backward (``ds^T q``, ``p^T dO``), and an f32
    tile is three bf16 parts (ISSUE 29): forms Mosaic has to take.

    Scoped VMEM (limit 16 MiB), read as the least ``vmem_limit_bytes``
    under which a kernel compiles for this described chip (bisection in
    64 KiB steps, ISSUE 29), train shape, bf16, dropout 0.1: forward 9.62
    MiB (11.62 with the log-sum-exp output), fused backward 10.94 —
    where the kernels that upcast q/k/v/dO to f32 first took 12.69
    (14.69) and 12.19: the three bf16 parts of a 512 x 512 tile, 1.5 MiB,
    cost less than the f32 copies they replace. Without dropout 8.44
    (10.44) and 9.56; the 4 x 768 prefill 5.75 forward, 10.38 backward.
    f32 operands are as they were, 14.94 (16.94) and 14.44 with dropout:
    the f32 forward WITH dropout and the log-sum-exp output at this
    shape is over the limit and is refused (16.91 M against 16.00 M;
    ISSUE 29 found it, no cell or model runs it: AMP steps are bf16)."""
    fa = _kernel("flash_attention")
    q = chip(shape, dtype)
    key = chip((), jax.random.key(0).dtype)
    b = chip((shape[0], 1, 1, shape[1]), F32)

    def loss(q, k, v, key, b):
        return _sum32(fa.flash_attention(
            q, k, v, bias=b if bias else None, causal=not bias,
            dropout_rate=dropout, dropout_key=key if dropout else None))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    v = "_v1" if bias else ""            # additive bias: the v1 kernels
    names = ["flash_fwd" + v] + (
        ["flash_dq_v1", "flash_dkv_v1"] if grad and bias
        else ["flash_bwd"] if grad else [])
    _assert_kernels(compile_for_chip(fn, q, q, q, key, b), *names)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_chunked_ce_compiles(chip, compile_for_chip, dtype, grad):
    """The backward is the one the compiler refused at the forward's row
    block: 16.25 MiB (bf16) / 20.38 MiB (f32) of scoped VMEM against a
    16 MiB limit."""
    ce = _kernel("chunked_ce")

    def loss(logits, labels):
        return ce.chunked_ce_loss(logits, labels, CE_CHUNK).sum()

    fn = jax.grad(loss) if grad else loss
    text = compile_for_chip(fn, chip(LOGITS, dtype),
                            chip(LOGITS[:1], I32))
    _assert_kernels(text, "chunked_ce_lse",
                    *(["chunked_ce_dlogits"] if grad else []))


@pytest.mark.parametrize("dtype,quant,slots,table,pool", [
    (BF16, False, SLOTS, TABLE, POOL), (F32, False, SLOTS, TABLE, POOL),
    (BF16, True, SLOTS, TABLE, POOL), (F32, True, SLOTS, TABLE, POOL),
    (BF16, False, 64, (64, 64), (24 * 4097, 1, 16, 1024)),
], ids=["bf16-plain", "f32-plain", "bf16-int8", "f32-int8",
        "bf16-plain-closed64"])
def test_paged_decode_compiles(chip, compile_for_chip, dtype, quant, slots,
                               table, pool):
    """Refused until ISSUE 22: a dot_general batched over a non-leading
    dimension is not a form Mosaic parses. Since ISSUE 31 the kernel
    copies its pages itself (a loop of ``make_async_copy`` out of pools
    left in HBM, two VMEM buffers a pool): the serve cell's own call —
    64 slots, a table of 64 entries, the pools of all 24 layers — is the
    last case."""
    pd = _kernel("paged_decode")
    q = chip((slots, 16, 64), dtype)
    table, pos = chip(table, I32), chip((slots,), I32)
    if quant:
        pages, scales = chip(pool, I8), chip(pool[:3] + (16,), F32)
        text = compile_for_chip(
            lambda q, k, ks, v, vs, t, p: pd.paged_decode_attention_quant(
                q, k, ks, v, vs, t, p, scale=0.125),
            q, pages, scales, pages, scales, table, pos)
    else:
        pages = chip(pool, dtype)
        text = compile_for_chip(
            lambda q, k, v, t, p: pd.paged_decode_attention(
                q, k, v, t, p, scale=0.125),
            q, pages, pages, table, pos)
    _assert_kernels(text, "paged_decode_int8" if quant else "paged_decode")


def test_bgmv_compiles(chip, compile_for_chip):
    text = compile_for_chip(
        _kernel("bgmv").bgmv, chip((SLOTS, 1, 1024), BF16),
        chip((5, 16, 1024), BF16), chip((5, 16, 3072), BF16),
        chip((SLOTS,), I32))
    _assert_kernels(text, "bgmv")


def test_fused_dropout_compiles(chip, compile_for_chip):
    dr = _kernel("dropout")
    text = compile_for_chip(
        jax.grad(lambda x, key: _sum32(dr.fused_dropout(x, 0.1, key))),
        chip((8, 1024, 1024), BF16), chip((), jax.random.key(0).dtype))
    _assert_kernels(text, "fused_dropout")


def test_int8_matmul_compiles(chip, compile_for_chip):
    text = compile_for_chip(
        _kernel("quant_matmul").int8_matmul, chip((8192, 1024), I8),
        chip((1024, 4096), I8), chip((4096,), F32), chip((), F32))
    _assert_kernels(text, "int8_matmul")


# -- the serving programs, whole ---------------------------------------------

# the serve cell's engine (benchmark/workloads/gpt2_345m.serve.closed64.json)
CELL_ENGINE = dict(max_batch_slots=64, block_size=16, max_context_len=1024,
                   prefill_buckets=(256, 768), batch_buckets=(1, 4),
                   cache_dtype="bfloat16")
CELL_PAGES = 1 + 64 * 64
# GPT-2 345M cut to 4 layers, ISSUE 27's second choice: at 24 the two
# compiles take 70 s, and every execution stacks the layers' parameters
# into 0.60 GB of temporaries that would hide a pool copy of a layer
GUARD_LAYERS = 4
LAYER_POOL = CELL_PAGES * 16 * 1024 * 2      # bytes: one layer's K pool
TEMP_LIMIT = 100e6                   # bytes: ISSUE 27's, for decode
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(", re.M)


@pytest.fixture
def cell_engine():
    """GPT-2 345M's widths at ``GUARD_LAYERS`` layers, bf16 weights,
    behind the cell's engine settings. The engine's own pools are one
    slot small — the compiles below take the cell's 4097-page pools as
    SHAPES. (An engine lives one test long: conftest resets the serving
    layer after each.)"""
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.models.gpt import GPTForPretraining, gpt2_medium
    from paddle_tpu.serving import ServingConfig
    paddle.seed(0)
    model = GPTForPretraining(gpt2_medium(num_layers=GUARD_LAYERS))
    cfg = inference.Config.from_layer(model, input_spec=[])
    cfg.enable_tpu_bf16()
    return inference.create_serving_engine(
        cfg, ServingConfig(num_pages=65, **CELL_ENGINE))


def _pool_sized_moves(text, layer_pool=LAYER_POOL, rows=None):
    """Instructions of the optimized HLO that copy, slice or update-
    slice (alone or as the root of a fusion XLA named after them) into
    a result as large as one layer's pool — with ``rows``, only results
    whose two minor dims are a pool's ``(block_size, width)``: a
    program may hold other arrays of that size."""
    found = []
    for name, dtype, dims, op in _INSTR.findall(text):
        shape = [int(d) for d in dims.split(",") if d] or [1]
        what = name if op == "fusion" else op
        if (re.search(r"copy|dynamic-slice|dynamic-update-slice", what)
                and int(np.prod(shape)) * _BYTES.get(dtype, 4) >= layer_pool
                and (rows is None or tuple(shape[-2:]) in rows)):
            found.append((name, op, dtype, dims))
    return found


def _compile_with_cell_pools(eng, kind, pages, chip, build_for_chip,
                             monkeypatch):
    """One of an engine's serving programs, compiled for the chip with
    the pools at a cell's page count as SHAPES (the engine's own are a
    few pages)."""
    prog, args = {"decode": eng._decode_program,
                  "prefill": lambda: eng._prefill_program(
                      1, eng.config.prefill_buckets[0]),
                  "prefill_ctx": lambda: eng._prefill_ctx_program(
                      1, eng.config.prefill_buckets[0])}[kind]()
    shaped = jax.tree.map(lambda a: chip(a.shape, a.dtype), args)
    pools = tuple(chip((p.shape[0], pages) + p.shape[2:], p.dtype)
                  for p in shaped[1])
    # dispatch asks the backend whether kernels can run: they can, there
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = build_for_chip(prog._jitted.lower, shaped[0], pools,
                              *shaped[2:])
    monkeypatch.undo()
    return compiled


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_serving_program_moves_no_pool(chip, build_for_chip, cell_engine,
                                       monkeypatch, kind):
    """ISSUE 27's guard: the page pools go from the program's donated
    arguments through the layer scan's carry to the kernel in ONE
    layout. The pools are updated in place (aliased to the arguments),
    and ALL the program's temporaries stay under the issue's 100 MB
    with nothing deducted and no instruction exempted: a relayout of
    ONE layer's K pool is 134 MB, whatever XLA names it. (What is there,
    by the compiler's buffer assignment: the four layers' parameters
    stacked, 92 MB, whose space the sampler's ``[64, 50304]`` arrays use
    after the scan; 94.8 MB in decode, 94.6 in the prefill. The smallest
    prefill bucket, because the pools take the same way through every
    bucket and a 4x768 group's logits alone are 309 MB.)"""
    compiled = _compile_with_cell_pools(cell_engine, kind, CELL_PAGES, chip,
                                        build_for_chip, monkeypatch)
    text = compiled.as_text()
    _assert_kernels(text, "paged_decode" if kind == "decode"
                    else "flash_fwd")
    assert _pool_sized_moves(text) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * GUARD_LAYERS * LAYER_POOL, mem
    assert mem.temp_size_in_bytes < TEMP_LIMIT, mem


# -- the one-chip train step, whole (ISSUE 34) ----------------------------------


class _Built(Exception):
    """Carries the executable out of a TrainStep's first call."""


def _train_step_for_chip(policy, chip, build_for_chip, monkeypatch):
    """The step of `gpt2_345m.train.b8s1024` (AMP O1, AdamW, recompute,
    B=8, S=1024) at ``GUARD_LAYERS`` layers, compiled for the chip. A
    TrainStep builds its program in its first call: that build is made
    for the described device, on the arguments' SHAPES, and the call
    ends there."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import aot
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       GPTPretrainingCriterion, gpt2_medium)
    paddle.seed(0)
    model = GPTForPretraining(gpt2_medium(
        num_layers=GUARD_LAYERS, use_recompute=True, recompute_policy=policy))
    crit = GPTPretrainingCriterion()

    def loss_fn(layer, ids, labels):
        with paddle.amp.auto_cast(level="O1"):
            return crit(layer(ids), labels)

    step = paddle.jit.TrainStep(model, loss_fn, paddle.optimizer.AdamW(
        learning_rate=1e-4, weight_decay=0.01,
        parameters=model.parameters()))

    def build(self, args):
        shaped = jax.tree.map(lambda a: chip(a.shape, a.dtype), args)
        raise _Built(build_for_chip(self._jitted.lower, *shaped))

    monkeypatch.setattr(aot.AOTProgram, "_build", build)
    # dispatch asks the backend whether kernels can run: they can, there
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ids = np.zeros(TRAIN_QKV[:2], np.int32)
    with pytest.raises(_Built) as built:
        step(ids, ids)
    monkeypatch.undo()
    return built.value.args[0]


def test_train_step_keeps_the_flash_residuals_dense(chip, build_for_chip,
                                                    monkeypatch):
    """A recomputed layer body runs no Mosaic kernel again, by
    `aot.kernel_calls`' reading of the optimized program: no `flash_fwd`
    (its output and log-sum-exp are kept) and no `fused_dropout` (the
    attention branch after the hidden dropout is kept); under
    ``"full"`` both run again, once a body. Nor does it run an MXU
    product again (`aot.products`): the QKV, out-projection and FFN-in
    products are three a body under ``"full"``, none under the default.

    What keeping costs stays near what is reckoned a layer: `o` (16 MiB)
    and the log-sum-exp tile as the kernel writes it (4 MiB of
    ``[B, H, S, 8]``; kept whole, its 8 lanes would pad to 128 under the
    ``T(8, 128)`` tiling, 64 MiB a layer, so ONE column is kept and the
    backward widens it), plus the block's kept set: the attention
    branch (16 MiB: bfloat16 under AMP O1, as the residual stream), the
    FFN's first product (64 MiB) and the fused QKV product (48 MiB). By
    the compiler's buffer assignment for the described chip the step's
    preallocated temporaries grow by the kept set once (577 MiB at 4
    layers: 144 a layer); the temporaries `memory_analysis()` reports
    grow by 1,155 MiB, twice that, as they grew 33.0 MiB a layer for the
    flash residuals' 16.5. So the limit is twice the reckoned bytes, and
    at least once is kept."""
    from paddle_tpu.jit import aot
    kept = _train_step_for_chip(None, chip, build_for_chip, monkeypatch)
    full = _train_step_for_chip("full", chip, build_for_chip, monkeypatch)
    _assert_kernels(kept.as_text(), "flash_fwd", "flash_bwd",
                    "chunked_ce_lse", "chunked_ce_dlogits")

    def attn_kernels(compiled):
        module = aot.index_program(compiled.as_text())
        assert module == "jit_train_step"
        return [sorted(n.split(".")[0]
                       for n in aot.kernel_calls(module, "attn", phase))
                for phase in ("fwd", "remat", "bwd")]

    assert attn_kernels(full) == [["flash_fwd", "fused_dropout"],
                                  ["flash_fwd", "fused_dropout"],
                                  ["flash_bwd", "fused_dropout"]]
    assert len(aot.products("jit_train_step", phase="remat")) == 3
    assert attn_kernels(kept) == [["flash_fwd", "fused_dropout"],
                                  [],
                                  ["flash_bwd", "fused_dropout"]]
    assert aot.products("jit_train_step", phase="remat") == []

    B, S, H, D = TRAIN_QKV
    E = H * D
    flash = B * S * H * D * 2 + B * H * S * 8 * 4
    block = B * S * E * 2 + B * S * 4 * E * 2 + B * S * 3 * E * 2
    grew = (kept.memory_analysis().temp_size_in_bytes
            - full.memory_analysis().temp_size_in_bytes)
    reckoned = GUARD_LAYERS * (flash + block)
    assert reckoned <= grew <= 2 * reckoned, (grew, reckoned)


def _step_on_described_mesh(topo, build_for_chip, monkeypatch, hybrid,
                            model, loss_fn, batch, tpu=True, **step_kw):
    """The first build of a TrainStep (``model()``, AdamW) over the
    fleet mesh of ``hybrid`` degrees, compiled for the described 2x2
    (``tpu``: the dispatch gates are told the backend is a TPU, so the
    kernels are taken)."""
    import paddle_tpu as paddle
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import env as dist_env, fleet
    from paddle_tpu.jit import aot
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = dict(dict(dp_degree=1, mp_degree=1,
                                        pp_degree=1, sharding_degree=1),
                                   **hybrid)
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh
    described = Mesh(np.array(topo.devices[:4]).reshape(mesh.devices.shape),
                     mesh.axis_names)
    try:
        layer = model()
        step = paddle.jit.TrainStep(
            layer, loss_fn, paddle.optimizer.AdamW(
                learning_rate=1e-4, weight_decay=0.01,
                parameters=layer.parameters()),
            mesh=mesh, data_spec=P(("dp", "sharding")), **step_kw)
    except BaseException:
        fleet.reset()
        dist_env.reset()
        raise

    def on_described(a):
        spec = a.sharding.spec if isinstance(a.sharding, NamedSharding) \
            else P()
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(described, spec))

    def build(self, args):
        # the model's layout pins and flash's shard_map read the mesh of
        # the distributed env while the step is traced
        dist_env.set_mesh(described)
        raise _Built(build_for_chip(self._jitted.lower,
                                    *jax.tree.map(on_described, args)))

    monkeypatch.setattr(aot.AOTProgram, "_build", build)
    if tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ids = np.zeros((batch, TRAIN_QKV[1]), np.int32)
    try:
        with pytest.raises(_Built) as built:
            step(ids, ids)
    finally:
        monkeypatch.undo()
        fleet.reset()
        dist_env.reset()
    return built.value.args[0]


def _amp_loss(loss):
    import paddle_tpu as paddle

    def loss_fn(layer, ids, labels):
        with paddle.amp.auto_cast(level="O1"):
            return loss(layer, ids, labels)

    return loss_fn


def test_mesh_train_step_remat_runs_no_all_reduce(topo, build_for_chip,
                                                  monkeypatch):
    """`gpt2_774m.train.mesh4`'s step (774M widths at ``GUARD_LAYERS``
    layers, AMP O1, AdamW with ZeRO over ``sharding``, global B=16)
    compiled for the described 2x2 as sharding2 x mp2: the forward and
    the backward hold the tensor-parallel all-reduces, the recomputed
    layer body none, and no product either. Without the kept attention
    branch it rebuilds it through the out-projection AND its
    all-reduce: one in five of a layer body's all-reduces."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import aot
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       GPTPretrainingCriterion, gpt2_large)
    crit = GPTPretrainingCriterion()

    def model():
        paddle.seed(0)
        return GPTForPretraining(gpt2_large(num_layers=GUARD_LAYERS,
                                            use_recompute=True))

    compiled = _step_on_described_mesh(
        topo, build_for_chip, monkeypatch,
        dict(mp_degree=2, sharding_degree=2), model,
        _amp_loss(lambda layer, ids, labels: crit(layer(ids), labels)), 16,
        zero_axis="sharding")
    module = aot.index_program(compiled.as_text())
    assert module == "jit_train_step"

    def all_reduces(phase):
        return [n for n in aot.products(module, phase=phase)
                if n.startswith("all-reduce")]

    assert all_reduces("fwd") and all_reduces("bwd")
    assert aot.products(module, phase="remat") == []


def test_pipeline_stage_keeps_no_block_value(topo, build_for_chip,
                                             monkeypatch):
    """Why the fill-drain pipeline's stage remat keeps the flash
    residuals alone (``flash_residuals_policy``) and not a block's
    ``LAYER_RESIDUAL_NAMES`` too: a stage holds what it keeps for every
    tick of its schedule. GPT-2 345M's widths at 8 layers over pp=4 (two
    a stage), AMP O1, AdamW, 4 microbatches of 2 (7 ticks), compiled
    for the described 2x2 under the stage's policy and under the
    default: the default's temporaries are larger by at least the kept
    set a layer a tick (the attention branch 4 MiB, the FFN-in product
    16, the QKV product 12: 448 MiB), and read 907 MiB larger, twice
    that, as a kept stack reads in `memory_analysis()` (above).

    The stage's Mosaic kernels do not lower inside the pipeline's
    partially manual shard_map on a TPU ("cannot be automatically
    partitioned"), so the step is traced with the dispatch gates of a
    CPU backend: XLA attention and dropout, which name no flash
    residual; the block's names are there all the same."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForPretrainingPipe, gpt2_medium
    rc = importlib.import_module("paddle_tpu.distributed.fleet.utils."
                                 "recompute")
    layers, micro, batch, stages = 8, 4, 8, 4

    def model():
        paddle.seed(0)
        return GPTForPretrainingPipe(gpt2_medium(num_layers=layers),
                                     num_microbatches=micro,
                                     schedule="fill_drain")

    def temp(policy):
        with monkeypatch.context() as m:
            m.setattr(rc, "flash_residuals_policy", policy)
            compiled = _step_on_described_mesh(
                topo, build_for_chip, m, dict(pp_degree=stages),
                model, _amp_loss(
                    lambda layer, ids, labels:
                    layer.pretraining_loss(ids, labels)),
                batch, tpu=False)
        return compiled.memory_analysis().temp_size_in_bytes

    stage = temp(rc.flash_residuals_policy)
    default = temp(lambda: rc.resolve_checkpoint_policy(None))
    B, S, E = batch // micro, TRAIN_QKV[1], 1024
    a_tick = B * S * E * 2 + B * S * 4 * E * 2 + B * S * 3 * E * 2
    reckoned = layers // stages * (micro + stages - 1) * a_tick
    assert default - stage >= reckoned, (default - stage, reckoned)


# -- a model that declares its own page kinds (ISSUE 28) -----------------------

GLM_CELL = "glm52_ep16.serve.closed32_ctx8k"


@pytest.fixture(scope="module")
def glm_model():
    """GLM-5.2's chip share at the PUBLISHED widths (3.88 B parameters,
    as zeros: nothing runs), and the cell's system settings."""
    import json
    import os
    from paddle_tpu.nn import initializer
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_glm_moe_dsa",
        os.path.join(root, "benchmark", "models", "glm_moe_dsa.py"))
    fam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fam)
    with open(os.path.join(root, "benchmark", "configs",
                           "glm52_ep16.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "workloads",
                           GLM_CELL + ".json")) as f:
        system = json.load(f)
    draw = initializer.Normal.__call__
    initializer.Normal.__call__ = lambda self, shape, dtype=None: jnp.zeros(
        tuple(shape), dtype or "float32")
    try:
        model = fam.build_model(config, 0, dtype=system["weights_dtype"])
    finally:
        initializer.Normal.__call__ = draw
    return model, system


@pytest.mark.parametrize("kind", ["decode", "prefill_ctx"])
def test_glm_serving_program_fits_and_moves_no_pool(
        chip, build_for_chip, glm_model, monkeypatch, kind):
    """The same guard for the model with two page kinds, at the new
    cell's widths and engine settings: the decode program and the
    2,048-token context-prefill chunk compile for the chip, update BOTH
    pools in place (the latent pool, 5 layers of 671 MB as stored, and
    the index-key pool, 2 of 134 MB), hold no instruction of a layer's
    pool size, and keep every temporary under ISSUE 28's 2 GB — the
    chunk's scores against its context, its index scores ``[2048,
    32768]`` and its expert rows included. (A latent row of 576 values
    stored as 576 had XLA lay the pool out pages-minor and relayout all
    3 GB of it on the way in and out of every program: 3.47 GB of
    temporaries in decode. Stored as 640: 0.15 GB in decode, 0.98 GB in
    the chunk.)"""
    from paddle_tpu.serving import ServingConfig, ServingEngine
    model, system = glm_model
    kw = dict(system["engine"])
    pages = kw.pop("num_pages")
    for key in ("prefill_buckets", "batch_buckets"):
        kw[key] = tuple(kw[key])
    # the engine's own pools are a few pages (an engine lives one test)
    with flag_scope("serve_prefill_chunk", system["prefill_chunk"]):
        eng = ServingEngine(model, ServingConfig(num_pages=33, **kw))
    compiled = _compile_with_cell_pools(eng, kind, pages, chip,
                                        build_for_chip, monkeypatch)
    text = compiled.as_text()
    rows = {p.shape[-2:] for p in eng.cache.pool_args()}
    assert rows == {(16, 640), (16, 128)}
    assert _pool_sized_moves(text, pages * 16 * 128 * 2, rows) == []
    mem = compiled.memory_analysis()
    pools = sum(int(np.prod(p.shape[2:])) * p.shape[0] * pages * 2
                for p in eng.cache.pool_args())
    assert mem.alias_size_in_bytes >= pools, mem
    assert mem.temp_size_in_bytes < 2e9, mem


# -- two page lifetimes, grouped K/V heads (ISSUE 32) ---------------------------

COHERE_CELL = "command_a_plus_ep8.serve.closed24_mixed"


@pytest.fixture(scope="module")
def cohere_model():
    """Command A+'s chip share at the PUBLISHED widths (4.73 B
    parameters, as zeros: nothing runs), and the cell's system
    settings."""
    import json
    import os
    from paddle_tpu.nn import initializer
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_cohere2_moe",
        os.path.join(root, "benchmark", "models", "cohere2_moe.py"))
    fam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fam)
    with open(os.path.join(root, "benchmark", "configs",
                           "command_a_plus_ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "workloads",
                           COHERE_CELL + ".json")) as f:
        system = json.load(f)
    draw = initializer.Normal.__call__
    initializer.Normal.__call__ = lambda self, shape, dtype=None: jnp.zeros(
        tuple(shape), dtype or "float32")
    try:
        model = fam.build_model(config, 0, dtype=system["weights_dtype"])
    finally:
        initializer.Normal.__call__ = draw
    return model, system


@pytest.mark.parametrize("kind", ["decode", "prefill", "prefill_ctx"])
def test_cohere_serving_program_fits_and_moves_no_pool(
        chip, build_for_chip, cohere_model, monkeypatch, kind):
    """The three serving programs of the model with two page lifetimes,
    at the cell's widths and engine settings, compile for the chip with
    the pools at the cell's page counts as shapes (20,481 pages of the
    slot lifetime a full layer; 24 x 385 + 1 of the window lifetime a
    window layer): the decode step runs the paged kernel (128 query
    heads on 8 K/V heads, the windowed sweep) and NO gather fallback,
    the first chunk the flash kernel over grouped heads; every pool is
    updated in place, no instruction is as large as a layer's pool, and
    the temporaries leave room beside 9.47 GB of weights and 3.16 GB of
    pages on a 16 GiB chip."""
    from paddle_tpu.serving import ServingConfig, ServingEngine
    model, system = cohere_model
    kw = dict(system["engine"])
    pages = kw.pop("num_pages")
    for key in ("prefill_buckets", "batch_buckets"):
        kw[key] = tuple(kw[key])
    # the engine's own pools are small (an engine lives one test): two
    # slots, so the window lifetime is 2 x 385 + 1 pages
    kw["max_batch_slots"] = 2
    with flag_scope("serve_prefill_chunk", system["prefill_chunk"]):
        eng = ServingEngine(model, ServingConfig(num_pages=33, **kw))
    (win,) = eng.cache.windows
    assert win.pages_per_slot == 385
    slots = system["engine"]["max_batch_slots"]
    counts = {"slot": pages, win.window: slots * win.pages_per_slot + 1}
    prog, args = {"decode": eng._decode_program,
                  "prefill": lambda: eng._prefill_program(1, 2048),
                  "prefill_ctx": lambda: eng._prefill_ctx_program(1, 2048)
                  }[kind]()
    shaped = jax.tree.map(lambda a: chip(a.shape, a.dtype), args)
    pools = tuple(chip((p.shape[0], counts[kd.lifetime]) + p.shape[2:],
                       p.dtype)
                  for kd, p in zip(eng.cache.kinds, shaped[1]))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = build_for_chip(prog._jitted.lower, shaped[0], pools,
                              *shaped[2:])
    monkeypatch.undo()
    text = compiled.as_text()
    if kind != "prefill_ctx":       # the context path is an XLA loop
        _assert_kernels(text, {"decode": "paged_decode",
                               "prefill": "flash_fwd"}[kind])
    rows = {p.shape[-2:] for p in eng.cache.pool_args()}
    assert rows == {(16, 1024)}
    assert _pool_sized_moves(text, counts[win.window] * 16 * 1024 * 2,
                             rows) == []
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * 2 for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    # 16 GiB - 9.47 GB - 3.16 GB leaves 4.5 GB; a chunk's expert rows and
    # scores are the largest
    assert mem.temp_size_in_bytes < 3.0e9, mem
    print(kind, "temp", mem.temp_size_in_bytes / 1e9, "GB")


# -- a four-stream residual path, dense latent decode (ISSUE 35) -----------------

XING_CELL = "xing4_29b_ep1.serve.closed64_ctx2k"


def test_paged_mla_decode_compiles_at_the_cells_shape(chip, compile_for_chip):
    """The latent form at the Xing cell's own call: 64 slots, 32 heads'
    absorbed queries of 576 in 640 lanes, a table of 1,152 entries, ONE
    pool of six layers' 24,577 pages of 16 x 640."""
    pd = _kernel("paged_decode")
    text = compile_for_chip(
        lambda q, pool, t, p: pd.paged_mla_decode(
            q, pool, t, p, scale=0.1447, value_width=512),
        chip((64, 32, 640), BF16), chip((6 * 24577, 1, 16, 640), BF16),
        chip((64, 1152), I32), chip((64,), I32))
    _assert_kernels(text, "paged_mla_decode")


@pytest.fixture(scope="module")
def xing_model():
    """Xing4.0's published widths over ONE dense and ONE expert layer
    (all 64 experts; 1.81 B parameters, as zeros: nothing runs; the
    cell's six layers would be 9.6 GB of host memory in this process),
    and the cell's system settings."""
    import json
    import os
    from paddle_tpu.nn import initializer
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_xing4", os.path.join(root, "benchmark", "models", "xing4.py"))
    fam = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fam)
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4_29b_ep1.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "workloads",
                           XING_CELL + ".json")) as f:
        system = json.load(f)
    config.update(num_hidden_layers=2, layers_held=[1, 2])
    draw = initializer.Normal.__call__
    initializer.Normal.__call__ = lambda self, shape, dtype=None: jnp.zeros(
        tuple(shape), dtype or "float32")
    try:
        model = fam.build_model(config, 0, dtype=system["weights_dtype"])
    finally:
        initializer.Normal.__call__ = draw
    return model, system


@pytest.mark.parametrize("kind", ["decode", "prefill", "prefill_ctx"])
def test_xing_serving_program_fits_and_moves_no_pool(
        chip, build_for_chip, xing_model, monkeypatch, kind):
    """The serving programs of the four-stream family at the cell's
    widths and engine settings over a dense and an expert layer, compiled
    for the chip with the pool at the cell's page count as a shape: the
    decode step runs `paged_mla_decode` (no gather fallback), the pool is
    updated in place with no instruction of a layer's pool size, and the
    temporaries — a chunk's four streams in float32, its expert rows, its
    `[2048, 131072]` logits — leave room beside 9.59 GB of weights and
    3.02 GB of pages on a 16 GiB chip."""
    from paddle_tpu.serving import ServingConfig, ServingEngine
    model, system = xing_model
    kw = dict(system["engine"])
    pages = kw.pop("num_pages")
    for key in ("prefill_buckets", "batch_buckets"):
        kw[key] = tuple(kw[key])
    kw["prefill_buckets"] = kw["prefill_buckets"][-1:]      # the 2,048 one
    with flag_scope("serve_prefill_chunk", system["prefill_chunk"]):
        eng = ServingEngine(model, ServingConfig(num_pages=33, **kw))
    compiled = _compile_with_cell_pools(eng, kind, pages, chip,
                                        build_for_chip, monkeypatch)
    text = compiled.as_text()
    if kind == "decode":
        _assert_kernels(text, "paged_mla_decode")
    rows = {p.shape[-2:] for p in eng.cache.pool_args()}
    assert rows == {(16, 640)}
    assert _pool_sized_moves(text, pages * 16 * 640 * 2, rows) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pages * 16 * 640 * 2, mem
    # 16 GiB - 9.59 GB - 3.02 GB leaves 4.5 GB
    assert mem.temp_size_in_bytes < 3.0e9, mem
    print(kind, "temp", mem.temp_size_in_bytes / 1e9, "GB")
