"""The `lfm2_moe` family (gated short convolutions whose state a slot
rides beside GQA pages, every expert held) at a small size on the CPU,
through the engine's own forward and programs, against the plain
reference (benchmark/reference/lfm2_moe.py) on seeded weights; the
state kind's rules (position 0 reads zeros, a padded tail and a padded
row move nothing, a reused or preempted slot starts fresh); what the
engine refuses with a state kind; and the pin that holds the Xing
family, which shares the expert layer, to the programs it traced to."""
import hashlib
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag_scope
from paddle_tpu.incubate.moe import held_experts_ffn, sigmoid_topk_routing
from paddle_tpu.models import lfm2_moe
from paddle_tpu.models.glm_moe_dsa import _rotary, _rotary_half
from paddle_tpu.serving import ServingConfig, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/lfm2_moe.py", "ref_lfm2_moe")

CHUNK, PAGE = 8, 4
IDS = np.random.default_rng(5).integers(0, 256, (27,)).astype(np.int32)


def sizes_of(cfg):
    """What the reference needs beside the weights."""
    return {k: getattr(cfg, k) for k in (
        "num_heads", "num_kv_heads", "num_experts_per_tok",
        "routed_scaling_factor", "router_eps", "experts_held",
        "layer_types", "mlp_layer_types", "rope_theta", "rms_norm_eps")}


def build(slots=2, chunk=CHUNK, buckets=(CHUNK,), batch=(1,), **kw):
    """Weights of N(0, 0.2), ten times the published range: at these
    widths a layer's output is then as large as the row it is added to,
    as it is at the published widths with 0.02, so the conv state shows."""
    paddle.seed(3)
    cfg = lfm2_moe.lfm2_moe_tiny(**{"initializer_range": 0.2, **kw})
    model = lfm2_moe.Lfm2MoeForCausalLM(cfg)
    with flag_scope("serve_prefill_chunk", chunk):
        eng = ServingEngine(model, ServingConfig(
            max_batch_slots=slots, block_size=PAGE, max_context_len=64,
            prefill_buckets=buckets, batch_buckets=batch))
    return cfg, model, eng


class Slots:
    """Programs through `eng._forward` over the engine's own cache
    (pages from its allocator, the state array's rows of the slots), and
    what each slot saw: its rows' chosen experts, a layer, and the logits
    of each program's last real row."""

    def __init__(self, eng, model, seqs):
        self.eng, self.model, self.seqs = eng, model, seqs
        self.pools = eng.cache.pool_args()
        n_moe = sum(t == "sparse" for t in model.cfg.mlp_layer_types)
        k = model.cfg.num_experts_per_tok
        self.routing = [[np.zeros((len(s), k), np.int32)
                         for _ in range(n_moe)] for s in seqs]
        self.rows = [[] for _ in seqs]
        self.logits = [[] for _ in seqs]
        self._jits = {}
        for j, s in enumerate(seqs):
            assert eng.cache.alloc_slot(j, len(s))

    def run(self, rows, width, starts, lens, ctx=False, ids=None,
            record=True):
        """One program of `len(rows)` rows of `width` positions: row i is
        slot `rows[i]` (None: a padded row) at `starts[i]` with `lens[i]`
        real positions. Returns the logits [B, width, V]."""
        B = len(rows)
        if ids is None:
            ids = np.zeros((B, width), np.int32)
            for i, j in enumerate(rows):
                if j is not None:
                    a = starts[i]
                    ids[i, :lens[i]] = self.seqs[j][a:a + lens[i]]
        out, self.pools, topk = self.forward(ctx)(
            self.eng.params, jnp.asarray(ids), self.pools,
            self.eng.cache.table_array(rows),
            jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32))
        out = np.asarray(out)
        for i, j in enumerate(rows):
            if j is None or not record:
                continue
            a, n = starts[i], lens[i]
            for mine, theirs in zip(self.routing[j], topk):
                mine[a:a + n] = np.asarray(theirs[i])[:n]
            self.rows[j].append(a + n - 1)
            self.logits[j].append(out[i, n - 1])
        return out

    def forward(self, ctx):
        """The engine's forward, jitted (a kernel interpreted on the CPU
        is traced once a shape, not once a call), returning the logits,
        the pools and every expert layer's chosen experts."""
        if ctx not in self._jits:
            def fwd(params, ids, pools, table, pos, lens):
                self.model.taps = {}
                try:
                    out, pools, _ = self.eng._forward(
                        params, ids, pools, table, pos, ctx=ctx, lens=lens)
                    return out, pools, self.model.taps["router_topk"]
                finally:
                    self.model.taps = None
            self._jits[ctx] = jax.jit(fwd)
        return self._jits[ctx]

    def decode(self, live, at):
        """A decode step at the engine's decode shape: slot j of `live`
        at position `at[j]`, every other row padded."""
        slots = self.eng.config.max_batch_slots
        rows = [j if j in live else None for j in range(slots)]
        starts = [at[live.index(j)] if j in live else 0 for j in range(slots)]
        return self.run(rows, 1, starts, [1] * slots)

    def chunks(self, j, plen, chunk=CHUNK):
        for a in range(0, plen, chunk):
            n = min(chunk, plen - a)
            self.run([j], chunk, [a], [n], ctx=a > 0)

    def against_reference(self, j, **how):
        want = ref.forward(self.eng.params, self.seqs[j][:self.rows[j][-1] + 1],
                           sizes_of(self.model.cfg), rows=self.rows[j],
                           forced={"routing": [r[:self.rows[j][-1] + 1]
                                               for r in self.routing[j]]},
                           **how)
        return np.stack(self.logits[j]), want

    def state(self):
        return np.asarray(self.pools[-1])


@pytest.mark.parametrize("plen,kernel", [
    pytest.param(21, True, marks=pytest.mark.pallas, id="padded-tail-kernel"),
    pytest.param(17, False, id="one-row-chunk-gathered-xla")])
def test_prefill_chunks_and_decode_match_the_reference(plen, kernel):
    """A prompt in chunks of 8 (the plain path at position 0, the context
    path after; 21 = 8 + 8 + 5: a padded tail behind the state write;
    17 = 8 + 8 + 1: a chunk of ONE real row, which keeps the older
    state's last entry), then decode steps, through the interpreted
    paged kernel or the blocked context read, against the reference's ONE
    causal forward with no state: the last real row's logits of every
    program (three decode steps), every row's chosen experts."""
    cfg, model, eng = build()
    try:
        with flag_scope("pallas_paged_decode", kernel):
            s = Slots(eng, model, [IDS[:plen + 3]])
            s.chunks(0, plen)
            for t in range(plen, plen + 3):
                s.decode([0], [t])
        got, want = s.against_reference(0)
    finally:
        eng.shutdown()
    np.testing.assert_allclose(got, np.asarray(want["logits"]), rtol=2e-4,
                               atol=2e-5)
    for j in want["routing_judged"]:
        assert bool(j["sizes_equal"]) and float(j["min_overlap"]) == 1.0


def test_a_padded_tail_and_a_padded_row_move_no_state():
    """The same 5 real rows in an 8-row bucket behind two different
    tails, beside a padded row, leave the same state and the same
    logits; the padded row writes the scratch row alone."""
    cfg, model, eng = build(slots=3, batch=(1, 2))
    try:
        s = Slots(eng, model, [IDS, IDS[::-1].copy()])
        s.chunks(0, 8)
        s.chunks(1, 8)
        pools0, before = s.pools, s.state()
        outs, states = [], []
        for tail in (0, 77):
            s.pools = pools0
            ids = np.full((2, 8), tail, np.int32)
            ids[0, :5] = IDS[8:13]
            outs.append(s.run([0, None], 8, [8, 0], [5, 1], ctx=True,
                              ids=ids, record=False)[0, 4])
            states.append(s.state())
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(outs[0], outs[1])
    for st in states:
        np.testing.assert_array_equal(st[:, 0], states[0][:, 0])
        assert not np.array_equal(st[:, 0], before[:, 0])
        # slot 1 and the unused slot 2 keep what they held
        np.testing.assert_array_equal(st[:, 1:3], before[:, 1:3])


def test_four_prompts_of_four_lengths_in_one_prefill():
    """`nb = 4`: four slots' prompts of 16, 11, 5 and 1 tokens in ONE
    plain prefill of 4 x 16 rows, then three decode steps with the four
    live, each slot against the reference over its own sequence."""
    cfg, model, eng = build(slots=4, chunk=16, buckets=(16,), batch=(4,))
    lens = [16, 11, 5, 1]
    rng = np.random.default_rng(9)
    seqs = [rng.integers(0, 256, (n + 3,)).astype(np.int32) for n in lens]
    try:
        s = Slots(eng, model, seqs)
        s.run([0, 1, 2, 3], 16, [0] * 4, lens)
        for step in range(3):
            s.decode([0, 1, 2, 3], [n + step for n in lens])
        pairs = [s.against_reference(j) for j in range(4)]
    finally:
        eng.shutdown()
    for got, want in pairs:
        np.testing.assert_allclose(got, np.asarray(want["logits"]),
                                   rtol=2e-4, atol=2e-5)


def test_a_reused_slot_equals_a_fresh_one():
    """A slot that held another request's state (its pages and its conv
    rows) and was freed: the next request's first chunk at position 0
    reads zeros, not the old state, bit for bit what a fresh cache
    gives; no write resets the slot."""
    cfg, model, eng = build()
    other = IDS[::-1].copy()
    try:
        s = Slots(eng, model, [other])
        s.chunks(0, 16)
        s.decode([0], [16])
        eng.cache.free_slot(0)
        held = s.state()
        assert np.abs(held[:, 0]).max() > 0
        s.seqs, s.rows, s.logits = [IDS], [[]], [[]]
        assert eng.cache.alloc_slot(0, len(IDS))
        s.chunks(0, 16)
        reused = np.stack(s.logits[0])
        fresh = Slots(eng, model, [])
        fresh.pools = eng.cache.pool_args()      # never written: zeros
        fresh.seqs, fresh.rows, fresh.logits = [IDS], [[]], [[]]
        fresh.routing = s.routing
        fresh.chunks(0, 16)
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(reused, np.stack(fresh.logits[0]))


def test_a_preempted_request_continues_token_exact():
    """Two requests on a pool that cannot hold both to the end: the
    newer one is preempted and prefilled again from position 0 (its
    prompt and what it had generated, in chunks), and both streams are
    the tokens an engine with room gives."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(2, 250, (10,)).astype(np.int32)
               for _ in range(2)]
    outs = []
    for pages in (10, 40):
        paddle.seed(3)
        model = lfm2_moe.Lfm2MoeForCausalLM(
            lfm2_moe.lfm2_moe_tiny(initializer_range=0.2))
        with flag_scope("serve_prefill_chunk", CHUNK):
            eng = ServingEngine(model, ServingConfig(
                max_batch_slots=2, block_size=PAGE, max_context_len=24,
                num_pages=pages, prefill_buckets=(CHUNK,),
                batch_buckets=(1,)))
        try:
            outs.append(eng.generate(prompts, max_new_tokens=12))
            preempted = eng.stats()["preemptions"]
            assert eng.cache.allocator.pages_in_use == 0
        finally:
            eng.shutdown()
        assert (preempted >= 1) == (pages == 10)
    for tight, roomy in zip(*outs):
        np.testing.assert_array_equal(tight, roomy)


def test_the_controls_are_not_the_model():
    """What the cell's comparison has to refuse, at the small size:
    convolutions started from zero at every program move the logits by
    far more than any rounding, and in bfloat16 the router's scores
    move by a rounding's worth."""
    cfg, model, eng = build()
    eng.shutdown()
    sz, rows = sizes_of(cfg), [7, 15, 20, 21, 22]
    want = ref.forward(eng.params, IDS[:23], sz, rows=rows)
    reset = ref.forward(eng.params, IDS[:23], sz, rows=rows,
                        conv_from=[0, 8, 16, 21, 22])
    err = np.abs(np.asarray(reset["logits"] - want["logits"]))
    scale = np.abs(np.asarray(want["logits"])).max()
    assert err[0].max() == 0.0                    # the first chunk is whole
    assert err[1:].max(axis=-1).min() / scale > 0.05, err.max(-1) / scale
    low = ref.forward(eng.params, IDS[:23], sz, rows=rows,
                      dtype=jnp.bfloat16)
    p = low["router_probe"][-1]
    anew = ref.router_scores_of(p["x"], eng.params["layers.4.moe.router.weight"])
    off = float(jnp.max(jnp.abs(p["scores"].astype(jnp.float32) - anew)))
    assert 1e-4 < off < 0.1, off


def test_all_experts_held_is_the_sum_of_four_shares():
    """The guide's share test with this family's deployment: what ONE
    chip computes with all 8 experts held equals the sum of what four
    chips of 2 would, and the reference's uncut routed part (the
    renormalization's eps included)."""
    cfg = lfm2_moe.lfm2_moe_tiny()
    rng = np.random.default_rng(2)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    D, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
    x = n(13, D)
    weights = {"router.weight": n(D, E), "router.bias": n(E) * 0.1,
               "experts.w_in": n(E, D, 2 * F), "experts.w_out": n(E, F, D)}
    routing = sigmoid_topk_routing(
        x, weights["router.weight"], weights["router.bias"],
        cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.router_eps)
    whole, tokens, here = held_experts_ffn(
        x, routing, weights["experts.w_in"], weights["experts.w_out"], 0)
    assert bool(jnp.all(here)) and int(tokens.sum()) == 13 * 4
    parts = sum(held_experts_ffn(
        x, routing, weights["experts.w_in"][f:f + 2],
        weights["experts.w_out"][f:f + 2], f)[0] for f in range(0, E, 2))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(parts),
                               rtol=1e-5, atol=1e-6)
    uncut, _, _ = ref.routed_part(x, weights, "", sizes_of(cfg),
                                  experts=(0, E))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)


def test_the_routing_eps_is_added_to_the_chosen_sum():
    x = jnp.asarray(np.random.default_rng(4).normal(size=(5, 16)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(6).normal(size=(16, 8)) * 0.3,
                    jnp.float32)
    bias = jnp.zeros((8,), jnp.float32)
    plain = sigmoid_topk_routing(x, w, bias, 4)
    eps = sigmoid_topk_routing(x, w, bias, 4, scale=1.0, eps=0.5)
    chosen = jnp.take_along_axis(plain.scores, plain.idx, -1)
    np.testing.assert_allclose(np.asarray(plain.gates.sum(-1)), 1.0,
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(eps.gates),
        np.asarray(chosen / (chosen.sum(-1, keepdims=True) + 0.5)),
        rtol=1e-6)
    # eps 0 adds nothing to the program: the families without one trace
    # to what they did
    trace = lambda **kw: str(jax.make_jaxpr(
        lambda a: sigmoid_topk_routing(a, w, bias, 4, **kw))(x))
    assert trace() == trace(eps=0.0) != trace(eps=1e-6)


def test_half_split_rotary_against_hand_values():
    """dims i and i + d/2 turn as a pair by position x theta^(-2i/d):
    at d = 4, theta 100, position 3, pair 0 turns by 3 rad and pair 1 by
    0.3; the same rotation as the interleaved form on the dims in
    another order, and the reference's `rotate_half`."""
    x = jnp.asarray([[[[1.0, 2.0, 3.0, 4.0]]]], jnp.float32)   # [1,1,1,4]
    pos = jnp.asarray([[3]], jnp.int32)
    got = np.asarray(_rotary_half(x, pos, 100.0))[0, 0, 0]
    c0, s0, c1, s1 = math.cos(3), math.sin(3), math.cos(0.3), math.sin(0.3)
    want = [1 * c0 - 3 * s0, 2 * c1 - 4 * s1, 1 * s0 + 3 * c0,
            2 * s1 + 4 * c1]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    perm = [0, 2, 1, 3]                 # halves -> interleaved pairs
    inter = np.asarray(_rotary(x[..., perm], pos, 100.0))[0, 0, 0]
    np.testing.assert_allclose(inter[perm], got, rtol=1e-6)
    rows = np.random.default_rng(1).normal(size=(6, 3, 8)).astype(np.float32)
    mine = _rotary_half(jnp.asarray(rows)[None], jnp.arange(6)[None], 1e6)
    np.testing.assert_allclose(np.asarray(mine)[0], np.asarray(
        ref.rotate_half(jnp.asarray(rows), 1e6)), rtol=1e-5, atol=1e-6)


# -- what the cache holds and the engine counts --------------------------------------

def test_the_state_kind_beside_the_pages():
    cfg = lfm2_moe.Lfm2MoeConfig(
        layer_types=("conv", "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "conv"),
        mlp_layer_types=("dense",) + ("sparse",) * 8)
    k, v = cfg.page_kinds()
    assert (k.name, k.width, k.layers, k.heads) == ("k", 512, (1, 5), 8)
    (conv,) = cfg.state_kinds()
    assert (conv.name, conv.shape, conv.layers)         == ("conv", (2, 2048), (0, 2, 3, 4, 6, 7, 8))
    _, _, eng = build(slots=3)
    try:
        pools = eng.cache.pool_args()
        assert len(pools) == 3 and pools[-1].shape == (4, 4, 2, 64)
        assert eng.cache.state_bytes_per_slot() == 4 * 2 * 64 * 4
        tbl, slots = eng.cache.table_array([2, None, 0])
        assert np.asarray(slots).tolist() == [2, 3, 0]
        assert np.asarray(tbl).shape == (3, 16)
        assert [a.shape for a in eng.cache.table_like(2)] == [(2, 16), (2,)]
    finally:
        eng.shutdown()


def test_decode_steps_count_state_rows_and_experts_read():
    """21 tokens in chunks of 8 then five decode steps: one fresh row,
    two rows of context chunks and five decode rows from a carried
    state; every expert layer reads the 4 experts its one row chose."""
    cfg, model, eng = build()
    try:
        eng.generate([IDS[:21].tolist()], max_new_tokens=6)
        counters = eng._stats["model_counters"]
    finally:
        eng.shutdown()
    assert counters["serve_conv_state_fresh_total"] == 1
    assert counters["serve_conv_state_carried_total{program=prefill_ctx}"] == 2
    assert counters["serve_conv_state_carried_total{program=decode}"] == 5
    assert counters["serve_moe_experts_read_total"] == 5 * 4 * 4
    assert counters["serve_moe_skipped_pairs_total"] == 0


@pytest.mark.parametrize("flag,value,names", [
    ("serve_prefix_cache", True, "prefix hit maps pages"),
    ("serve_spec_k", 2, "truncate_slot"),
    ("serve_kv_quant", "int8", "cache dtype"),
])
def test_refused_with_a_state_kind_by_name(flag, value, names):
    with flag_scope(flag, value):
        with pytest.raises(ValueError) as ei:
            build()
    assert f"FLAGS_{flag} with a state kind" in str(ei.value)
    assert names in str(ei.value)


def test_a_serving_mesh_is_refused_with_a_state_kind():
    from paddle_tpu.distributed.spmd import make_mesh
    paddle.seed(3)
    model = lfm2_moe.Lfm2MoeForCausalLM(lfm2_moe.lfm2_moe_tiny())
    with pytest.raises(ValueError, match="serving mesh with a state kind"):
        ServingEngine(model, ServingConfig(
            max_batch_slots=2, block_size=PAGE, max_context_len=64,
            prefill_buckets=(CHUNK,), batch_buckets=(1,),
            mesh=make_mesh({"mp": 2}, jax.devices()[:2])))


# -- the family that shares the expert layer traces to what it was ------------------

#: sha256 of `str(jaxpr)` of the tiny Xing engine's three serving
#: programs (chunk 8, page 4, two slots, float32), taken from the commit
#: BEFORE the expert layer gained its optional shared expert, the
#: routing's eps and the experts-read count, and the engine its state
#: kinds (commit 20e1ae7e): both are off for this family, so it traces
#: to what it was, equation for equation. A change of jax's printer
#: would move the hashes with no change here: take them anew from that
#: commit then.
_PR39_XING_JAXPR = {
    "prefill": "f8c14c6ed4afe49dfe14028bc9c6ab9caa5e283c537377f34ce24cf59221f79e",
    "prefill_ctx": "fb07d8eaf0866f239f45da86e593c995cea6c56a6ad5b6de25e4e28e76c12a6e",
    "decode": "17f640b5e01bb060ca3b3ff052e3de342d2162e7653a7b80fc2dac84f146f5c8"}


@pytest.mark.pallas
def test_the_xing_programs_trace_as_before():
    from paddle_tpu.models.xing4 import Xing4ForCausalLM, xing4_tiny
    paddle.seed(3)
    with flag_scope("serve_prefill_chunk", CHUNK):
        eng = ServingEngine(Xing4ForCausalLM(xing4_tiny()), ServingConfig(
            max_batch_slots=2, block_size=PAGE, max_context_len=64,
            prefill_buckets=(CHUNK,), batch_buckets=(1,)))
    try:
        for kind, (prog, args) in (
                ("prefill", eng._prefill_program(1, CHUNK)),
                ("prefill_ctx", eng._prefill_ctx_program(1, CHUNK)),
                ("decode", eng._decode_program())):
            text = str(prog._jitted.trace(*args).jaxpr)
            assert hashlib.sha256(text.encode()).hexdigest() \
                == _PR39_XING_JAXPR[kind], kind
    finally:
        eng.shutdown()
