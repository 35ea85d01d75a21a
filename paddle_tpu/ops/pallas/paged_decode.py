"""Pallas paged flash-decode attention (TPU serving hot path).

Decode-step attention over the block-structured KV pool of
:mod:`paddle_tpu.serving.kv_cache` — the kernel form of PagedAttention
(vLLM, SOSP '23) and the TPU-native replacement for the reference's
fused decode attention (reference: fused_multi_transformer_op.cu's
masked attention over the growing cache).

The XLA fallback (``models/gpt.py _paged_attention``) materializes the
slot-contiguous context first: ``gather_pages`` writes a dense
``[B, MB*bs, H, D]`` copy of every slot's pages to HBM, the masked SDPA
reads it back, and most of that traffic is wasted — a slot at position
``p`` only owns ``ceil(p/bs)`` of its ``MB`` table entries, the rest
point at the scratch page. Here the block table IS the access path,
and the sweep follows it only as far as the slot lives. The grid is
``(slots, G)``: a grid step is one slot (and head group), the pools stay
in HBM (``memory_space=HBM``: no block of them is pipelined), and the
kernel copies pages itself. Slot ``b`` at position ``p`` owns
``n = p // bs + 1`` table entries; its step runs ``ceil(n / k)``
iterations of a ``fori_loop`` whose trip count comes from the
scalar-prefetched ``pos``, each over ``k`` pages (``_pages_per_step``:
what fits 256 KB a pool, 8 bf16 pages of 16 x 1024) copied by ``k``
``make_async_copy`` a pool into one of two VMEM buffers, so that group
``i + 1`` (or the NEXT grid step's first group) is in flight while group
``i`` is scored as one ``[k*bs, F]`` tile. Entries past the slot's last
live one are never read: a last group that runs past ``n`` copies the
last live page again and its columns are masked, as a live page's tail
is. Why not a grid over the table's entries, a page a step through a
BlockSpec index map: at 345M's serve cell that is 98,304 grid steps a
decode step, 70% of them past the slots' positions at 0.10 us each and
the live ones 0.54 us for a 64 KB transfer — 23.0 ms where this walk
takes 4.7 (``PERF.md`` section 6, PR 31). The grid is sequential
(``arbitrary``): a step hands the next one its first group.

The pool is the engine's own array, ``[N, G, bs, (H/G)*D]``
(:mod:`paddle_tpu.serving.kv_cache`): a page block is
``(1, 1, bs, (H/G)*D)`` with the heads of a token row fused into the
minor dim, so the block the kernel DMAs is whole ``(8, 128)`` tiles
(345M: 16 rows x 1024 lanes) and the layout the kernel wants is the
layout the array already has — XLA relays nothing around the call. (A
``[bs, H, 64]`` page pads its 64 lanes to 128: twice the DMA, and a
pool-sized relayout copy on each side of the kernel.) ``N`` counts the
pages of ALL layers; the caller adds the layer's first page to the table.

Online softmax over the sweep's groups (running (m, l) row stats per
head, f32 accumulation), additive key masking by per-slot position — the same
math as the fallback's ``cols <= pos`` mask, so decode stays TOKEN-EXACT
against the dense path (pinned in tests/test_pallas_kernels.py).

All heads of a page ride one program, on the MXU: the query is laid out
block-diagonally once a slot (row ``h`` holds ``q[h]`` in head ``h``'s
``D`` lanes and zeros elsewhere), so ``q_bd @ k^T`` is the per-head score
``[H/G, k*bs]`` with no reshape of the fused dim, and ``p @ v``
``[H/G, (H/G)*D]`` holds head ``h``'s weighted value sum in head ``h``'s
lanes of row ``h`` (the other lanes are dropped when the slot finishes).
bf16 pools under a bf16 query multiply in bf16 passes with f32
accumulation and lose nothing by it: a product of two bf16 values is
exact in f32, and the f32 probabilities go through ``p @ v`` as three
bf16 parts (``_probs_dot``), so scores, probabilities and the weighted
sum have the precision of f32 arithmetic on values read from bf16.
Everything else multiplies in f32 at the highest precision. The page
size ``bs`` set by ``ServingConfig.block_size`` is the KV block size —
there is no separate kernel block knob.

The LATENT form (:func:`paged_mla_decode`; trace name
``paged_mla_decode``) is the same sweep over ONE pool: a row is MLA's
``[c_kv ; rope(k_r)]`` of a position, the keys of every head and, in its
first ``value_width`` lanes, their values. A page is copied once a
group, each head's absorbed query is a whole row (no block diagonal:
every head reads every lane), scores are ``q @ k^T`` ``[H, k*bs]`` and
the accumulator ``p @ k[:, :value_width]`` ``[H, value_width]``.

``G > 1`` is the layout of a pool sharded over an ``mp`` mesh. Under a
mesh ``kernel_enabled`` routes decode to the XLA fallback, so until a
per-shard call exists only the tests run the kernel with two groups.

Tests run this kernel on CPU via the Pallas interpreter
(FLAGS_pallas_interpret; the ``pallas`` pytest marker).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["paged_decode_attention", "paged_decode_attention_quant",
           "paged_mla_decode"]

NEG_INF = -1e30


def _head_lanes(hg, D, kv_group=1):
    """``[H/G, F]`` bool: lane ``f`` of the fused dim belongs to K/V head
    ``f // D``, which query heads ``kv_group * (f // D) ..`` read — one
    each when ``kv_group`` is 1 (compares, no vector division by ``D``)."""
    F = hg // kv_group * D
    lane = jax.lax.broadcasted_iota(jnp.int32, (hg, F), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (hg, F), 0)
    if kv_group > 1:
        head = head // kv_group
    return (lane >= head * D) & (lane < (head + 1) * D)


def _probs_dot(pr, v, prec):
    """``pr @ v`` with the f32 probabilities at their full precision.
    Against bf16 values the probabilities split into three bf16 parts
    (8 + 8 + 8 = the 24 bits of an f32 mantissa): every product is exact
    in f32, so three one-pass products, the small parts summed first,
    give what an f32 multiply on the VPU gives, in half the passes of
    f32 operands at the highest precision."""
    if v.dtype != jnp.bfloat16:
        return jnp.dot(pr, v, precision=prec,
                       preferred_element_type=jnp.float32)

    def dot(part):
        return jnp.dot(part, v, preferred_element_type=jnp.float32)

    hi = pr.astype(jnp.bfloat16)
    rest = pr - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return (dot(lo) + dot(mid)) + dot(hi)


#: bytes of K (and as many of V) one step of the sweep copies from a pool
#: into one of its two VMEM buffers. A page of 16 x 1024 bf16 values is
#: 32 KB, 0.04 us of HBM time, a fraction of what starting and awaiting a
#: step costs: a step takes as many pages as fit here.
_STEP_BYTES = 256 * 1024


def _pages_per_step(bs, F, itemsize, MB):
    """``k``: the pages of a pool that one step of the sweep copies and
    scores as ONE ``[k*bs, F]`` tile: what fits ``_STEP_BYTES``, at most
    the table's width (pages of 16 x 1024: bf16 8, f32 4, int8 16)."""
    return max(1, min(MB, _STEP_BYTES // (bs * F * itemsize)))


def _decode_kernel(tbl_ref, pos_ref, *refs, scale, bs, hg, D, k,
                   quant=False, kv_group=1, windowed=False, latent=0):
    # the slots' first visible positions (a third prefetched scalar array,
    # under a window only), the query, the pools in HBM (int8 pools ride with their scale pools:
    # k, k_scales, v, v_scales), the output row, a [2, k, bs, width]
    # buffer pair a pool, a DMA semaphore a (pool, buffer), and the
    # buffer this grid step's first group was copied into. The latent
    # form (`latent` > 0: the width of the values) has ONE pool, whose
    # rows are the keys of every head and whose first `latent` lanes are
    # the values: a page is copied once and read as both
    first_ref, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    q_ref, refs = refs[0], refs[1:]
    n = 1 if latent else 4 if quant else 2
    hbm, o_ref, bufs = refs[:n], refs[n], refs[n + 1:2 * n + 1]
    sems, slot_ref = refs[2 * n + 1:]
    b, g = pl.program_id(0), pl.program_id(1)
    G = pl.num_programs(1)
    step, steps = b * G + g, pl.num_programs(0) * G
    n_kv = hg // kv_group
    MB, F = tbl_ref.shape[1], n_kv * D
    if latent:
        F = latent                   # the accumulator's width: the values'
    cdt = jnp.bfloat16 if (q_ref.dtype == hbm[0].dtype == jnp.bfloat16) \
        else jnp.float32
    # one bf16 MXU pass is exact for bf16 x bf16; f32 operands need all
    # of their mantissa
    prec = (jax.lax.Precision.DEFAULT if cdt == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)

    def last_page(bb):
        """Index of slot ``bb``'s last live table entry."""
        return jnp.minimum(pos_ref[bb] // bs, MB - 1)

    def first_page(bb):
        """Index of the first table entry slot ``bb``'s sweep reads: the
        page of its first visible position under a window, else 0."""
        return first_ref[bb] // bs if windowed else 0

    def start_group(bb, gg, i, slot):
        """Start the copies of pages ``i*k .. i*k+k-1`` of the sweep of
        (slot ``bb``, head group ``gg``) into buffer ``slot``. Entries
        past the slot's last live one are clamped to it: the sweep never
        reads a page the slot does not own, and the columns are masked
        below."""
        last = last_page(bb)
        for t in range(k):
            entry = i * k + t
            if windowed:
                entry = first_page(bb) + entry
            page = tbl_ref[bb, jnp.minimum(entry, last)]
            for j in range(n):
                pltpu.make_async_copy(hbm[j].at[page, gg],
                                      bufs[j].at[slot, t],
                                      sems.at[j, slot]).start()

    def wait_group(slot):
        for j in range(n):
            for t in range(k):
                pltpu.make_async_copy(hbm[j].at[0, g], bufs[j].at[slot, t],
                                      sems.at[j, slot]).wait()

    @pl.when(step == 0)
    def _first():
        slot_ref[0] = 0
        start_group(b, g, 0, 0)

    p = pos_ref[b]
    # ceil(pages of the sweep / k)
    groups = ((last_page(b) - first_page(b)) // k + 1 if windowed
              else last_page(b) // k + 1)
    slot0 = slot_ref[0]
    # the query, block-diagonal: row h = q[h] in the lanes of the K/V
    # head it reads (its own when kv_group is 1)
    if latent:
        # every head's absorbed query reads the whole row: no diagonal
        q_bd = q_ref[0, 0].astype(cdt)                   # [H, width]
    else:
        lanes = _head_lanes(hg, D, kv_group)
        q_row = q_ref[0, 0].astype(jnp.float32)
        if kv_group > 1:
            # [H/G, D] rows, repeated under every K/V head's lanes
            q_row = jnp.concatenate([q_row] * n_kv, axis=1)
        q_bd = jnp.where(lanes, q_row, 0.0).astype(cdt)

    def sweep(i, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + i) % 2

        # the next group is in flight while this one is computed: the
        # slot's own next, or the first of the next grid step's
        @pl.when(i + 1 < groups)
        def _next_group():
            start_group(b, g, i + 1, 1 - slot)

        @pl.when((i + 1 == groups) & (step + 1 < steps))
        def _next_slot():
            start_group((step + 1) // G, (step + 1) % G, 0, 1 - slot)

        wait_group(slot)

        def tile(j, dtype):
            """Pool ``j``'s k pages as one ``[k*bs, width]`` tile."""
            return bufs[j][slot].astype(dtype).reshape(k * bs, -1)

        if latent:
            kk = tile(0, cdt)
            vv = kk[:, :latent]
        elif quant:
            # identical math to kv_cache.dequant_pages (each int8 value
            # times its head's scale, in f32), so the kernel stays
            # token-exact against the XLA gather fallback; the scales
            # [k*bs, H/G] spread over their heads' lanes exactly: one
            # nonzero term a lane
            spread = lanes.astype(jnp.float32)
            kk, vv = (tile(j, cdt) * jnp.dot(
                tile(j + 1, jnp.float32)[:, :hg], spread, precision=prec)
                for j in (0, 2))
        else:
            kk, vv = tile(0, cdt), tile(1, cdt)
        # s[h, c] = q[h] . k[c, head h's lanes]
        s = jax.lax.dot_general(
            q_bd, kk, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale  # [H/G, k*bs]
        cols = i * (k * bs) + jax.lax.broadcasted_iota(
            jnp.int32, (hg, k * bs), 1)
        # slot b sees written positions 0..p (current token included) —
        # identical to the fallback's additive key mask; under a window,
        # from its first visible position on
        seen = cols <= p
        if windowed:
            cols = first_page(b) * bs + cols
            seen = (cols <= p) & (cols >= first_ref[b])
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        pr = jnp.exp(s - shift)                          # masked -> 0
        alpha = jnp.exp(m_prev - shift)
        l_new = alpha * l_prev + jnp.sum(pr, axis=1, keepdims=True)
        # acc[h] += pr[h] @ v: head h's sum lands in head h's lanes
        return m_new, l_new, acc * alpha + _probs_dot(pr, vv, prec)

    _, l, acc = jax.lax.fori_loop(
        0, groups, sweep,
        (jnp.full((hg, 1), NEG_INF, jnp.float32),
         jnp.zeros((hg, 1), jnp.float32), jnp.zeros((hg, F), jnp.float32)))
    slot_ref[0] = (slot0 + groups) % 2
    safe_l = jnp.where(l == 0.0, 1.0, l)                 # inactive slot
    if latent:
        o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)
        return
    out = jnp.where(lanes, acc / safe_l, 0.0)
    if kv_group > 1:
        # row h keeps the D lanes of its K/V head: the other heads' lane
        # blocks of the row are zero
        o_ref[0, 0] = functools.reduce(jnp.add, [
            out[:, j * D:(j + 1) * D] for j in range(n_kv)]
        ).astype(o_ref.dtype)
    else:
        o_ref[0, 0] = jnp.sum(out, axis=0,
                              keepdims=True).astype(o_ref.dtype)


def _paged_decode(q, pools, block_table, pos, *, scale, quant, name,
                  first=None, latent=0):
    """The one pallas_call behind the entry points. ``pools`` is
    ``(k, v)``, ``(k, k_scales, v, v_scales)`` or, in the latent form,
    ``(pool,)``; the number of query heads a K/V head follows from ``q``
    and the pools' row width."""
    B, H, D = q.shape
    _, G, bs, F = pools[0].shape
    hg = H // G
    kv_group = 1 if latent else hg * D // F
    if latent:
        if G != 1 or D != F or not 0 < latent <= F:
            raise ValueError(
                f"paged_mla_decode: queries of {D} and values of {latent} "
                f"over pool rows of {F} in {G} groups")
    elif kv_group < 1 or hg % kv_group or hg // kv_group * D != F:
        raise ValueError(
            f"paged_decode: {H} query heads of {D} over {G} groups do not "
            f"divide pool rows of {F}")
    if quant and kv_group > 1:
        raise NotImplementedError(
            "paged_decode over int8 pages with fewer K/V heads than query "
            "heads")
    windowed = first is not None
    k = _pages_per_step(bs, F, pools[0].dtype.itemsize,
                        block_table.shape[1])
    if quant:
        # a copy out of HBM moves whole 128-lane tiles and a row of
        # scales is H/G wide: the scale pools go in padded to a tile (a
        # pool-sized copy a call, which lane-dense scale pools in
        # kv_cache would end; no cell runs int8 pages)
        lanes = ((0, 0),) * 3 + ((0, -hg % 128),)
        pools = (pools[0], jnp.pad(pools[1], lanes),
                 pools[2], jnp.pad(pools[3], lanes))

    # a slot's query and output: one fused row when a query head has a
    # K/V head of its own, [H/G, D] rows when several share one
    rows = (1, F) if kv_group == 1 else (hg, D)
    if latent:
        rows = (hg, F)
    out_rows = (hg, latent) if latent else rows

    def row(rows=rows):
        return pl.BlockSpec((1, 1) + rows, lambda b, g, *_: (b, g, 0, 0))

    scalars = (block_table.astype(jnp.int32), pos.astype(jnp.int32))
    if windowed:
        scalars += (first.astype(jnp.int32),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),         # table, pos(, first)
        grid=(B, G),
        # the pools stay where they are: the kernel copies the pages
        # the table names, and nothing else of them moves
        in_specs=[row()] + [pl.BlockSpec(memory_space=pltpu.HBM)
                            for _ in pools],
        out_specs=row(out_rows),
        scratch_shapes=[pltpu.VMEM((2, k, bs, a.shape[-1]), a.dtype)
                        for a in pools] + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), bs=bs,
                          hg=hg, D=D, k=k, quant=quant, kv_group=kv_group,
                          windowed=windowed, latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G) + out_rows, q.dtype),
        # a grid step starts the copies of the next one's first group
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=name,
    )(*scalars, q.reshape((B, G) + rows), *pools)
    return out.reshape(B, H, latent or D)


def paged_decode_attention(q, k_pages, v_pages, block_table, pos, *,
                           scale: float, first=None):
    """One decode step of attention over paged KV state.

    ``q``: ``[B, H, D]`` (the decode token's query, S dim squeezed);
    ``k_pages``/``v_pages``: ``[N, G, bs, (Hkv/G)*D]`` pools, ``Hkv``
    K/V heads of which query head ``n`` reads head ``n // (H / Hkv)``
    (``Hkv == H``: a head each);
    ``block_table``: ``[B, MB]`` int32 PHYSICAL page ids (the layer's
    first page already added);
    ``pos``: ``[B]`` int32 per-slot positions (the current token's
    logical index — attended inclusively, like the XLA fallback);
    ``first``: ``[B]`` int32, a slot's first visible position (a window
    of W: ``max(0, pos - W + 1)``), or None for all of ``0 .. pos``.
    The sweep then starts at that position's table entry, and entries
    before it are never read (they may point at the scratch page).
    Returns ``[B, H, D]`` in q's dtype.
    """
    return _paged_decode(q, (k_pages, v_pages), block_table, pos,
                         scale=scale, quant=False, name="paged_decode",
                         first=first)


def paged_decode_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                 block_table, pos, *, scale: float):
    """Decode attention over an int8-quantized paged pool
    (``FLAGS_serve_kv_quant=int8``).

    Same contract as :func:`paged_decode_attention`, plus the parallel
    f32 scale pools ``k_scales``/``v_scales`` ``[N, G, bs, H/G]``. The
    scale blocks are copied by the SAME page ids as their pages, so the
    dequantize (``int8 * scale``) happens in VMEM right before the
    online-softmax update — the dequantized context never exists in
    HBM. Must match ``kv_cache.gather_pages_quant`` + masked SDPA
    token-exactly (same dequant math, f32 accumulation).
    """
    return _paged_decode(q, (k_pages, k_scales, v_pages, v_scales),
                         block_table, pos, scale=scale, quant=True,
                         name="paged_decode_int8")


def paged_mla_decode(q, pool, block_table, pos, *, scale: float,
                     value_width: int):
    """One decode step of DENSE latent attention (MLA in absorbed form)
    over ONE paged pool.

    ``q``: ``[B, H, W]``, a head's absorbed query ``[q_nope W_uk ;
    rope(q_rope)]`` zero-padded to the pool's row width ``W``;
    ``pool``: ``[N, 1, bs, W]``, a position's ``[c_kv ; rope(k_r)]``
    (576 values stored in 640 lanes), the keys of EVERY head and, in
    its first ``value_width`` lanes, their values: the sweep copies a
    page once where ``paged_decode_attention(q, pool, pool, ...)`` would
    copy it twice; ``block_table``, ``pos``: as there. Returns
    ``[B, H, value_width]``, the weighted latent a head, which the
    caller takes through ``W_uv`` and ``W_o``. Same sweep over a slot's
    live pages, same precision."""
    return _paged_decode(q, (pool,), block_table, pos, scale=scale,
                         quant=False, name="paged_mla_decode",
                         latent=int(value_width))
