"""Paged KV cache: block-structured decode state with static XLA shapes.

The vLLM/PagedAttention (SOSP '23) memory model mapped onto a TPU-native
static-shape program — the generalization of ``GPTAttention.StaticCache``
(one contiguous ``[B, L_max, H, D]`` buffer per request) to a shared pool
of fixed-size pages:

- what a model keeps in pages is the model's to declare
  (:class:`PageKind`: K and V a head for a plain decoder, a latent and
  an index key for MLA under a sparse selection) and how long a page of
  it lives (``PageKind.lifetime``: as long as its slot, or a window of
  the last W positions); kinds of one lifetime share ONE allocator and
  block table, and every kind has ONE pool of its own;
- a kind lives in ONE lane-dense pool, ``[L, P, G, bs, (H/G)*D]``: ``P``
  pages a layer, ``bs`` token rows a page, the heads of a token row
  fused into the minor dim (GPT-2 345M: 16 x 64 = 1024 lanes, whole
  ``(16, 128)`` bf16 tiles, nothing padded). ``G`` is the number of head
  groups — the ``mp`` size of a serving mesh, which shards that axis
  (``distributed.spmd.SERVE_KV_SPEC``), 1 without one. The array the
  engine holds, the array a serving program takes and donates, and the
  block a kernel DMAs all have this ONE physical layout;
- inside a program the pool is viewed as ``L*P`` pages
  ``[L*P, G, bs, (H/G)*D]`` (a bitcast: leading dims merge) and is never
  sliced by layer: it rides the scan CARRY
  (:func:`paddle_tpu.nn.scan.scan_layers_with_cache`), and layer ``l``
  addresses its pages as ``l*P + block_table`` (``base`` below). A step
  scatters only the rows it produced, in place;
- each batch slot owns a row of a **block table** ``[slots, MB]`` mapping
  logical block ``j`` (token positions ``j*bs .. j*bs+bs-1``) to a
  layer-relative page; unallocated entries point at the reserved scratch
  page 0 (physical page ``l*P`` of layer ``l``);
- pages are allocated incrementally as a request's sequence grows and
  freed the step it finishes — HBM scales with tokens actually held, not
  with ``slots * max_context`` (the fragmentation PagedAttention exists
  to kill), and the page pool size is the admission-control currency the
  scheduler trades in;
- every device shape is static: block tables and per-slot positions are
  small int32 *arguments* of the compiled step, so admitting/evicting a
  request between steps never recompiles anything;
- what a model keeps a SLOT rather than a position (the last inputs of
  a short convolution: :class:`StateKind`) is not paged: one array a
  kind, ``[L_kind, slots + 1, *shape]``, row ``slots`` the scratch row
  of padded and inactive rows, riding the same donated argument as the
  pools. A row whose first position is 0 reads zeros there instead of
  what its slot holds, so admission and slot reuse write nothing.

Everything that touches pages goes through two primitives and their
``_quant`` twins — :func:`write_pages` (one XLA scatter of the new rows)
and :func:`gather_pages` (one ``pages[base + table]`` gather, the XLA
fallback of the Pallas decode kernel and the context-prefill read) — so
there is one addressing rule. Out-of-range logical positions (a bucketed
prefill's padded tail) route to the layer's scratch page by construction
and are masked at read time, so no branch guards the hot path.
"""

from __future__ import annotations

import collections
import math
from typing import List, NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

__all__ = ["BlockAllocator", "PagedKVCache", "PageKind", "kv_page_kinds",
           "StateKind", "WindowPages",
           "PagedPools",
           "ContextPagedPools", "PagedCacheView",
           "PagedLayerCache", "ContextPagedCacheView",
           "ContextPagedLayerCache", "write_pages", "gather_pages",
           "write_pages_quant", "gather_pages_quant", "dequant_pages",
           "blocks_needed"]

#: lanes of a TPU vector register: the tile of a pool's minor dim
LANES = 128

#: physical page 0 is never allocated: it is the shared scratch target for
#: writes from inactive slots and padded prefill tails, and is masked out
#: of every read
SCRATCH_PAGE = 0


def blocks_needed(num_tokens: int, block_size: int) -> int:
    return max(0, math.ceil(int(num_tokens) / int(block_size)))


class PageKind(NamedTuple):
    """One kind of per-position state a model keeps in pages, as the
    model's ``cfg.page_kinds()`` declares it: ``width`` values a
    position (``heads`` head segments fused into that minor dim; 1 for
    state with no head axis, an MLA latent or an index key) in each of
    the model's ``layers`` (indices into its stack; a kind only some
    layers keep has a pool of ``len(layers)`` layers). ``lifetime`` says
    how long a page lives: ``"slot"``, until its slot is freed, or an
    int ``W``, while a later query can still reach one of its positions
    (a query at ``i`` sees keys ``j`` with ``0 <= i - j < W``). Kinds of
    one lifetime share a block table, an allocator and a page count of
    :class:`PagedKVCache`: page ``p`` of a slot holds the same positions
    in every kind of that lifetime.

    A pool's minor dim is :meth:`stored_width`: ``width`` rounded up to
    whole 128-lane tiles when it is wider than one and not a multiple
    (an MLA latent of 576 is stored as 640). XLA:TPU lays a minor dim
    that is no multiple of 128 out with ANOTHER dim minor-most, and
    every program then relayouts the whole pool on the way in and out.
    :func:`write_pages` zero-pads the rows; a reader takes the leading
    ``width``."""

    name: str
    width: int
    layers: tuple
    heads: int = 1
    lifetime: object = "slot"

    def stored_width(self) -> int:
        if self.width <= LANES or self.width % LANES == 0:
            return self.width
        return -(-self.width // LANES) * LANES


def kv_page_kinds(num_layers: int, num_heads: int, head_dim: int) -> tuple:
    """The kinds of a plain decoder: ``k`` and ``v``, a head segment a
    head, in every layer."""
    layers = tuple(range(int(num_layers)))
    return tuple(PageKind(n, int(num_heads) * int(head_dim), layers,
                          int(num_heads)) for n in ("k", "v"))


class StateKind(NamedTuple):
    """What a model keeps a SLOT, not a position, as its
    ``cfg.state_kinds()`` declares it: ``shape`` values a slot in each
    of the model's ``layers`` (indices into its stack), in the cache's
    dtype. A short convolution of length ``L`` keeps its last ``L - 1``
    inputs: ``(L - 1, width)``. The cache holds a kind as ONE array
    ``[len(layers), max_slots + 1, *shape]``; row ``max_slots`` is the
    scratch row a padded or inactive row reads and writes."""

    name: str
    shape: tuple
    layers: tuple


class PagedPools(NamedTuple):
    """What the engine hands a model as ``caches`` and takes back:
    ``pools``, one ``[L_kind, P, G, bs, W]`` array per declared
    :class:`PageKind` in declaration order, and the ``[B, MB]`` int32
    ``block_table`` they share. ``scales`` are the f32 scale pools of a
    quantized cache (``FLAGS_serve_kv_quant``), one per pool; ``lora``
    the ``(a_pool, b_pool, per_slot_rows)`` triple of a multi-tenant
    engine; ``stats`` small per-slot arrays by name that a model may
    return from a decode step for the engine's counters (never read as
    an input). ``state``, one array per declared :class:`StateKind`,
    and ``rows``, the ``([B] slot, [B] real positions)`` pair that says
    which slot's state a row carries and how many of its positions are
    real (a padded bucket's tail is not), where the model declares
    state kinds. A NamedTuple, so a pytree."""

    pools: tuple
    block_table: object
    scales: object = None
    lora: object = None
    stats: object = None
    state: object = None
    rows: object = None


class ContextPagedPools(PagedPools):
    """Marker subtype selecting the **context prefill** path, as
    :class:`ContextPagedCacheView` does for the K/V view: an S>1 chunk
    at per-slot positions ``pos`` attends over what is already in the
    pages as well as over itself."""


class PagedCacheView(NamedTuple):
    """The K/V-per-head form of the traced view (``GPTModel`` converts
    :class:`PagedPools` to it at its door, and back). ``k``/``v`` are the lane-dense pools
    ``[L, P, G, bs, (H/G)*D]``; ``block_table`` is ``[B, MB]`` int32.
    Being a NamedTuple it is a pytree — it flows through jit/scan
    unchanged.

    Optional trailing fields (all default ``None`` so every pre-existing
    3-arg construction is unchanged): ``k_scale``/``v_scale`` are the
    ``[L, P, G, bs, H/G]`` f32 scale pools of a quantized cache
    (``FLAGS_serve_kv_quant``); ``lora_a``/``lora_b`` are per-layer
    stacked LoRA pools ``[L, A, r, E]`` / ``[L, A, r, O]`` and
    ``lora_ids`` the ``[B]`` int32 per-slot adapter rows (serving.lora).
    """

    k: object
    v: object
    block_table: object
    k_scale: object = None
    v_scale: object = None
    lora_a: object = None
    lora_b: object = None
    lora_ids: object = None


class PagedLayerCache(NamedTuple):
    """One layer's window on the view, handed to ``GPTAttention.forward``
    by both the scan body and the loop layout. ``k_pages``/``v_pages``
    are the WHOLE pools viewed as ``[L*P, G, bs, (H/G)*D]`` — no layer
    is sliced out — and ``page_base`` (``l*P``; a traced scalar under
    the scan, an int in the loop) is the layer's first physical page:
    the layer reads and writes pages ``page_base + block_table`` only.
    Optional trailing fields mirror :class:`PagedCacheView` (whole
    ``[L*P, G, bs, H/G]`` scale pools under the same addressing;
    per-layer ``[A, r, E]``/``[A, r, O]`` LoRA slices)."""

    k_pages: object
    v_pages: object
    block_table: object
    k_scale: object = None
    v_scale: object = None
    lora_a: object = None
    lora_b: object = None
    lora_ids: object = None
    page_base: object = 0


class ContextPagedCacheView(PagedCacheView):
    """Marker subtype of :class:`PagedCacheView` selecting the
    **context prefill** attention path: an S>1 chunk at per-slot
    positions ``pos`` attends over everything ALREADY IN THE PAGES
    (positions ``< pos``) as well as causally over itself — the math
    chunked prefill, prefix-cache-hit admission and speculative verify
    all need, where the plain view's S>1 path assumes ``pos == 0`` and
    attends only over its own chunk. Being a NamedTuple subtype it is
    still a pytree, and ``isinstance(x, PagedCacheView)`` still routes
    it into the paged forward; the CLASS carries the static bit, so the
    dispatch choice is resolved at trace time, never on a traced
    value."""


class ContextPagedLayerCache(PagedLayerCache):
    """One layer's slice of a :class:`ContextPagedCacheView` (same
    marker contract at the attention-block level)."""


def _physical_rows(block_table, pos, S, bs, base):
    """``(page, row)`` ``[B, S]`` of logical positions ``pos[b] + 0..S-1``
    through ``block_table`` ``[B, MB]``: the ONE addressing rule of the
    flat pool — physical page = ``base`` (the layer's first page) + the
    table's layer-relative entry. Positions past ``MB*bs`` (padded
    prefill tails) route to the layer's scratch page."""
    mb = block_table.shape[1]
    idx = pos[:, None].astype(jnp.int32) + \
        jnp.arange(S, dtype=jnp.int32)[None, :]                  # [B, S]
    blk_logical = jnp.minimum(idx // bs, mb - 1)
    blk = jnp.take_along_axis(block_table, blk_logical, axis=1)  # [B, S]
    blk = jnp.where(idx >= bs * mb, SCRATCH_PAGE, blk)
    return blk + base, idx % bs


def write_pages(pages, new, block_table, pos, base=0):
    """Scatter ``new`` ``[B, S, H, D]`` into the pool ``pages``
    ``[N, G, bs, (H/G)*D]`` at logical positions ``pos[b] + 0..S-1``
    through ``block_table`` ``[B, MB]``, whose entries are relative to
    physical page ``base``. Only the ``B*S`` new rows move: inside a
    compiled step the scatter updates the (donated, carried) pool in
    place. Rows narrower than the pool's (``PageKind.stored_width``) are
    zero-padded. Returns the updated pool."""
    _, G, bs, F = pages.shape
    B, S = new.shape[:2]
    blk, off = _physical_rows(block_table, pos, S, bs, base)
    rows = new.reshape(B, S, G, -1).astype(pages.dtype)
    if rows.shape[-1] < F:
        rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, F - rows.shape[-1]),))
    return pages.at[blk, :, off].set(rows)


def _contiguous(g, head_dim):
    """Gathered pages ``[B, MB, G, bs, (H/G)*D]`` as a slot-contiguous
    context ``[B, MB*bs, H, D]`` (a free reshape when ``G == 1``)."""
    B, MB, G, bs, F = g.shape
    return jnp.swapaxes(g, 2, 3).reshape(
        B, MB * bs, G * F // head_dim, head_dim)


def gather_pages(pages, block_table, head_dim, base=0):
    """Gather a slot-contiguous context ``[B, MB*bs, H, D]`` out of the
    pool ``[N, G, bs, (H/G)*D]`` via the block table (the PagedAttention
    read), table entries relative to physical page ``base``."""
    return _contiguous(pages[block_table + base], head_dim)


#: int8 quant range: symmetric, -127..127 (no -128 — keeps the scale
#: inversion exact under negation)
_QMAX = 127.0
#: absmax floor so an all-zero token row quantizes to scale eps, not 0/0
_QEPS = 1e-8


def write_pages_quant(pages, scales, new, block_table, pos, base=0):
    """Quantizing scatter (``FLAGS_serve_kv_quant=int8``): same indexing
    as :func:`write_pages`, but ``new`` ``[B, S, H, D]`` is stored as
    int8 in ``pages`` with a per-token-row, per-head absmax scale in the
    parallel f32 pool ``scales`` ``[N, G, bs, H/G]``. Quantization
    happens at write time — every token row is quantized exactly once,
    so pages can move between slots (COW sharing, radix donation,
    ``truncate_slot``, drain snapshots) without ever touching the
    payload: the scale rides the same physical page index. Returns
    ``(pages, scales)``."""
    _, G, bs, F = pages.shape
    B, S = new.shape[:2]
    blk, off = _physical_rows(block_table, pos, S, bs, base)
    newf = new.astype(jnp.float32)                               # [B,S,H,D]
    scale = jnp.maximum(jnp.max(jnp.abs(newf), axis=-1),
                        _QEPS) / _QMAX                           # [B,S,H]
    q = jnp.clip(jnp.round(newf / scale[..., None]),
                 -_QMAX, _QMAX).astype(jnp.int8)
    return (pages.at[blk, :, off].set(q.reshape(B, S, G, F)),
            scales.at[blk, :, off].set(
                scale.reshape(B, S, G, -1).astype(scales.dtype)))


def dequant_pages(pages, scales):
    """Dequantize an int8 pool (or any gathered slice of one) back to
    f32 in the pool's own shape: each ``D``-wide head segment of the
    fused minor dim ``[..., (H/G)*D]`` times its scale ``[..., H/G]``."""
    hg = scales.shape[-1]
    seg = pages.astype(jnp.float32).reshape(pages.shape[:-1] + (hg, -1))
    return (seg * scales.astype(jnp.float32)[..., None]).reshape(
        pages.shape)


def gather_pages_quant(pages, scales, block_table, head_dim, base=0):
    """Quantized PagedAttention read: gather int8 pages and their scales
    through the block table and dequantize to a slot-contiguous f32
    ``[B, MB*bs, H, D]`` context (the XLA fallback the quant Pallas
    decode kernel must match)."""
    tbl = block_table + base
    return _contiguous(dequant_pages(pages[tbl], scales[tbl]), head_dim)


class BlockAllocator:
    """Host-side refcounted free list over the physical page pool (page
    0 reserved as scratch). O(1) alloc/incref/free; allocation is
    all-or-nothing so a half-admitted request never wedges the pool.

    Refcounts are the prefix-cache currency (ISSUE 15): a page mapped
    into N slot block tables plus the radix tree holds N+1 references;
    :meth:`free` DECREMENTS and the page only re-enters the free list
    when the count hits zero — no holder can ever see its page recycled
    under it, and a page can never be freed twice (pinned by the
    scheduler fuzz). Pages allocated by :meth:`alloc` start at count 1
    (the pre-refcount semantics: one owner, one free)."""

    def __init__(self, num_pages: int, reserved: int = 1):
        if num_pages <= reserved:
            raise ValueError(
                f"page pool of {num_pages} leaves nothing to allocate "
                f"({reserved} reserved)")
        self.num_pages = int(num_pages)
        self.reserved = int(reserved)
        self._free = collections.deque(range(reserved, num_pages))
        #: page -> reference count, for every currently-allocated page
        self._rc: dict = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - self.reserved - len(self._free)

    def refcount(self, page: int) -> int:
        """Current reference count (0 = on the free list)."""
        return self._rc.get(int(page), 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages at refcount 1, or None (and no change) when the pool
        cannot cover them — the scheduler's cue to wait or preempt."""
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._rc[p] = 1
        return pages

    def incref(self, page: int) -> None:
        """Add a reference to an ALLOCATED page (mapping a cached
        prefix page into another slot's block table)."""
        page = int(page)
        if page not in self._rc:
            raise ValueError(f"incref on unallocated page {page}")
        self._rc[page] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page re-enters the free list
        only when its last reference goes."""
        for p in pages:
            p = int(p)
            if not (self.reserved <= p < self.num_pages):
                raise ValueError(f"freeing page {p} outside the pool")
            rc = self._rc.get(p)
            if rc is None:
                raise ValueError(f"double free of page {p} "
                                 "(refcount already 0)")
            if rc > 1:
                self._rc[p] = rc - 1
            else:
                del self._rc[p]
                self._free.append(p)


class WindowPages:
    """The block table and allocator of the kinds that keep only the
    last ``window`` positions of a slot (``PageKind.lifetime`` an int).

    The table keeps the slot lifetime's LOGICAL indexing (entry ``j``
    holds positions ``j*bs .. j*bs+bs-1``), so the one addressing rule
    (:func:`_physical_rows`) and the kernels' walk need nothing new: an
    entry whose positions no later query can reach points at the scratch
    page again and its page is back on the free list. A slot whose next
    program writes positions ``pos .. pos+n-1`` (every later query sits
    at ``>= pos``) holds entries ``(pos - window + 1) // bs`` to
    ``(pos + n - 1) // bs``: at most ``ceil((window + chunk) / bs) + 1``
    pages for programs of up to ``chunk`` positions, which is what a
    slot is given — so this lifetime never refuses an admission, and
    admission stays the slot lifetime's to decide."""

    def __init__(self, window: int, *, block_size: int, max_slots: int,
                 max_blocks_per_slot: int, max_chunk: int):
        self.window = int(window)
        if self.window < 1:
            raise ValueError(f"a window lifetime of {window} positions")
        self.block_size = int(block_size)
        #: pages a slot can hold at once (the bound of the class docstring)
        self.pages_per_slot = min(
            int(max_blocks_per_slot),
            blocks_needed(self.window + int(max_chunk), block_size) + 1)
        self.num_pages = 1 + int(max_slots) * self.pages_per_slot
        self.allocator = BlockAllocator(self.num_pages)
        self.tables = np.full((max_slots, max_blocks_per_slot),
                              SCRATCH_PAGE, np.int32)
        #: live entries of a slot are ``[first, end)``
        self._first = [0] * max_slots
        self._end = [0] * max_slots
        #: pages freed because their positions left the window (cumulative)
        self.freed = 0

    def live_blocks(self, slot: int) -> int:
        return self._end[slot] - self._first[slot]

    def first_position(self, slot: int) -> int:
        """The lowest position the slot still holds a page for."""
        return self._first[slot] * self.block_size

    def advance(self, slot: int, pos: int, n: int) -> int:
        """The slot's next program writes positions ``pos .. pos+n-1``
        and no later query sits below ``pos``: free the pages no such
        query reaches, then cover the positions to be written. Returns
        the pages freed."""
        bs, tbl = self.block_size, self.tables[slot]
        first, end = self._first[slot], self._end[slot]
        keep = max(0, int(pos) - self.window + 1) // bs
        freed = 0
        if keep > first:
            stop = min(keep, end)
            if stop > first:
                self.allocator.free(tbl[first:stop].tolist())
                tbl[first:stop] = SCRATCH_PAGE
                freed = stop - first
                self.freed += freed
            first = keep
            end = max(end, first)
        need = blocks_needed(int(pos) + int(n), bs)
        if need > end:
            if need > tbl.shape[0]:
                raise ValueError(
                    f"slot {slot}: {pos + n} tokens exceed the "
                    f"{tbl.shape[0] * bs}-token slot capacity")
            pages = self.allocator.alloc(need - end)
            if pages is None:
                raise RuntimeError(
                    f"window lifetime (W={self.window}): slot {slot} needs "
                    f"{need - first} pages for positions {pos}..{pos + n - 1}"
                    f" but a slot is given {self.pages_per_slot} — a program "
                    "wrote more positions than the cache's max_chunk")
            tbl[end:need] = pages
            end = need
        self._first[slot], self._end[slot] = first, end
        return freed

    def free_slot(self, slot: int) -> None:
        first, end = self._first[slot], self._end[slot]
        if end > first:
            self.allocator.free(self.tables[slot, first:end].tolist())
        self.tables[slot, :] = SCRATCH_PAGE
        self._first[slot] = self._end[slot] = 0


class PagedKVCache:
    """Device page pools + host block tables for a fixed slot batch:
    a pool a :class:`PageKind` (``kinds=``; K and V of ``num_layers x
    num_heads x head_dim`` when none is given), one allocator and one
    block table a lifetime: this object's own for the ``"slot"`` kinds
    (``num_pages`` pages), a :class:`WindowPages` in ``windows`` for
    each window ``W`` a kind declares (its page count derived from the
    slots, ``W``, ``max_chunk`` and the block size).

    ``pool_args()`` is the argument a compiled step takes, ``update
    (*pools)`` swaps in the pools it returned; ``table_array()``
    snapshots the host tables as the step's int32 argument (one array;
    with window lifetimes a tuple, the slot lifetime's first). Slot
    bookkeeping (``alloc_slot``/``extend_slot``/``advance``/
    ``free_slot``) is pure host work — device shapes never change.
    """

    def __init__(self, num_layers: Optional[int] = None,
                 num_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 *, num_pages: int, block_size: int, max_slots: int,
                 max_blocks_per_slot: int, dtype=jnp.float32,
                 head_groups: int = 1,
                 kinds: Optional[Sequence[PageKind]] = None,
                 max_chunk: Optional[int] = None,
                 state_kinds: Sequence[StateKind] = ()):
        from ..core.flags import get_flag
        if kinds is None:
            kinds = kv_page_kinds(num_layers, num_heads, head_dim)
        #: the page kinds, in the order programs take and return pools
        self.kinds = tuple(kinds)
        #: head groups G: the pools' third axis, which a serving mesh
        #: shards over ``mp`` (one group a chip); 1 on a single chip
        self.head_groups = int(head_groups)
        for kd in self.kinds:
            if kd.heads % self.head_groups:
                raise ValueError(
                    f"page kind {kd.name!r}: heads={kd.heads} not "
                    f"divisible by head_groups={head_groups}")
        self.block_size = int(block_size)
        self.max_slots = int(max_slots)
        self.max_blocks_per_slot = int(max_blocks_per_slot)
        self.dtype = jnp.dtype(dtype)
        #: quant mode, read ONCE at construction (engine convention):
        #: "" = full-precision pools (the flags-off oracle), "int8" =
        #: int8 pools + parallel f32 per-(page, row, head) scale pools;
        #: when quantized, each pool is a (pages, scales) 2-tuple
        #: — pytrees, so they flow through the existing jit arg slots.
        self.quant = str(get_flag("serve_kv_quant") or "")
        if self.quant not in ("", "int8"):
            raise ValueError(
                f"FLAGS_serve_kv_quant={self.quant!r}: supported modes "
                "are '' (full precision) and 'int8'")
        G = self.head_groups
        #: a :class:`WindowPages` a window lifetime, in the order the
        #: kinds first name them; ``max_chunk`` is the longest run of
        #: positions one program writes into a slot
        self.windows = tuple(
            WindowPages(w, block_size=block_size, max_slots=max_slots,
                        max_blocks_per_slot=max_blocks_per_slot,
                        max_chunk=(max_chunk if max_chunk
                                   else max_blocks_per_slot * block_size))
            for w in dict.fromkeys(kd.lifetime for kd in self.kinds
                                   if kd.lifetime != "slot"))
        pages_of = {w.window: w.num_pages for w in self.windows}
        #: kind name -> ``[L_kind, P, G, bs, W/G]`` pool (quantized: a
        #: ``(pages, scales)`` pair, scales ``[L_kind, P, G, bs, heads/G]``)
        self.pools = {}
        for kd in self.kinds:
            shape = (len(kd.layers), pages_of.get(kd.lifetime, num_pages),
                     G, block_size, kd.stored_width() // G)
            if self.quant == "int8":
                self.pools[kd.name] = (
                    jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:-1] + (kd.heads // G,), jnp.float32))
            else:
                self.pools[kd.name] = jnp.zeros(shape, dtype)
        #: the state kinds, in the order programs take and return them
        #: (after the pools)
        self.state_kinds = tuple(state_kinds)
        #: kind name -> ``[L_kind, max_slots + 1, *shape]``
        self.states = {
            sk.name: jnp.zeros((len(sk.layers), max_slots + 1)
                               + tuple(sk.shape), dtype)
            for sk in self.state_kinds}
        self.allocator = BlockAllocator(num_pages)
        self._tables = np.full((max_slots, max_blocks_per_slot),
                               SCRATCH_PAGE, np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        #: leading pages of each slot mapped COPY-ON-WRITE from the
        #: radix prefix cache (never written by this slot: every write
        #: lands at positions >= shared * block_size)
        self._slot_shared: List[int] = [0] * max_slots
        #: optional RadixPrefixCache (serving.prefix_cache): consulted
        #: for LRU eviction when the free list cannot cover an alloc,
        #: and fed donated pages by free_slot
        self.prefix_cache = None

    # -- device-side --------------------------------------------------------
    def pool_args(self) -> tuple:
        """The pools in declaration order, then the state kinds' arrays:
        the one argument every serving program takes, donates and
        returns."""
        return tuple(self.pools[kd.name] for kd in self.kinds) \
            + tuple(self.states[sk.name] for sk in self.state_kinds)

    def update(self, *new) -> None:
        """Swap in the pools (and states) a compiled step returned
        (declaration order, as :meth:`pool_args` gave them)."""
        for kd, pool in zip(self.kinds, new):
            self.pools[kd.name] = pool
        for sk, state in zip(self.state_kinds, new[len(self.kinds):]):
            self.states[sk.name] = state

    def state_bytes_per_slot(self) -> int:
        """Device bytes ONE slot's state costs across all state kinds
        and their layers (nothing a position: it does not grow)."""
        return sum(len(sk.layers) * math.prod(sk.shape)
                   for sk in self.state_kinds) * self.dtype.itemsize

    # the K/V pair of a plain decoder, by name
    k = property(lambda self: self.pools["k"],
                 lambda self, pool: self.pools.__setitem__("k", pool))
    v = property(lambda self: self.pools["v"],
                 lambda self, pool: self.pools.__setitem__("v", pool))

    def kv_bytes_per_token(self) -> int:
        """Device bytes ONE token position costs across all kinds and
        layers — the capacity currency the kv-quant flag halves: int8
        pays ``width`` payload + ``heads`` f32 scale bytes a layer of a
        kind, full precision pays ``width * itemsize`` (the width as
        stored, ``PageKind.stored_width``)."""
        per = (lambda kd: kd.stored_width() + 4 * kd.heads) \
            if self.quant == "int8" \
            else (lambda kd: kd.stored_width() * self.dtype.itemsize)
        return sum(len(kd.layers) * per(kd) for kd in self.kinds)

    def table_array(self, rows: Optional[Sequence[Optional[int]]] = None):
        """Snapshot block tables as the step's int32 argument: all slots,
        or one row per entry of ``rows`` — a ``None`` entry (a padded
        prefill row) gets an all-scratch row, so its garbage K/V can
        never land in another slot's pages. With window lifetimes, a
        tuple: this table, then one of each of ``windows``; with state
        kinds, a tuple ending in the ``[n]`` int32 slot of each row (a
        ``None`` entry: the scratch row ``max_slots``)."""
        tables = self._tables_array(rows)
        if not self.state_kinds:
            return tables
        slots = np.arange(self.max_slots, dtype=np.int32) if rows is None \
            else np.asarray([self.max_slots if s is None else s
                             for s in rows], np.int32)
        return (tables if isinstance(tables, tuple) else (tables,)) \
            + (jnp.asarray(slots),)

    def _tables_array(self, rows):
        def snap(tables):
            if rows is None:
                return jnp.asarray(tables)
            t = np.full((len(rows), self.max_blocks_per_slot),
                        SCRATCH_PAGE, np.int32)
            for i, s in enumerate(rows):
                if s is not None:
                    t[i] = tables[s]
            return jnp.asarray(t)

        if not self.windows:
            return snap(self._tables)
        return (snap(self._tables),) + tuple(snap(w.tables)
                                             for w in self.windows)

    def table_like(self, n: int):
        """An all-scratch table argument of ``n`` rows, in the form
        :meth:`table_array` gives: what a program is compiled against."""
        z = jnp.zeros((n, self.max_blocks_per_slot), jnp.int32)
        if self.state_kinds:
            return (z,) * (1 + len(self.windows)) \
                + (jnp.zeros((n,), jnp.int32),)
        return (z,) * (1 + len(self.windows)) if self.windows else z

    @property
    def max_context_len(self) -> int:
        return self.max_blocks_per_slot * self.block_size

    # -- slot bookkeeping ---------------------------------------------------
    def slot_blocks(self, slot: int) -> int:
        return len(self._slot_pages[slot])

    def slot_shared_blocks(self, slot: int) -> int:
        """Leading COW pages mapped from the prefix cache (writes to
        this slot must start at/after ``shared * block_size``)."""
        return self._slot_shared[slot]

    def capacity_tokens(self, slot: int) -> int:
        """Token positions the slot's allocated blocks cover."""
        return self.slot_blocks(slot) * self.block_size

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Allocator alloc with prefix-cache pressure relief: when the
        free list cannot cover ``n``, evict LRU radix-tree pages until
        it can (or the tree runs out) — cached prefixes are strictly
        lower-value than live requests, so they leave BEFORE any
        recompute-preemption fires."""
        pages = self.allocator.alloc(n)
        while pages is None and self.prefix_cache is not None:
            if self.prefix_cache.evict_for(
                    n - self.allocator.free_pages) <= 0:
                break
            pages = self.allocator.alloc(n)
        return pages

    def alloc_slot(self, slot: int, num_tokens: int,
                   shared_pages: Sequence[int] = ()) -> bool:
        """Allocate blocks covering ``num_tokens`` positions for a
        fresh slot. ``shared_pages`` are prefix-cache hits (already
        incref'd by the match) mapped read-only at the head of the
        block table; only the remainder is newly allocated. False when
        the pool cannot cover the remainder — the shared references are
        dropped again, so a failed admission leaks nothing."""
        if self._slot_pages[slot]:
            raise RuntimeError(f"slot {slot} already holds pages; "
                               "free_slot first")
        shared = list(shared_pages)
        need = blocks_needed(num_tokens, self.block_size)
        if len(shared) > need:
            raise ValueError(
                f"slot {slot}: {len(shared)} shared pages exceed the "
                f"{need} blocks {num_tokens} tokens need")
        pages = self._alloc(need - len(shared))
        if pages is None:
            if shared:
                self.allocator.free(shared)
            return False
        self._slot_pages[slot] = shared + pages
        self._slot_shared[slot] = len(shared)
        self._tables[slot, :need] = self._slot_pages[slot]
        return True

    def extend_slot(self, slot: int, num_tokens: int) -> bool:
        """Grow the slot to cover ``num_tokens`` positions (decode
        crossing a block boundary). False when the pool is dry — the
        preemption trigger."""
        need = blocks_needed(num_tokens, self.block_size)
        have = len(self._slot_pages[slot])
        if need <= have:
            return True
        if need > self.max_blocks_per_slot:
            raise ValueError(
                f"slot {slot}: {num_tokens} tokens exceed the "
                f"{self.max_context_len}-token slot capacity")
        pages = self._alloc(need - have)
        if pages is None:
            return False
        self._slot_pages[slot].extend(pages)
        self._tables[slot, have:need] = pages
        return True

    def advance(self, slot: int, pos: int, n: int = 1) -> int:
        """Tell the window lifetimes that the slot's next program writes
        positions ``pos .. pos+n-1`` and that no later query sits below
        ``pos`` (a decode step: ``n`` 1; a prefill chunk: its length):
        pages out of every later query's reach are freed, the positions
        to be written covered. Returns the pages freed. Nothing to do
        without a window lifetime: the slot lifetime's pages were
        allocated by ``alloc_slot`` / ``extend_slot``."""
        return sum(w.advance(slot, pos, n) for w in self.windows)

    def truncate_slot(self, slot: int, num_tokens: int) -> int:
        """Shrink the slot to cover only ``num_tokens`` positions — the
        speculative-decode rollback: pages holding ONLY rejected draft
        K/V leave the block table and drop their reference. Never cuts
        into the COW-shared prefix (committed tokens always cover it).
        Returns the number of pages released."""
        if self.windows:
            raise NotImplementedError(
                "truncate_slot (the speculative-decode rollback) under a "
                "window page lifetime: a rejected draft's rows may already "
                "have pushed pages out of the window")
        keep = blocks_needed(num_tokens, self.block_size)
        pages = self._slot_pages[slot]
        if keep >= len(pages):
            return 0
        if keep < self._slot_shared[slot]:
            raise ValueError(
                f"slot {slot}: truncation to {num_tokens} tokens would "
                f"cut into the {self._slot_shared[slot]} shared prefix "
                "pages — committed tokens must cover the shared prefix")
        tail = pages[keep:]
        self.allocator.free(tail)
        self._slot_pages[slot] = pages[:keep]
        self._tables[slot, keep:] = SCRATCH_PAGE
        return len(tail)

    def free_slot(self, slot: int,
                  donate_tokens: Optional[Sequence[int]] = None) -> None:
        """Release the slot's pages (one reference each). With a prefix
        cache attached and ``donate_tokens`` — the token ids whose K/V
        the slot's pages VALIDLY hold, in order — full pages are donated
        into the radix tree instead (ownership of this slot's reference
        transfers; duplicates of already-cached paths are simply
        dropped), so completed/evicted requests seed future prefix
        hits."""
        pages = self._slot_pages[slot]
        if pages:
            donated = 0
            if self.prefix_cache is not None and donate_tokens is not None:
                donated = self.prefix_cache.donate(donate_tokens, pages)
            if donated < len(pages):
                self.allocator.free(pages[donated:])
        self._slot_pages[slot] = []
        self._slot_shared[slot] = 0
        self._tables[slot, :] = SCRATCH_PAGE
        for w in self.windows:
            w.free_slot(slot)
