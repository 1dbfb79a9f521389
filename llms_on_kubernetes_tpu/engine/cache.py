"""Paged KV cache: device-side page pool + host-side page allocator.

The reference stack got paged attention from the vLLM image (reference
SURVEY §2.3); this is the TPU-native equivalent. Design:

- ONE flat pool for all layers: ``k_pages``/``v_pages`` have shape
  [n_kv, L * P, page_size, head_dim] — **head-major**, so one
  (head, page) slice is a contiguous [page, d] block: the Pallas decode
  kernel DMAs it HBM→VMEM in a single aligned transfer (a head-minor
  layout puts n_kv in the tiled sublane slot and Mosaic rejects the
  size-1 slice). Layer ``l``'s pages occupy the block [l*P, (l+1)*P); the
  decoder adds ``l*P`` to the (per-layer-local) page table inside the
  layer body. n_kv is the sharded axis (mesh "model") so each TP shard
  holds its own heads' pages — the pool never crosses chips.
- A page row is 128 lanes wherever that costs no byte: heads of 64 are
  stored TWO TO A ROW (``heads_per_row``), heads 2h and 2h+1 side by
  side, so the pool is [n_kv/2, L * P, page, 128] with
  ``stored[h, p, t, j*64 + c] = logical[2*h + j, p, t, c]``. The bytes a
  token takes are the same; the page DMA of the Pallas decode kernels,
  which Mosaic compiles only for whole 128-lane rows, then serves such a
  model with the kernel body it runs at 128 (ops/pallas_paged.py). Every
  reader takes the layout from the pool it is handed: ``write_tokens``
  reshapes the new rows to the pool's row (heads are adjacent, so that is
  free), ``ops/attention._gather_pool`` un-pairs after its gather, and a
  page's payload (host tier, prefill/decode hand-over) is the pool's bytes
  in the pool's shape (``CacheConfig.pool_row``).
- A LATENT pool (``CacheConfig.latent``: DeepSeek's MLA) keeps one row a
  token a layer, the normalised latent and the shared roped key side by
  side (576 values, then zeros up to 640: whole 128-lane tiles,
  ``ModelConfig.cache_row``), from which both keys and values are
  computed: the K pool is that one array, [1, L * P, page, 640], one
  "head", and there is no V pool (``v_pages`` is a one-element
  placeholder that rides the same arguments and is never read). ``write_latent`` writes it with the
  in-place updates of ``write_tokens``; pages, tables and the allocator
  are the same.

  Why flat instead of a leading [L, ...] axis: the layer loop is
  ``lax.scan``, and a pool that rides the scan as xs/ys gets its updated
  per-layer slices STACKED into a fresh output buffer — a full pool
  rewrite (GBs) every step. The flat pool rides the scan CARRY, where
  XLA aliases the buffer across iterations and the per-token scatter
  lowers to a true in-place update (measured: the xs/ys layout cost
  ~19 ms/step at Llama-3-8B scale; the carry layout ~0).
- Physical page ``l*P`` (per-layer-local page 0) is reserved as a trash
  page: padded prompt positions write there, so prefill needs no masking
  on the scatter path. It is never allocated and never read (length masks
  exclude it).
- The allocator is plain host Python (free list) handing out PER-LAYER-
  LOCAL ids in [1, P) — every layer uses the same local table, so the
  engine ships one small [slots, pages_per_seq] int32 table per step.

All shapes are static: ``num_pages``, ``page_size``, ``pages_per_slot`` are
fixed at engine start, which is what keeps the decode step at exactly one
compiled executable (XLA retraces on any shape change).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class CacheConfig:
    # layers that keep keys and values (``ModelConfig.num_attn_layers``):
    # a layer of another kind holds no page, and its per-slot state lives
    # beside the pools (models/decoder.init_conv_state)
    num_layers: int
    num_kv_heads: int
    head_dim: int
    num_pages: int = 2048
    page_size: int = 64
    pages_per_slot: int = 32
    dtype: str = "bfloat16"
    # "int8": per-token symmetric KV quantization (scale per (head, page,
    # token) stored beside the data) — halves decode-attention HBM traffic
    # and doubles token capacity per chip. None => KV stored in `dtype`.
    kv_dtype: "Optional[str]" = None
    # size of the mesh's ``model`` axis, over which the pool's head axis is
    # sharded (parallel/sharding.pool_sharding): heads pair into one row
    # only where every shard keeps whole pairs
    model_shards: int = 1
    # one latent row a token a layer (num_kv_heads 1, head_dim its width)
    # and no V side: see the module docstring
    latent: bool = False

    @property
    def max_seq_len(self) -> int:
        return self.pages_per_slot * self.page_size

    @property
    def pool_row(self) -> tuple[int, int]:
        """(rows of heads, lanes a row) of the pools as stored: the first
        and the last axis of ``init_pages``' shape and of a page's payload
        (module docstring: two 64-wide heads share a 128-lane row)."""
        n, _ = heads_per_row(self.num_kv_heads, self.head_dim, self.kv_dtype,
                             self.model_shards)
        return self.num_kv_heads // n, self.head_dim * n

    @property
    def bytes_per_page(self) -> int:
        if self.kv_dtype == "int8":
            per_tok = self.num_kv_heads * (self.head_dim + 4)  # data + scale
        else:
            per_tok = (self.num_kv_heads * self.head_dim
                       * jnp.dtype(self.dtype).itemsize)
        sides = 1 if self.latent else 2
        return sides * self.num_layers * self.page_size * per_tok

    @property
    def bytes_per_token(self) -> int:
        """KV bytes per cached token across all layers, both sides — the
        capacity-planning number behind llm_kv_bytes_per_token. int8 is
        (head_dim + 4) bytes per (head, token, side) vs 2 * head_dim for
        bf16: ~2x smaller at head_dim 128."""
        return self.bytes_per_page // self.page_size


def heads_per_row(num_kv_heads: int, head_dim: int,
                  kv_dtype: "Optional[str]" = None,
                  model_shards: int = 1) -> tuple[int, str]:
    """(heads a page row holds, why a 64-wide pool keeps one): two where
    head_dim is 64, so a row is the 128 lanes the decode kernels' page DMA
    needs. One, with the reason for the dispatcher's record, for an int8
    pool (its scales are one a head and token, which the kernels apply to
    a whole row of logits), an odd number of heads, and a model axis that
    would split a pair; one, with no reason, at every other width."""
    if head_dim != 64:
        return 1, ""
    if kv_dtype == "int8":
        return 1, "an int8 pool keeps one scale a head, so heads stay apart"
    if num_kv_heads % 2:
        return 1, f"{num_kv_heads} kv heads do not pair"
    if (num_kv_heads // 2) % model_shards:
        return 1, (f"a model axis of {model_shards} does not divide "
                   f"{num_kv_heads // 2} pairs of heads")
    return 2, ""


@jax.tree_util.register_pytree_node_class
class KVPool:
    """One side (K or V) of the paged cache: flat head-major ``data``
    [n_kv, L*P, page, d] ([n_kv/2, L*P, page, 128] where two 64-wide heads
    share a row) plus, when int8-quantized, a per-token ``scale``
    [n_kv, L*P, page] float32. A pytree, so it rides jit arguments,
    donation, lax.scan carries, and device_put shardings like the plain
    array it replaces."""

    def __init__(self, data: jnp.ndarray, scale: Optional[jnp.ndarray] = None):
        self.data = data
        self.scale = scale

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def quantized(self) -> bool:
        return self.scale is not None

    def tree_flatten(self):
        if self.scale is None:
            return (self.data,), False
        return (self.data, self.scale), True

    @classmethod
    def tree_unflatten(cls, has_scale, children):
        return cls(*children) if has_scale else cls(children[0])

    def __repr__(self):
        return (f"KVPool(shape={tuple(self.data.shape)}, "
                f"dtype={self.data.dtype}, quantized={self.quantized})")


def init_pages(cfg: CacheConfig, sharding=None) -> tuple[KVPool, KVPool]:
    """Flat head-major pools [n_kv, L * P, page, d], or [n_kv/2, L * P,
    page, 128] at 64-wide heads (``cfg.pool_row``; layer l's block starts
    at l * P; see module docstring for why the layer axis is folded in).
    ``sharding`` (parallel/sharding.pool_sharding on the engine's mesh)
    creates every leaf already sharded, so no device ever holds a whole
    pool."""
    heads, lanes = cfg.pool_row
    shape = (heads, cfg.num_layers * cfg.num_pages, cfg.page_size, lanes)
    if cfg.kv_dtype == "int8":
        def one():
            return KVPool(jnp.zeros(shape, jnp.int8, device=sharding),
                          jnp.zeros(shape[:3], jnp.float32, device=sharding))
        return one(), one()
    if cfg.kv_dtype is not None:
        raise ValueError(f"unsupported kv_dtype {cfg.kv_dtype!r} "
                         f"(None or 'int8')")
    dt = jnp.dtype(cfg.dtype)
    if cfg.latent:
        return (KVPool(jnp.zeros(shape, dt, device=sharding)),
                KVPool(jnp.zeros((1, 1, 1, 1), dt)))
    return (KVPool(jnp.zeros(shape, dt, device=sharding)),
            KVPool(jnp.zeros(shape, dt, device=sharding)))


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token symmetric int8: x [..., d] -> (int8 data, f32 scale [...])."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    data = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return data, scale


# Page updates are unrolled per (slot, touched page); beyond this many
# touched pages per row the code falls back to one HLO scatter. The
# threshold covers every realistic bucket/page combination (2048-token
# chunks at page 64, 1024 at 32); beyond it each LAYER's scatter copies
# the whole flat pool — only acceptable for exotic configs (huge buckets
# with tiny pages), never for the decode hot path.
_MAX_RMW_PAGES = 33


def write_tokens(
    k_pages: "KVPool",
    v_pages: "KVPool",
    k: jnp.ndarray,
    v: jnp.ndarray,
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    owner: "Optional[tuple]" = None,
) -> tuple["KVPool", "KVPool"]:
    """Write new KV for one layer into the page pool IN PLACE.

    k_pages/v_pages: KVPool — data [n_kv, P_total, page, d] (flat
                     head-major pool) + optional per-token int8 scale
    k, v:            [B, T, n_kv, d]; written as the pool's rows (two
                     adjacent 64-wide heads are one 128-lane row of a
                     paired pool: a reshape that moves nothing)
    page_table:      [B, pages_per_seq] int32 — GLOBAL page ids (the layer
                     body has already added its l*P block offset)
    positions:       [B, T] int32 token positions; each row's valid entries
                     are CONTIGUOUS (pos0, pos0+1, ...); negative => skip
                     (padding). Row-contiguity holds for every caller:
                     decode writes one token, prefill/chunk write a
                     front-packed chunk.

    The update itself is :func:`_write_rows`', one implementation for
    every pool (a latent pool's too: :func:`write_latent`).
    """
    B, T = k.shape[:2]
    n_kv, _, _, d = k_pages.shape
    k_pages, v_pages = _write_rows(
        [k_pages, v_pages],
        [k.reshape(B, T, n_kv, d), v.reshape(B, T, n_kv, d)],
        page_table, positions, owner)
    return k_pages, v_pages


def write_latent(pool: "KVPool", rows: jnp.ndarray, page_table: jnp.ndarray,
                 positions: jnp.ndarray) -> "KVPool":
    """Write one layer's new latent rows [B, T, w] into a latent pool
    [1, P_total, page, width] IN PLACE (w <= width: the rest of a pool
    row is zeros, written as zeros): ``write_tokens``' update of one pool
    with one "head". ``page_table`` and ``positions`` as there."""
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pool.shape[3] - rows.shape[2])))
    return _write_rows([pool], [rows[:, :, None, :]], page_table, positions)[0]


def _write_rows(pools: list, rows: list, page_table: jnp.ndarray,
                positions: jnp.ndarray,
                owner: "Optional[tuple]" = None) -> list:
    """Write ``rows[i]`` [B, T, n, d] into ``pools[i]`` (data [n, P_total,
    page, d]) IN PLACE, for pools that share a page table: K and V, or a
    latent pool alone.

    Quantized pools write int8 data + per-token scale with the same DUS
    pattern (the quantization is per token, so an append never has to
    rescale previously written tokens).

    Implementation note (measured on v5e): HLO scatter never updates a
    multi-GB pool in place — it materializes a full copy per call — and a
    pool riding a lax.scan/while carry pays a boundary copy too. So this
    uses ``dynamic_update_slice`` exclusively (verified in-place under
    donation): one [n, 1, 1, d] DUS per slot for decode (T==1), and a
    read-merge-write of each touched page for chunked writes. Callers must
    keep the layer loop UNROLLED (see decoder._run_layers) so no while
    loop ever carries the pool.

    ``owner`` = (base, width): context-parallel mode (ops/cp.py) — the
    pool argument is ONE device's shard of the flat axis, covering global
    flat slots [base, base+width); page ids are translated to local and
    non-owned updates become read-merge no-ops (a blind DUS at a clamped
    local slot would corrupt a page another sequence owns there).
    """
    B, T = rows[0].shape[:2]
    page = pools[0].shape[2]
    pps = page_table.shape[1]
    scales: list = []
    for i, pool in enumerate(pools):
        if pool.quantized:
            rows[i], sc = quantize_kv(rows[i])          # [B, T, n] scale
            scales.append(sc)
        else:
            rows[i] = rows[i].astype(pool.dtype)
            scales.append(None)
    datas = [pool.data for pool in pools]
    dscales = [pool.scale for pool in pools]

    def rewrap():
        return [KVPool(d, sc) for d, sc in zip(datas, dscales)]

    if T == 1:
        pos = positions[:, 0]
        safe = jnp.maximum(pos, 0)
        logical = safe // page
        pid = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
        # padding -> trash page 0 (never read; keeps the write unconditional)
        pid = jnp.where(pos < 0, 0, pid)
        off = jnp.where(pos < 0, 0, safe % page)
        owned = None
        if owner is not None:
            base, width = owner
            lpid = pid - base
            owned = (lpid >= 0) & (lpid < width)
            pid = jnp.where(owned, lpid, 0)
        # The unrolled per-slot DUS below is pure op overhead: 0.9 us an
        # op, 2 x B ops a layer whatever the occupancy (1.9 ms of a
        # 16.6 ms mistral-7b token step at B=32 on a v5e: PERF.md §6,
        # PR 34). The decode step no longer comes here where the paged
        # decode kernel applies: the append rides inside that kernel
        # (ops/attention.dispatch_paged_attention_write). This loop is
        # what every other shape, mesh and backend still takes (a batched
        # Pallas write kernel of its own was bit-exact but made the step's
        # Mosaic compile blow up at B=64).
        for b in range(B):
            for i, r in enumerate(rows):
                at = (0, pid[b], off[b], 0)
                upd = r[b, 0][:, None, None, :]             # [n, 1, 1, d]
                if owned is not None:  # CP: non-owner preserves the old value
                    upd = jnp.where(owned[b], upd, jax.lax.dynamic_slice(
                        datas[i], at, upd.shape))
                datas[i] = jax.lax.dynamic_update_slice(datas[i], upd, at)
            for i, sc in enumerate(scales):
                if sc is None:
                    continue
                at = (0, pid[b], off[b])
                upd = sc[b, 0][:, None, None]
                if owned is not None:
                    upd = jnp.where(owned[b], upd, jax.lax.dynamic_slice(
                        dscales[i], at, upd.shape))
                dscales[i] = jax.lax.dynamic_update_slice(dscales[i], upd, at)
        return rewrap()

    n_touch = (T - 1) // page + 2  # max pages a T-token contiguous run spans
    if n_touch > _MAX_RMW_PAGES:
        if owner is not None:
            raise ValueError(
                "context-parallel writes require the RMW page path; this "
                f"chunk touches {n_touch} pages > {_MAX_RMW_PAGES} "
                "(use a larger page_size or smaller prefill buckets)")
        return _write_rows_scatter(pools, rows, scales, page_table, positions)

    valid = positions >= 0                       # [B, T]
    # rows are front-packed: entry 0 is the first (lowest) position, or -1
    # for an all-invalid row (idle slot) — then pos0=0 and mask kills it
    pos0 = jnp.maximum(positions[:, 0], 0)       # [B]
    base_lg = pos0 // page
    page_iota = jnp.arange(page, dtype=jnp.int32)
    for b in range(B):
        for j in range(n_touch):
            lg = base_lg[b] + j
            lg_c = jnp.clip(lg, 0, pps - 1)
            # out-of-range or idle row -> trash page 0 (never read)
            pid = jnp.where((lg < pps) & valid[b, 0], page_table[b, lg_c], 0)
            own = None
            if owner is not None:
                base, width = owner
                lpid = pid - base
                own = (lpid >= 0) & (lpid < width)
                pid = jnp.where(own, lpid, 0)
            page_pos = lg * page + page_iota     # global positions [page]
            t_idx = page_pos - pos0[b]
            t_c = jnp.clip(t_idx, 0, T - 1)
            mask = None
            if j == 0 or own is not None:
                # head page may hold a PREVIOUS chunk's tokens below pos0:
                # read-merge-write. Every later page is append-territory —
                # offsets past the chunk are unwritten (appends only ever
                # move forward) and each will be overwritten before any
                # length-masked read can see it, so pages j>=1 are written
                # blind (no read) with clamped-gather filler. Under CP
                # (own is not None) EVERY page read-merge-writes: a
                # non-owner's clamped local slot 0 holds a real page.
                in_chunk = (t_idx >= 0) & (t_idx < T)
                mask = in_chunk & valid[b, t_c]  # [page]
                if own is not None:
                    mask = mask & own
            for i, r in enumerate(rows):
                n, d = r.shape[2:]
                new = jnp.take(r[b], t_c, axis=0).transpose(1, 0, 2)  # [n, page, d]
                if mask is not None:
                    cur = jax.lax.dynamic_slice(
                        datas[i], (0, pid, 0, 0), (n, 1, page, d))[:, 0]
                    new = jnp.where(mask[None, :, None], new, cur)
                datas[i] = jax.lax.dynamic_update_slice(
                    datas[i], new[:, None], (0, pid, 0, 0))
                if scales[i] is None:
                    continue
                new = jnp.take(scales[i][b], t_c, axis=0).T       # [n, page]
                if mask is not None:
                    cur = jax.lax.dynamic_slice(
                        dscales[i], (0, pid, 0), (n, 1, page))[:, 0]
                    new = jnp.where(mask[None, :], new, cur)
                dscales[i] = jax.lax.dynamic_update_slice(
                    dscales[i], new[:, None], (0, pid, 0))
    return rewrap()


def _write_rows_scatter(pools, rows, scales, page_table, positions):
    """HLO-scatter fallback for huge chunks (costs one pool copy)."""
    page = pools[0].shape[2]
    trash = positions < 0
    pos = jnp.where(trash, 0, positions)
    logical_page = pos // page                                   # [B, T]
    page_ids = jnp.take_along_axis(page_table, logical_page, axis=1)
    page_ids = jnp.where(trash, 0, page_ids)
    offs = pos % page
    out = []
    for pool, r, sc in zip(pools, rows, scales):
        # adjacent advanced indices on dims (1, 2): result [n, B, T, d]
        data = pool.data.at[:, page_ids, offs].set(
            jnp.moveaxis(r, 2, 0), mode="drop")
        scale = pool.scale
        if sc is not None:
            scale = scale.at[:, page_ids, offs].set(
                jnp.moveaxis(sc, 2, 0), mode="drop")
        out.append(KVPool(data, scale))
    return out


class PageAllocator:
    """Host-side refcounting allocator over the physical page pool, with
    optional hash-chained PREFIX CACHING (the vLLM-image capability the
    reference relied on, SURVEY §2.3 row 1).

    Page 0 is reserved (trash). ``allocate`` grows a slot's page list to
    cover ``num_tokens``; ``free`` releases a slot's references.

    Prefix caching: each FULL page of a prompt gets a digest chained over
    every token up to and including that page (sha256 — exact-match, no
    collision handling needed at 2^-128). ``match_prefix`` finds the
    longest cached chain; ``adopt_prefix`` maps those shared pages into a
    slot's table (read-only — the adopting request writes only at
    positions past the cached prefix, which land in later, private
    pages); ``register_prefix`` publishes a slot's freshly written prompt
    pages. Pages keep their content after the last reference drops: they
    move to an LRU of evictable cached pages and are reclaimed only when
    the free list runs dry.
    """

    def __init__(self, num_pages: int, page_size: int, num_slots: int,
                 pages_per_slot: int, prefix_caching: bool = False):
        self.num_pages = num_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.num_slots = num_slots
        self.prefix_caching = prefix_caching
        self.free_pages: list[int] = list(range(num_pages - 1, 0, -1))  # page 0 reserved
        # page_tables[s] is the authoritative host copy; unused entries point
        # at the trash page 0 (never read thanks to length masking).
        self.page_tables = np.zeros((num_slots, pages_per_slot), dtype=np.int32)
        self.slot_pages: list[list[int]] = [[] for _ in range(num_slots)]
        self.refcount: dict[int, int] = {}
        self._prefix_map: dict[bytes, int] = {}   # digest -> page id
        self._page_digest: dict[int, bytes] = {}  # page id -> digest
        # refcount-0 pages whose content is still a valid cached prefix,
        # oldest-released first (python dicts preserve insertion order)
        self._lru: dict[int, None] = {}
        self.hit_tokens_total = 0  # metrics: prompt tokens served from cache
        # pre-adoption LRU order per slot, kept until commit/rollback: a
        # blocked cache-hit admission retries every engine iteration, and
        # each retry must neither count the hit nor refresh the adopted
        # pages' LRU recency (round-3 advisor finding)
        self._adopt_snapshot: dict[int, list[int]] = {}

    @property
    def num_free_pages(self) -> int:
        return len(self.free_pages)

    @property
    def num_evictable_pages(self) -> int:
        return len(self._lru)

    @property
    def num_live_pages(self) -> int:
        """Pages some live sequence holds: neither free nor a finished
        request's cached prefix waiting for eviction (page 0 is trash)."""
        return (self.num_pages - 1 - len(self.free_pages)
                - len(self._lru))

    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    def can_allocate(self, slot: int, num_tokens: int) -> bool:
        need = self.pages_needed(num_tokens) - len(self.slot_pages[slot])
        return (need <= len(self.free_pages) + len(self._lru)
                and self.pages_needed(num_tokens) <= self.pages_per_slot)

    def _take_page(self) -> int:
        if self.free_pages:
            return self.free_pages.pop()
        if self._lru:  # evict the oldest cached page
            p = next(iter(self._lru))
            if p in self.refcount:
                # the LRU must only ever hold refcount-0 pages; evicting a
                # page some slot still reads would silently corrupt that
                # slot's KV — fail loudly instead (eviction-edge guard)
                raise RuntimeError(
                    f"evictable page {p} is still referenced "
                    f"(refcount={self.refcount[p]}) — LRU invariant broken")
            del self._lru[p]
            d = self._page_digest.pop(p, None)
            if d is not None and self._prefix_map.get(d) == p:
                del self._prefix_map[d]
            return p
        raise MemoryError("KV page pool exhausted")

    def allocate(self, slot: int, num_tokens: int) -> None:
        """Ensure the slot holds enough pages to cover num_tokens tokens."""
        need = self.pages_needed(num_tokens)
        if need > self.pages_per_slot:
            raise ValueError(
                f"sequence of {num_tokens} tokens needs {need} pages > "
                f"pages_per_slot={self.pages_per_slot}"
            )
        have = len(self.slot_pages[slot])
        for i in range(have, need):
            p = self._take_page()
            self.refcount[p] = 1
            self.slot_pages[slot].append(p)
            self.page_tables[slot, i] = p

    def free(self, slot: int) -> None:
        for p in self.slot_pages[slot]:
            if p not in self.refcount:
                # refcount underflow = a double free (the page was already
                # released through another slot list or a stale free): the
                # page may be on the free list or in another slot by now,
                # so continuing would hand the same page to two sequences
                raise RuntimeError(
                    f"double free of page {p} (slot {slot}): page has no "
                    "outstanding references")
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                del self.refcount[p]
                if p in self._page_digest:
                    self._lru[p] = None  # cached: evictable, content kept
                else:
                    self.free_pages.append(p)
        self.slot_pages[slot] = []
        self.page_tables[slot, :] = 0

    # -- prefix caching ----------------------------------------------------

    def _digests(self, tokens, salt: bytes = b"") -> list[bytes]:
        """Chained digest per FULL page of ``tokens``."""
        import hashlib

        out = []
        prev = salt
        for i in range(len(tokens) // self.page_size):
            chunk = tokens[i * self.page_size:(i + 1) * self.page_size]
            h = hashlib.sha256(prev)
            h.update(np.asarray(chunk, np.int64).tobytes())
            prev = h.digest()
            out.append(prev)
        return out

    def _match_digests(self, tokens, salt: bytes = b"") -> list[int]:
        """Page ids of the longest cached prefix — ONE incremental pass
        with early stop at the first miss (an EMA of full-prompt sha256
        passes per admission attempt would be pure waste: a blocked
        admission retries every engine iteration). Capped so at least one
        token remains to prefill (its logits seed sampling).

        ``salt`` seeds the digest chain — multimodal prompts mix a hash
        of their image BYTES in, so identical token streams carrying
        different images (image soft tokens share one placeholder id)
        can never alias."""
        if not self.prefix_caching or len(tokens) <= self.page_size:
            return []
        import hashlib

        cap_pages = (len(tokens) - 1) // self.page_size
        pages: list[int] = []
        prev = salt
        for i in range(cap_pages):
            chunk = tokens[i * self.page_size:(i + 1) * self.page_size]
            h = hashlib.sha256(prev)
            h.update(np.asarray(chunk, np.int64).tobytes())
            prev = h.digest()
            p = self._prefix_map.get(prev)
            if p is None:
                break
            pages.append(p)
        return pages

    def match_prefix(self, tokens, salt: bytes = b"") -> int:
        """Longest cached prefix of ``tokens`` in TOKENS."""
        return len(self._match_digests(tokens, salt)) * self.page_size

    def adopt_prefix(self, slot: int, tokens, salt: bytes = b"") -> int:
        """Map the longest cached prefix into ``slot``'s table (increfs the
        shared pages). Must be called before ``allocate`` grows the slot.
        Returns the number of cached tokens adopted.

        The adoption is PROVISIONAL: the caller either commits it
        (``commit_adopt`` — counts the hit in ``hit_tokens_total``) once
        the admission goes through, or rolls it back (``rollback_adopt``)
        when allocation/validation fails — so a blocked admission
        retrying every engine iteration neither inflates the hit metric
        nor churns the LRU recency of the adopted pages."""
        pages = self._match_digests(tokens, salt)
        if not pages:
            return 0
        assert not self.slot_pages[slot], "adopt_prefix on a non-empty slot"
        self._adopt_snapshot[slot] = list(self._lru)
        for i, p in enumerate(pages):
            self.refcount[p] = self.refcount.get(p, 0) + 1
            self._lru.pop(p, None)  # referenced again: not evictable
            self.slot_pages[slot].append(p)
            self.page_tables[slot, i] = p
        return len(pages) * self.page_size

    def commit_adopt(self, slot: int, hit_tokens: int) -> None:
        """The adoption's admission succeeded: count the cache hit."""
        self._adopt_snapshot.pop(slot, None)
        self.hit_tokens_total += hit_tokens

    def rollback_adopt(self, slot: int) -> None:
        """Undo a provisional ``adopt_prefix``: decref the pages and
        restore the pre-adoption LRU order (a plain ``free`` would
        re-insert the cached pages at the NEWEST recency position, so a
        retrying admission would skew eviction order every iteration)."""
        snap = self._adopt_snapshot.pop(slot, None)
        self.free(slot)
        if snap is not None:
            restored: dict[int, None] = {
                p: None for p in snap if p in self._lru}
            for p in self._lru:  # anything newer keeps its relative order
                restored.setdefault(p, None)
            self._lru = restored

    def register_prefix(self, slot: int, tokens, salt: bytes = b"") -> None:
        """Publish ``slot``'s pages holding full pages of ``tokens`` so
        later prompts with the same prefix can adopt them."""
        if not self.prefix_caching:
            return
        for i, d in enumerate(self._digests(tokens, salt)):
            if i >= len(self.slot_pages[slot]):
                break
            if d in self._prefix_map:
                continue  # identical prefix already cached (dedup)
            p = self.slot_pages[slot][i]
            old = self._page_digest.get(p)
            if old is not None and old != d:
                continue  # page already published under another digest
            self._prefix_map[d] = p
            self._page_digest[p] = d

    def prefix_digests(self) -> "list[bytes]":
        """Snapshot of the published device prefix-cache digests, for the
        replica's /ready membership filter. Server threads call this off
        the engine thread; the engine mutates ``_prefix_map`` without a
        lock, so retry the rare resize-during-copy race instead of adding
        locking to the admission hot path — the filter is a routing hint
        and a one-cycle-stale (or empty) snapshot is harmless."""
        for _ in range(4):
            try:
                return list(self._prefix_map)
            except RuntimeError:
                continue
        return []


class HostKVCache:
    """Host-RAM offload tier for inactive sessions' KV pages
    (AttentionStore/CachedAttention pattern; PAPERS.md).

    Device HBM holds the pages of RESIDENT streams; when a slot is freed
    (finish) or preempted, its full pages spill here — one entry per
    page, keyed by (tenant, chained-prefix digest), the SAME digest the
    PageAllocator's device prefix cache chains (salt included), so a
    returning session's token stream addresses both tiers with one hash
    pass. On a matching resume the engine re-uploads the pages and skips
    straight to decode for the covered tokens instead of re-prefilling
    (engine._adopt_cached_prefix), which turns per-chip session capacity
    from "resident streams" into "resident + parked sessions".

    Payloads are raw pool bytes per page, all layers stacked —
    ``{"k": [n_kv, L, page, d], "v": ..., "ks": [n_kv, L, page] | None,
    "vs": ...}`` (int8 data + f32 scales for quantized pools, the pool
    dtype otherwise; a pool of paired 64-wide heads gives its own rows,
    [n_kv/2, L, page, 128]) — so a reuse round-trips the exact bytes the device
    wrote and greedy streams stay bit-identical with the tier on or off.

    Keyed by tenant so one tenant's sessions can never be served another
    tenant's KV even on a (cryptographically impossible) digest collision,
    and so per-tenant flushes stay possible. Plain LRU over bytes. The
    engine thread owns the hot paths, but the disaggregated KV handoff
    (openai_api) reads/writes the tier from server threads — a prefill
    replica exports pages to a pulling decode replica, which ingests them
    locally before submitting — so every entry-map touch takes ``_lock``.
    """

    def __init__(self, capacity_bytes: int, page_size: int):
        import threading

        self.capacity_bytes = int(capacity_bytes)
        self.page_size = page_size
        self._entries: "dict[tuple[str, bytes], dict]" = {}
        self._bytes = 0
        self._lock = threading.RLock()
        self.hits = 0        # pages served to a resuming session
        self.misses = 0      # lookups where the chain had no next page
        self.evictions = 0   # pages dropped by LRU pressure
        self.spilled_pages = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def used_bytes(self) -> int:
        return self._bytes

    @staticmethod
    def _nbytes(payload: dict) -> int:
        return sum(int(a.nbytes) for a in payload.values() if a is not None)

    def put(self, tenant: str, digest: bytes, payload: dict) -> None:
        with self._lock:
            key = (tenant, digest)
            old = self._entries.pop(key, None)
            if old is not None:  # same prefix re-spilled: refresh recency
                self._bytes -= self._nbytes(old)
            nb = self._nbytes(payload)
            if nb > self.capacity_bytes:
                return  # one page larger than the whole tier: unconfigurable
            self._entries[key] = payload
            self._bytes += nb
            self.spilled_pages += 1
            while self._bytes > self.capacity_bytes and self._entries:
                k, v = next(iter(self._entries.items()))
                if k == key:  # never evict the page just stored
                    break
                del self._entries[k]
                self._bytes -= self._nbytes(v)
                self.evictions += 1

    def match_chain(self, tenant: str, digests: "list[bytes]",
                    start: int) -> "tuple[list[bytes], list[dict]]":
        """(matched digests, payloads) for the longest run of consecutive
        pages present, walking ``digests[start:]``. Pure peek: no stats,
        no recency — a blocked admission re-probes every engine iteration
        and must not spin the hit/miss counters or churn the LRU order.
        Call :meth:`commit` once when the admission actually lands."""
        matched: "list[bytes]" = []
        out: "list[dict]" = []
        with self._lock:
            for d in digests[start:]:
                e = self._entries.get((tenant, d))
                if e is None:
                    break
                matched.append(d)
                out.append(e)
        return matched, out

    def digests(self) -> "list[bytes]":
        """Digest part of every resident entry key (all tenants), for the
        replica's /ready membership filter. Pure peek — no stats, no
        recency. The filter is digest-only: tenancy is still enforced at
        adoption time by the (tenant, digest) entry key, a cross-tenant
        filter hit just fails to match there and re-prefills."""
        with self._lock:
            return [d for (_t, d) in self._entries]

    def export(self, tenant: str, digests: "list[bytes]") \
            -> "list[Optional[dict]]":
        """Payloads for a decode replica pulling a handoff, one per
        digest (None where the page is gone — evicted or never spilled).
        Pure peek like :meth:`match_chain`: the prefill replica's stats
        describe ITS sessions, and the decode replica counts the
        adoption outcome on its side."""
        with self._lock:
            return [self._entries.get((tenant, d)) for d in digests]

    def commit(self, tenant: str, digests: "list[bytes]") -> None:
        """Record a landed admission's outcome: one hit per page served
        (refreshing its LRU recency), or one miss for an empty match.
        Entries evicted between probe and commit are skipped silently —
        the engine uploads the payload objects it captured at probe time,
        so the reuse itself is unaffected."""
        served = 0
        with self._lock:
            for d in digests:
                key = (tenant, d)
                e = self._entries.pop(key, None)
                if e is None:
                    continue
                self._entries[key] = e  # move-to-end: LRU recency
                served += 1
            if served:
                self.hits += served
            else:
                self.misses += 1


def payload_shape_ok(payload, cache_config) -> bool:
    """True iff a host-tier payload has exactly the per-page shapes and
    dtypes this engine's pools expect.

    The engine's own spills are well-formed by construction; this guards
    the HANDOFF ingest path, where payloads crossed a network from a
    replica that may run a different model/topology (or were corrupted in
    flight). A payload that fails is treated as a missing page — the
    adoption chain stops and the remainder re-prefills (degraded, counted)
    instead of crashing in ``np.stack`` or splicing wrong-shaped bytes
    into the pools."""
    cc = cache_config
    if not isinstance(payload, dict):
        return False
    k, v = payload.get("k"), payload.get("v")
    ks, vs = payload.get("ks"), payload.get("vs")
    heads, lanes = cc.pool_row
    want = (heads, cc.num_layers, cc.page_size, lanes)
    data_dtype = np.dtype(np.int8 if cc.kv_dtype == "int8"
                          else jnp.dtype(cc.dtype).name)
    for side in (k, v):
        if (not isinstance(side, np.ndarray) or side.shape != want
                or side.dtype != data_dtype):
            return False
    if cc.kv_dtype == "int8":
        want_s = (cc.num_kv_heads, cc.num_layers, cc.page_size)
        for scale in (ks, vs):
            if (not isinstance(scale, np.ndarray) or scale.shape != want_s
                    or scale.dtype != np.dtype(np.float32)):
                return False
    elif ks is not None or vs is not None:
        return False
    return True
