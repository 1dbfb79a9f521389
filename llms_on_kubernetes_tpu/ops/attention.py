"""Reference (pure-XLA) attention ops over the paged KV cache.

These are the semantically-authoritative implementations; the Pallas kernels
in ``pallas_flash.py`` / ``pallas_paged.py`` must match them bit-for-bit in
their tests (tolerance: bf16). They are also the CPU fallback path — the
"ramalama-equivalent" local deployment (reference ramalama-models/) runs the
same engine on XLA-CPU with these ops.

Layout choices (TPU-first):
- head_dim is the last (lane) axis, padded shapes are multiples of 128 for
  the models that matter (Llama/Mistral head_dim=128).
- GQA is expressed by reshaping q to [.., n_kv, group, ..] and einsumming
  against k/v at n_kv granularity — no materialized repeat_kv, so the MXU
  sees one big batched matmul and the KV HBM read happens once.
- All masking is additive in float32; softmax is computed in float32.
"""

from __future__ import annotations

import contextlib
import math
import os
import sys
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38  # large finite negative; avoids NaN from (-inf) - (-inf)


def pallas_mode() -> Optional[str]:
    """How the Pallas kernels run here: "compiled", "interpret" or None
    (the XLA reference ops). LLMK_ATTENTION_IMPL = pallas | xla | auto.

    auto (default) compiles the kernels on TPU and takes the XLA reference
    path everywhere else (CPU tests, local/ramalama-equivalent serving).
    ``pallas`` on the CPU backend runs them through the Pallas interpreter
    (how the CPU tests pin kernel semantics); the interpreter is never
    chosen on an accelerator.
    """
    impl = os.environ.get("LLMK_ATTENTION_IMPL", "auto")
    if impl not in ("pallas", "xla", "auto"):
        raise ValueError(
            f"LLMK_ATTENTION_IMPL={impl!r} is not one of pallas|xla|auto"
        )
    backend = jax.default_backend()
    if impl == "xla" or (impl == "auto" and backend != "tpu"):
        return None
    return "interpret" if backend == "cpu" else "compiled"


def _no_pallas_why() -> str:
    """Why pallas_mode() is None, for the dispatchers' records."""
    if os.environ.get("LLMK_ATTENTION_IMPL") == "xla":
        return "LLMK_ATTENTION_IMPL=xla"
    return f"{jax.default_backend()} backend"


def check_interpret(interpret: bool) -> bool:
    """Guard every ``pallas_call(interpret=...)``: on an accelerator the
    interpreter would run the kernel as slow XLA ops and still answer —
    exactly the kind of fallback that hides the device."""
    if interpret and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"Pallas interpret mode requested on the "
            f"{jax.default_backend()!r} backend; it is a CPU-only test path")
    return interpret


# Scratch + temporaries a kernel may claim of a TensorCore's VMEM (128 MiB
# on v4/v5e/v6e), leaving room for Mosaic's own internal scratch. The
# dispatchers check each kernel's estimate against it and take the XLA path
# (saying so) rather than let Mosaic discover the overflow.
VMEM_BUDGET_BYTES = 96 << 20

# op -> (impl, why): what each dispatcher last chose, recorded at trace
# time. Every change is printed once to stderr as
# "[attention] op=<op> impl=<impl> why=<why>" so a log reader outside the
# process (chip_smoke.py) can tell which implementation actually ran.
_chosen: dict[str, tuple[str, str]] = {}


# the kind of attention layer being traced ("sliding" | "full") in a stack
# that mixes them, else None: see ``layer_kind``
_kind: Optional[str] = None


@contextlib.contextmanager
def layer_kind(kind: Optional[str]):
    """Around the trace of one attention layer of a stack that mixes window
    and full layers: its dispatchers' records are keyed ``<op>_<kind>``
    (``decode_sliding``: one word, as the log's readers take an op), so
    that the two kinds do not overwrite each other's line, and its
    operations lie under ``attn.<kind>`` in a trace. None (every other
    model) changes nothing."""
    global _kind
    if kind is None:
        yield
        return
    before, _kind = _kind, kind
    try:
        with jax.named_scope(f"attn.{kind}"):
            yield
    finally:
        _kind = before


def record_choice(op: str, impl: str, why: str) -> None:
    """Which implementation an operator runs and why, once a change: the
    dispatchers here, and every other module that chooses one (the
    experts' product, the state-space scan), under this one tag, which the
    log readers know."""
    if _kind is not None and op in ("prefill", "chunk", "decode"):
        op = f"{op}_{_kind}"
    if _chosen.get(op) != (impl, why):
        _chosen[op] = (impl, why)
        print(f"[attention] op={op} impl={impl} why={why}",
              file=sys.stderr, flush=True)


def _model_shards() -> int:
    """Size of the active mesh's ``model`` axis (1 without a mesh)."""
    from llms_on_kubernetes_tpu.parallel.mesh import AXIS_MODEL, get_active_mesh

    mesh = get_active_mesh()
    return int(mesh.shape[AXIS_MODEL]) if mesh is not None else 1


def _per_kv_head_shard(fn, n_kv: int, args, head_axes, out_head_axes):
    """Run a Pallas call once per tensor-parallel shard.

    XLA cannot partition a custom call: given operands sharded over
    ``model`` it would first gather them (the whole KV pool) onto every
    chip. The pool and q/k/v are already sharded on their head axes
    (parallel/sharding.py), so each device runs the kernel on its own
    heads under ``shard_map``, as ops/cp.py does for ``seq``.
    ``head_axes[i]`` is the head axis of ``args[i]`` (None = replicated);
    ``out_head_axes`` mirrors ``fn``'s outputs. Calls ``fn(*args)``
    directly when there is nothing to shard: no mesh, model=1, or kv heads
    the model axis does not divide (the pool is then replicated, see
    parallel/sharding._axis)."""
    from jax.sharding import PartitionSpec as P

    from llms_on_kubernetes_tpu.parallel.mesh import AXIS_MODEL, get_active_mesh

    mesh, tp = get_active_mesh(), _model_shards()
    if tp == 1 or n_kv % tp != 0:
        return fn(*args)

    def spec(ax):
        return P() if ax is None else P(*([None] * ax), AXIS_MODEL)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(ax) for ax in head_axes),
        out_specs=jax.tree.map(spec, out_head_axes),
        check_vma=False,
    )(*args)


def softcap(logits: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    """Gemma-2-style tanh soft-capping (no-op when cap is None)."""
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def _gather_pool(pool, page_table, B: int, S: int, d: int) -> jnp.ndarray:
    """Materialize a pool's logical KV [n_kv, B, S, d] f32 through the page
    table, dequantizing per token when the pool is int8 (engine/cache.py
    KVPool). A pool whose rows hold several ``d``-wide heads side by side
    (cache.heads_per_row) is un-paired after the gather."""
    data = getattr(pool, "data", pool)   # raw arrays accepted (tests)
    rows, pair = data.shape[0], data.shape[3] // d
    x = data[:, page_table].reshape(rows, B, S, pair * d).astype(jnp.float32)
    if pair > 1:
        x = jnp.moveaxis(x.reshape(rows, B, S, pair, d), 3, 1)
        x = x.reshape(rows * pair, B, S, d)
    if getattr(pool, "quantized", False):
        s = pool.scale[:, page_table].reshape(rows, B, S)
        x = x * s[..., None]
    return x


def prefill_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    mm_groups: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Causal self-attention over a (padded) prompt chunk.

    q:       [B, T, n_q, d]
    k, v:    [B, T, n_kv, d]
    lengths: [B] int32 — true prompt lengths (<= T); keys at or beyond a
             sequence's length are masked out.
    mm_groups: optional [B, T] int32 — image-group id per position (-1 for
             text). Soft tokens of the SAME image attend bidirectionally
             to each other (gemma-3 semantics: the image-block override
             ORs over both the causal and the sliding-window constraint).
    returns  [B, T, n_q, d]
    """
    B, T, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv

    qg = q.reshape(B, T, n_kv, group, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # [B, n_kv, group, T(q), T(k)]
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, kf) * scale
    logits = softcap(logits, attn_softcap)

    q_pos = jnp.arange(T, dtype=jnp.int32)[:, None]   # [T, 1]
    k_pos = jnp.arange(T, dtype=jnp.int32)[None, :]   # [1, T]
    mask = k_pos <= q_pos                             # causal
    if sliding_window is not None:
        mask = mask & (k_pos > q_pos - sliding_window)
    mask = jnp.broadcast_to(mask[None], (B, T, T))
    if mm_groups is not None:
        same_image = ((mm_groups[:, :, None] >= 0)
                      & (mm_groups[:, :, None] == mm_groups[:, None, :]))
        mask = mask | same_image
    # pad mask: key beyond the sequence's true length
    valid = k_pos < lengths[:, None, None]            # [B, 1, T]
    mask = mask & valid                               # [B, T, T]
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)

    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, vf)
    return out.reshape(B, T, n_q, d).astype(q.dtype)


def paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
) -> jnp.ndarray:
    """Single-token decode attention against the paged KV cache.

    q:          [B, n_q, d]       — one new token per active slot
    k_pages:    [n_kv, P, page, d] — global page pool (this layer,
                head-major; [n_kv/2, P, page, 2d] where two heads share a row)
    v_pages:    [n_kv, P, page, d]
    page_table: [B, pages_per_seq] int32 — physical page ids per slot
    lengths:    [B] int32 — tokens in cache per slot INCLUDING the current
                token (i.e. the query attends to keys [0, lengths)).
    returns     [B, n_q, d]

    The gather materializes each slot's logical KV ([n_kv, B, S_max, d]);
    that is the XLA-reference strategy. The Pallas kernel streams pages
    through VMEM instead (pallas_paged.py).
    """
    B, n_q, d = q.shape
    page = k_pages.shape[2]
    pages_per_seq = page_table.shape[1]
    S = pages_per_seq * page

    k = _gather_pool(k_pages, page_table, B, S, d)
    v = _gather_pool(v_pages, page_table, B, S, d)
    n_kv = k.shape[0]
    group = n_q // n_kv
    qg = q.reshape(B, n_kv, group, d).astype(jnp.float32)

    logits = jnp.einsum("bkgd,kbsd->bkgs", qg, k) * scale   # [B, n_kv, g, S]
    logits = softcap(logits, attn_softcap)

    k_pos = jnp.arange(S, dtype=jnp.int32)[None, :]          # [1, S]
    mask = k_pos < lengths[:, None]                          # [B, S]
    if sliding_window is not None:
        q_pos = lengths[:, None] - 1
        mask = mask & (k_pos > q_pos - sliding_window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)

    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,kbsd->bkgd", probs, v)
    return out.reshape(B, n_q, d).astype(q.dtype)


def chunk_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    history: jnp.ndarray,
    chunk_lengths: jnp.ndarray,
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
) -> jnp.ndarray:
    """Prefill-with-history attention: a prompt CHUNK against the paged pool.

    The chunked-prefill path for prompts longer than the largest bucket
    (the reference's vLLM image served arbitrary lengths up to
    max-model-len; SURVEY §2.3 row 1): the chunk's KV has already been
    written into the pages, so each query at global position
    ``history + t`` attends causally to every cached key — previous
    chunks' AND this chunk's — through the page table.

    q:             [B, T, n_q, d]   — this chunk's queries
    k/v_pages:     [n_kv, P, page, d] (one layer, head-major)
    page_table:    [B, pages_per_seq] int32
    history:       [B] int32 — tokens cached BEFORE this chunk
    chunk_lengths: [B] int32 — valid tokens in this chunk (0 => idle row)
    returns        [B, T, n_q, d]
    """
    B, T, n_q, d = q.shape
    S = page_table.shape[1] * k_pages.shape[2]

    k = _gather_pool(k_pages, page_table, B, S, d)
    v = _gather_pool(v_pages, page_table, B, S, d)
    n_kv = k.shape[0]
    group = n_q // n_kv
    qg = q.reshape(B, T, n_kv, group, d).astype(jnp.float32)

    logits = jnp.einsum("btkgd,kbsd->bkgts", qg, k) * scale  # [B,n_kv,g,T,S]
    logits = softcap(logits, attn_softcap)

    q_pos = history[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B, T]
    k_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]               # [1, 1, S]
    mask = k_pos <= q_pos[:, :, None]                                   # causal
    # bound reads to the written region (garbage beyond history+chunk)
    mask = mask & (k_pos < (history + chunk_lengths)[:, None, None])
    if sliding_window is not None:
        mask = mask & (k_pos > q_pos[:, :, None] - sliding_window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)

    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgts,kbsd->btkgd", probs, v)
    return out.reshape(B, T, n_q, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek MLA) over a latent pool (engine/cache.py)
# ---------------------------------------------------------------------------
# A cached row is [c | k_r | 0...]: the normalised latent, the one roped
# key all heads share, and zeros up to whole 128-lane tiles. Prefill and
# chunks EXPAND it to per-head keys and values (k_n,h = c W_UK,h and v_h =
# c W_UV,h), a block of keys at a time; a decode step ABSORBS the expansion
# into the query and the output and attends the rows as they lie
# (multi-query attention with wide keys). W_UK [H, lat, nope] and W_UV
# [H, lat, v] are head-major, so each of these is a matmul batched over
# heads with the weight as it lies. Operands keep their type (bfloat16 on
# the chip) and every product accumulates in float32.
# The absorbed step has a kernel (pallas_paged.pallas_latent_attention,
# chosen by dispatch_latent_decode): the paged decode pipeline with one kv
# head of 640 lanes, 128 query heads to it and ONE pool that is key and, in
# its first lat lanes, value, so a live slot's pages cross HBM -> VMEM once
# and an idle slot's never. latent_paged_attention below is its reference
# and what every other backend, and a mesh, serve with. The expanded paths
# (buckets, chunks) have one too (pallas_flash.flash_latent_attention,
# chosen by dispatch_latent_prefill and dispatch_latent_chunk): a block of
# keys is expanded to a few heads at a time in VMEM and the score tiles,
# the running softmax and the accumulator never leave it.
# latent_expanded_attention below is its reference and the fall-back.

LATENT_KEY_BLOCK = 256


def latent_expanded_attention(qn, qr, rows, w_uk, w_uv, q_pos, kv_len, *,
                              scale: float, block: int = LATENT_KEY_BLOCK):
    """Causal attention of queries over latent rows, expanded to heads.

    qn, qr: [B, T, H, nope], [B, T, H, rope]  (the rope part rotated)
    rows:   [B, S, >= lat + rope]   latent rows of positions 0..S-1
    w_uk:   [H, lat, nope];  w_uv: [H, lat, v]
    q_pos:  [B, T] int32            each query's position among the rows,
                                    ascending along T
    kv_len: [B] int32               rows that are written (0 => idle row)
    returns [B, T, H, v]

    One batch row at a time. Within it a loop over blocks of ``block``
    keys, each expanded to heads ONCE, and under it a loop over the blocks
    of queries that can see the key block (causal: those whose last
    position is not before it), with a running softmax a query block. So
    no [H, T, S] scores exist (at 128 heads, 2,048 queries and 9,216 keys
    they would be 9.7 GB: a tile is [H, block, block]), a bucket pays for
    the lower triangle of its square, and a chunk for its history, not for
    the slot's whole window."""
    S, T = rows.shape[1], qn.shape[1]
    lat, rope = w_uk.shape[1], qr.shape[-1]
    block, qb = math.gcd(S, block), math.gcd(T, block)
    nq = T // qb

    def one_row(args):
        qn, qr, rows, q_pos, kv_len = args
        H = qn.shape[1]
        # [nq, H, qb, nope + rope]: one product a tile, not two and a sum
        q = jnp.moveaxis(jnp.concatenate([qn, qr], axis=-1).reshape(
            nq, qb, H, -1), 2, 1)
        pos = q_pos.reshape(nq, qb)

        def key_block(i, carry):
            blk = jax.lax.dynamic_slice_in_dim(rows, i * block, block, 0)
            c = blk[:, :lat]
            k = jnp.concatenate(
                [jnp.einsum("sr,hrk->hsk", c, w_uk),
                 jnp.broadcast_to(blk[None, :, lat:lat + rope],
                                  (H, block, rope))], axis=-1)
            v = jnp.einsum("sr,hrk->hsk", c, w_uv)
            k_pos = i * block + jnp.arange(block, dtype=jnp.int32)

            def query_block(j, carry):
                m, l, acc = carry
                at = lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, False)
                s = jnp.einsum("htk,hsk->hts", at(q), k,
                               preferred_element_type=jnp.float32) * scale
                mask = ((k_pos[None, :] <= at(pos)[:, None])
                        & (k_pos[None, :] < kv_len))[None]   # [1, qb, block]
                s = jnp.where(mask, s, NEG_INF)
                m_new = jnp.maximum(at(m), s.max(axis=-1))
                p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
                alpha = jnp.exp(at(m) - m_new)
                new = (m_new, at(l) * alpha + p.sum(axis=-1),
                       at(acc) * alpha[..., None] + jnp.einsum(
                           "hts,hsk->htk", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32))
                return tuple(jax.lax.dynamic_update_index_in_dim(a, n, j, 0)
                             for a, n in zip(carry, new))

            # the first block of queries whose last position reaches this
            # block of keys (positions ascend along T)
            first = jnp.sum(pos[:, -1] < i * block, dtype=jnp.int32)
            return jax.lax.fori_loop(first, nq, query_block, carry)

        init = (jnp.full((nq, H, qb), NEG_INF, jnp.float32),
                jnp.zeros((nq, H, qb), jnp.float32),
                jnp.zeros((nq, H, qb, w_uv.shape[2]), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (kv_len + block - 1) // block,
                                      key_block, init)
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # [nq, H, qb, v]
        return jnp.moveaxis(out, 1, 2).reshape(T, H, -1).astype(qn.dtype)

    return jax.lax.map(one_row, (qn, qr, rows, q_pos, kv_len))


def _gather_latent(pool, page_table):
    """A latent pool's rows [B, S, width] through the page table."""
    data = getattr(pool, "data", pool)[0]                    # [P, page, W]
    B, pps = page_table.shape
    return data[page_table].reshape(B, pps * data.shape[1], data.shape[2])


LATENT_DECODE_PAGES = 8


def latent_paged_attention(q_abs, pool, page_table, lengths, *, scale: float,
                           lat: int, block_pages: int = LATENT_DECODE_PAGES):
    """One decode token a slot against the latent pool, absorbed.

    q_abs:   [B, H, lat + rope]  = [q_n,h W_UK,h^T | q_r,h]
    pool:    [1, P, page, width >= lat + rope] (this layer's pages through
             the table; past lat + rope a row is zeros)
    lengths: [B] rows INCLUDING the current token's (already written)
    returns  o_lat [B, H, lat] = softmax(q_abs . row) . row[:lat], float32
             accumulation, in q_abs' type; W_UV is the caller's.

    A loop over blocks of ``block_pages`` pages of every slot, gathered
    through the table one block at a time, with a running softmax; it ends
    at the longest live slot's last block. Nothing the size of the slots'
    windows is ever written: gathered whole they are 377 MB a layer at 32
    slots of 9,216 tokens, written once and read twice."""
    data = getattr(pool, "data", pool)[0]                    # [P, page, W]
    B, H, _ = q_abs.shape
    page, W = data.shape[1:]
    bp = math.gcd(page_table.shape[1], block_pages)
    blk = bp * page
    q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, W - q_abs.shape[2])))

    def body(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(page_table, i * bp, bp, axis=1)
        rows = data[ids].reshape(B, blk, W)
        s = jnp.einsum("bhw,bsw->bhs", q_abs, rows,
                       preferred_element_type=jnp.float32) * scale
        mask = ((i * blk + jnp.arange(blk, dtype=jnp.int32))[None, :]
                < lengths[:, None])[:, None]                 # [B, 1, blk]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhs,bsr->bhr", p.astype(rows.dtype), rows[..., :lat],
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(axis=-1), acc

    init = (jnp.full((B, H), NEG_INF, jnp.float32),
            jnp.zeros((B, H), jnp.float32),
            jnp.zeros((B, H, lat), jnp.float32))
    _, l, acc = jax.lax.fori_loop(
        0, (jnp.max(lengths) + blk - 1) // blk, body, init)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_abs.dtype)


def _latent_mesh_why() -> str:
    """Why neither latent kernel runs under the active mesh ("" if it
    does): one row a token that every head shares, so there is no head
    axis to give each chip its own part of, as ``_per_kv_head_shard``
    does."""
    from llms_on_kubernetes_tpu.parallel.mesh import seq_parallelism

    if _model_shards() == 1 and seq_parallelism() == 1:
        return ""
    return (f"a mesh of model {_model_shards()} x seq {seq_parallelism()}: "
            "a latent pool has one head, the kernel is not partitioned")


def _latent_flash_mode(qn, qr, S, w_uk, w_uv):
    """(mode, why not) for the expanded paths' kernel
    (pallas_flash.flash_latent_attention) at a bucket of queries over S
    rows, as ``_latent_kernel_mode`` is for the decode kernel: from the
    widths, the bucket, the active mesh and the VMEM a program needs."""
    from llms_on_kubernetes_tpu.ops.pallas_flash import (
        latent_flash_blocks, latent_flash_vmem_bytes,
    )

    mode = pallas_mode()
    if mode is None:
        return None, _no_pallas_why()
    if _latent_mesh_why():
        return None, _latent_mesh_why()
    T, H, nope = qn.shape[1:]
    lat, vd = w_uk.shape[1], w_uv.shape[2]
    if mode == "compiled":
        # Mosaic's tiling: heads lie side by side on the lanes, queries and
        # keys on the sublanes of their blocks
        qb, kb, _ = latent_flash_blocks(T, S, H)
        for what, n in (("a latent", lat), ("an un-roped key", nope),
                        ("a value", vd), (f"a key block of {S} rows", kb)):
            if n % 128 != 0:
                return None, f"{what} of {n} is not a multiple of 128"
        if qb % 16 != 0:
            return None, f"a query tile of bucket {T} is {qb} rows, not 16s"
    need = latent_flash_vmem_bytes(T, S, H, lat, qr.shape[3], nope, vd,
                                   qn.dtype.itemsize)
    if need > VMEM_BUDGET_BYTES:
        return None, (f"bucket {T} needs {_mib(need)} VMEM > "
                      f"{_mib(VMEM_BUDGET_BYTES)} budget")
    return mode, ""


def _latent_flash(op, said, qn, qr, rows, w_uk, w_uv, history, kv_len, scale):
    """The expanded paths' one choice: the flash kernel over latent rows
    wherever ``_latent_flash_mode`` lets it, else the XLA loop
    (``latent_expanded_attention``) with the reason; ``said`` is the
    path's own half of the record."""
    from llms_on_kubernetes_tpu.ops.pallas_flash import (
        flash_latent_attention, latent_flash_blocks,
    )

    T, H = qn.shape[1:3]
    mode, why = _latent_flash_mode(qn, qr, rows.shape[1], w_uk, w_uv)
    if mode is None:
        record_choice(op, "xla",
                f"{said}, expanded to {H} heads a block of up to "
                f"{LATENT_KEY_BLOCK} keys at a time as far as the row's "
                f"last written block, bucket {T}; {why}")
        q_pos = history[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        return latent_expanded_attention(qn, qr, rows, w_uk, w_uv, q_pos,
                                         kv_len, scale=scale)
    qb, kb, hb = latent_flash_blocks(T, rows.shape[1], H)
    record_choice(op, f"pallas-{mode}",
            f"latent flash kernel: {said}, a block of {kb} keys expanded "
            f"to {hb} of {H} heads a program in VMEM, q.k "
            f"{qn.shape[3] + qr.shape[3]} wide, query tiles of {qb}, "
            f"bucket {T}")
    return flash_latent_attention(qn, qr, rows, w_uk, w_uv, history, kv_len,
                                  scale=scale, interpret=mode == "interpret")


def dispatch_latent_prefill(qn, qr, rows, w_uk, w_uv, lengths, *, scale):
    """A prompt bucket over its own latent rows (nothing cached is read)."""
    return _latent_flash("prefill", "the bucket's own latent rows", qn, qr,
                         rows, w_uk, w_uv, jnp.zeros_like(lengths), lengths,
                         scale)


def dispatch_latent_chunk(qn, qr, pool, page_table, w_uk, w_uv, history,
                          chunk_lengths, *, scale):
    """A chunk over the cached latents of its history and its own rows,
    which are already written: gathered through the page table (11.8 MB a
    layer at 9,216 tokens; the pool stays where it is), attended as far as
    the row's last written block."""
    return _latent_flash("chunk", "cached latent rows gathered through the "
                         "page table", qn, qr, _gather_latent(pool, page_table),
                         w_uk, w_uv, history, history + chunk_lengths, scale)


def _latent_kernel_mode(pool, page_table):
    """(mode, why not) for the latent decode kernel on this pool, as
    ``_paged_kernel_mode`` is for the K/V pools' kernels: from the pool's
    stored shape and type, the page table's width and the active mesh."""
    from llms_on_kubernetes_tpu.ops.pallas_paged import latent_vmem_bytes

    mode = pallas_mode()
    if mode is None:
        return None, _no_pallas_why()
    if _latent_mesh_why():
        return None, _latent_mesh_why()
    _, _, page, width = pool.shape
    if mode == "compiled":
        # Mosaic's tiling, as for the K/V pools: a page DMA is whole
        # 128-lane rows landing at a multiple of 8 sublanes
        if width % 128 != 0:
            return None, f"a pool row of {width} is not a multiple of 128"
        if page % 8 != 0:
            return None, f"a page of {page} is not a multiple of 8"
    need = latent_vmem_bytes(page, page_table.shape[1], width, pool.dtype)
    if need > VMEM_BUDGET_BYTES:
        return None, (f"a block of {width}-lane rows needs {_mib(need)} "
                      f"VMEM > {_mib(VMEM_BUDGET_BYTES)} budget")
    return mode, ""


def dispatch_latent_decode(q_abs, pool, page_table, lengths, *, scale, lat):
    """The absorbed decode step: the latent kernel
    (pallas_paged.pallas_latent_attention: a program a slot, each live
    slot's pages streamed through VMEM once, keys and values from the same
    block) wherever ``_latent_kernel_mode`` lets it, else the XLA loop,
    with its reason."""
    data = getattr(pool, "data", pool)
    heads, width = q_abs.shape[1], data.shape[3]
    mode, why = _latent_kernel_mode(data, page_table)
    if mode is None:
        record_choice("decode", "xla",
                f"absorbed: {heads} query heads over one "
                f"{q_abs.shape[-1]}-wide row a token, gathered "
                f"{LATENT_DECODE_PAGES * data.shape[2]} tokens of every slot "
                f"at a time as far as the longest slot's last block; {why}")
        return latent_paged_attention(q_abs, pool, page_table, lengths,
                                      scale=scale, lat=lat)

    from llms_on_kubernetes_tpu.ops.pallas_paged import pallas_latent_attention

    record_choice("decode", f"pallas-{mode}",
            f"latent: {heads} query heads over one {width}-lane row a "
            "token, live pages only")
    return pallas_latent_attention(q_abs, data, page_table, lengths,
                                   scale=scale, lat=lat,
                                   interpret=mode == "interpret")


# ---------------------------------------------------------------------------
# Dispatchers (what the decoder calls)
# ---------------------------------------------------------------------------
# Each dispatcher picks a compiled Pallas kernel, the interpreted kernel
# (CPU tests under LLMK_ATTENTION_IMPL=pallas) or the XLA reference op from
# what it can observe at trace time, and records the pick through _choose.

def _static_window(w) -> bool:
    # Gemma-style interleaved layers trace the window as a scalar inside
    # lax.scan; the Pallas kernels need it static -> fall back to XLA there.
    return w is None or isinstance(w, int)


def _mib(n: int) -> str:
    return f"{n / (1 << 20):.0f} MiB"


def dispatch_prefill_attention(q, k, v, lengths, *, scale, sliding_window=None,
                               attn_softcap=None, mm_groups=None):
    if mm_groups is not None:
        # multimodal prompts take the XLA reference path: the image-block
        # bidirectional mask is a [B, T, T] override the flash/ring
        # kernels don't express (yet)
        record_choice("prefill", "xla", "multimodal image-block mask")
        return prefill_attention(q, k, v, lengths, scale=scale,
                                 sliding_window=sliding_window,
                                 attn_softcap=attn_softcap,
                                 mm_groups=mm_groups)
    # Context parallelism: a seq>1 mesh shards the prompt over the ring
    # axis; the quadratic attention runs as ring attention (K/V blocks
    # rotate via ppermute over ICI) instead of gathering the full sequence
    # per device. Long-context prefill is exactly where this matters —
    # SURVEY §5 noted the reference had no long-context story at all.
    from llms_on_kubernetes_tpu.parallel.mesh import get_active_mesh, seq_parallelism

    static = _static_window(sliding_window)
    if seq_parallelism() > 1 and static:
        from llms_on_kubernetes_tpu.ops.ring_attention import ring_prefill_attention

        record_choice("prefill", "ring", f"seq-parallel mesh ({seq_parallelism()})")
        return ring_prefill_attention(
            q, k, v, lengths, get_active_mesh(), scale=scale,
            attn_softcap=attn_softcap, sliding_window=sliding_window,
        )
    mode = pallas_mode()
    why = None
    if mode is None:
        why = _no_pallas_why()
    elif not static:
        why = "traced (per-layer) sliding window"
    else:
        from llms_on_kubernetes_tpu.ops.pallas_flash import (
            BLOCK_Q, flash_prefill_attention, flash_vmem_bytes,
        )

        T, d = q.shape[1], q.shape[3]
        need = flash_vmem_bytes(T, d, q.dtype.itemsize)
        if T % min(BLOCK_Q, T) != 0:
            why = f"bucket {T} is not a multiple of {BLOCK_Q}"
        elif need > VMEM_BUDGET_BYTES:
            why = (f"bucket {T} needs {_mib(need)} VMEM > "
                   f"{_mib(VMEM_BUDGET_BYTES)} budget")
    if why is not None:
        record_choice("prefill", "xla", why)
        return prefill_attention(q, k, v, lengths, scale=scale,
                                 sliding_window=sliding_window,
                                 attn_softcap=attn_softcap)
    record_choice("prefill", f"pallas-{mode}", f"flash kernel, bucket {T}")
    return _per_kv_head_shard(
        lambda q, k, v, lengths: flash_prefill_attention(
            q, k, v, lengths, scale=scale, sliding_window=sliding_window,
            attn_softcap=attn_softcap, interpret=mode == "interpret"),
        k.shape[2], (q, k, v, lengths), (2, 2, 2, None), 2)


def dispatch_chunk_attention(q, k_pages, v_pages, page_table, history,
                             chunk_lengths, *, scale, sliding_window=None,
                             attn_softcap=None):
    from llms_on_kubernetes_tpu.parallel.mesh import seq_parallelism

    if seq_parallelism() > 1:
        # context-sharded pool: partial attention per page shard + one
        # psum merge (ops/cp.py). Traced (gemma interleaved) window sizes
        # are fine here — shard_map hoists closed-over tracers as
        # replicated inputs (pinned by tests/test_cp.py)
        from llms_on_kubernetes_tpu.ops.cp import cp_chunk_attention

        record_choice("chunk", "cp", f"seq-parallel mesh ({seq_parallelism()})")
        return cp_chunk_attention(
            q, k_pages, v_pages, page_table, history, chunk_lengths,
            scale=scale, sliding_window=sliding_window,
            attn_softcap=attn_softcap)
    mode, why, pages = _chunk_kernel_mode(q, k_pages, page_table,
                                          sliding_window)
    if mode is None:
        record_choice("chunk", "xla", why)
        return chunk_attention(q, k_pages, v_pages, page_table, history,
                               chunk_lengths, scale=scale,
                               sliding_window=sliding_window,
                               attn_softcap=attn_softcap)
    from llms_on_kubernetes_tpu.ops.pallas_flash import flash_chunk_attention

    record_choice("chunk", f"pallas-{mode}", why)
    B, d = q.shape[0], q.shape[3]
    page = k_pages.shape[2]
    base = jnp.zeros_like(history)
    if pages < page_table.shape[1]:
        # a window layer: from the page that holds the first query's
        # window edge (the slot's last pages where that would run past its
        # end); the kernel's positions count from that page's first row
        first = jnp.clip((history - sliding_window + 1) // page, 0,
                         page_table.shape[1] - pages)
        page_table = jnp.take_along_axis(
            page_table, first[:, None] + jnp.arange(pages)[None, :], axis=1)
        base = first * page

    def gathered(pool):
        data = getattr(pool, "data", pool)
        return data[:, page_table].reshape(data.shape[0], B, pages * page, d)

    return _per_kv_head_shard(
        lambda q, k, v, history, kv_len: flash_chunk_attention(
            q, k, v, history, kv_len, scale=scale,
            sliding_window=sliding_window, attn_softcap=attn_softcap,
            interpret=mode == "interpret"),
        k_pages.shape[0],
        (q, gathered(k_pages), gathered(v_pages), history - base,
         jnp.where(chunk_lengths > 0, history + chunk_lengths - base, 0)),
        (2, 0, 0, None, None), 2)


def _chunk_kernel_mode(q, k_pages, page_table, sliding_window):
    """(mode, what the record says, pages of a slot to gather) for a
    chunk over its slot's cached keys: pallas_mode() where
    ``pallas_flash.flash_chunk_attention`` applies (a static window, a
    pool of plain arrays with one head a page row, blocks on Mosaic's
    tiling and inside the VMEM budget), else None and the reason for the
    XLA gather path."""
    mode = pallas_mode()
    if mode is None:
        return None, _no_pallas_why(), None
    if not _static_window(sliding_window):
        return None, "traced (per-layer) sliding window", None
    if getattr(k_pages, "quantized", False):
        return None, "an int8 pool is dequantized by the XLA gather", None
    from llms_on_kubernetes_tpu.ops.pallas_flash import (
        chunk_flash_blocks, chunk_flash_vmem_bytes, chunk_gather_pages,
    )

    T, d = q.shape[1], q.shape[3]
    page, lanes = k_pages.shape[2], k_pages.shape[3]
    if lanes != d:
        return None, (f"the pool holds {lanes // d} heads of {d} to a "
                      f"{lanes}-lane page row"), None
    slot = page_table.shape[1] * page
    pages = chunk_gather_pages(T, page, page_table.shape[1], sliding_window)
    S = pages * page
    bq, kb = chunk_flash_blocks(T, S)
    if T % bq:
        return None, f"bucket {T} is not a multiple of {bq}", None
    if mode == "compiled" and (d % 128 or bq % 8 or kb % 128):
        return None, (f"head_dim {d}, {bq} queries or {kb} keys a block "
                      f"are off Mosaic's tiling"), None
    need = chunk_flash_vmem_bytes(T, S, d, q.dtype.itemsize)
    if need > VMEM_BUDGET_BYTES:
        return None, (f"{S} gathered keys need {_mib(need)} VMEM > "
                      f"{_mib(VMEM_BUDGET_BYTES)} budget"), None
    keys = f"a slot's {S}" if S == slot else f"{S} of a slot's {slot}"
    window = "" if sliding_window is None else \
        f" inside a window of {sliding_window}"
    return mode, (f"flash chunk kernel: {keys} gathered keys, blocks of "
                  f"{kb} a query block of {bq} can see{window}, bucket "
                  f"{T}"), pages


def _paged_kernel_mode(q, k_pages, page_table, sliding_window):
    """(mode, note) for the paged decode kernels on these operands: mode
    is pallas_mode() when a kernel applies, and the note is what the record
    adds to the kernel's name ("" or the paired layout); else mode is None
    and the note is the reason. Decided from the pool it is handed: its
    stored shape and dtype against q's head_dim."""
    from llms_on_kubernetes_tpu.engine.cache import heads_per_row
    from llms_on_kubernetes_tpu.ops.pallas_paged import paged_vmem_bytes

    mode = pallas_mode()
    if mode is None:
        return None, _no_pallas_why()
    if not _static_window(sliding_window):
        return None, "traced (per-layer) sliding window"
    quantized = getattr(k_pages, "quantized", False)
    kd = getattr(k_pages, "data", k_pages)
    rows, _, page, lanes = kd.shape
    d = q.shape[-1]
    if mode == "compiled":
        # Mosaic tiling on real TPU (the interpreter takes any shape): the
        # manual page DMA needs page rows of whole 128-lane tiles. Heads of
        # 128 and 256 are such rows; heads of 64 are where the pool holds
        # two to a row (cache.heads_per_row); any other width (96: Phi-3)
        # takes the XLA gather path, it is not padded. The int8 kernels'
        # per-token scale DMAs land at lane offset i*page_size
        if lanes % 128 != 0:
            why = f"head_dim {d} is not a multiple of 128"
            if d != 64:
                return None, (why + ", pairs into no 128-lane row, "
                              "is not padded")
            _, unpaired = heads_per_row(
                rows, d, "int8" if quantized else None, _model_shards())
            return None, (f"{why} and "
                          f"{unpaired or 'the pool holds one head a row'}")
        if quantized and page % 128 != 0:
            return None, f"int8 KV needs page_size % 128 == 0, got {page}"
    need = paged_vmem_bytes(rows, page, page_table.shape[1], lanes, kd.dtype,
                            quantized)
    if need > VMEM_BUDGET_BYTES:
        return None, (f"a block of {rows} x {lanes} heads needs "
                      f"{_mib(need)} VMEM > {_mib(VMEM_BUDGET_BYTES)} budget")
    if lanes != d:
        return mode, f", {lanes // d} heads of {d} to a {lanes}-lane page row"
    return mode, ""


def dispatch_paged_attention_write(q, k_pages, v_pages, page_table, lengths,
                                   k_new, v_new, write_positions, *, scale,
                                   sliding_window=None, attn_softcap=None):
    """Decode attention WITH the current token's KV append.

    Wherever the paged decode kernel applies (_paged_kernel_mode: compiled
    or interpreted Pallas, a static window, and on the chip a page row of
    whole 128-lane tiles (head_dim 128 or 256, or 64 in a pool that holds
    two heads to a row), a page that is a multiple of 8 and a block's
    staging inside the VMEM budget) and the mesh has no seq axis, the
    write folds INTO the attention kernel
    (pallas_paged.pallas_paged_attention_write): one program a slot, run in turn, each attending its row a 512-token block
    at a time while the next block (the next LIVE slot's first, from a
    row's last) and that slot's 8-row write block are already being
    fetched; a live slot's program splices the new row into its write
    block, DMAs it back into the pool in place and merges the current
    token's contribution in registers; an idle slot's moves nothing. The
    per-slot DUS write loop (2 x slots ops a layer, 1.9 ms of a 16.6 ms
    mistral-7b token step on a v5e: PERF.md §6, PR 34) is gone. int8 KV
    pools take the quantize-at-write twin
    (pallas_paged_attention_write_int8): the new row is quantized in
    registers with the same arithmetic as cache.quantize_kv, so pool
    bytes match the DUS path (on a v5e at mistral-7b's shapes the Mosaic
    kernel left the pool byte-identical to the DUS loop: PERF.md §6,
    PR 34; the int8 twin has not been timed in a cell: PERF.md §7).
    Everywhere else (a seq-parallel mesh, a traced window, a page row that
    is not a multiple of 128 lanes: head_dim 96, or 64 where
    cache.heads_per_row could not pair; int8 at a page that is not a
    multiple of 128; off the TPU) this is exactly write_then_attend. The
    choice is made from what is observed at trace time and from nothing a
    user or an engine can set, so it holds for a whole executable.

    q [B, n_q, d]; k_new/v_new [B, n_kv, d] (post-rope);
    write_positions [B, 1] (negative => idle/trash).
    Returns (attn [B, n_q, d], k_pages, v_pages)."""
    from llms_on_kubernetes_tpu.engine.cache import KVPool
    from llms_on_kubernetes_tpu.parallel.mesh import seq_parallelism

    mode = None
    kd_shape = getattr(k_pages, "data", k_pages).shape
    if seq_parallelism() == 1:
        mode, note = _paged_kernel_mode(q, k_pages, page_table,
                                        sliding_window)
        # the in-kernel append is an 8-token-block RMW (Mosaic sublane
        # tiling): sub-8 page sizes can't host an aligned block
        if mode == "compiled" and kd_shape[2] % 8 != 0:
            mode = None
    if mode is None:
        return write_then_attend(
            q, k_pages, v_pages, page_table, lengths, k_new, v_new,
            write_positions, scale=scale, sliding_window=sliding_window,
            attn_softcap=attn_softcap)

    from llms_on_kubernetes_tpu.ops import pallas_paged

    kw = dict(scale=scale, sliding_window=sliding_window,
              attn_softcap=attn_softcap, interpret=mode == "interpret")
    if getattr(k_pages, "quantized", False):
        record_choice("decode", f"pallas-{mode}", "fused int8 write+attend kernel")
        attn, kd, ks, vd, vs = _per_kv_head_shard(
            lambda *a: pallas_paged.pallas_paged_attention_write_int8(*a, **kw),
            kd_shape[0],
            (q, k_pages.data, k_pages.scale, v_pages.data, v_pages.scale,
             page_table, lengths, k_new, v_new),
            (1, 0, 0, 0, 0, None, None, 1, 1), (1, 0, 0, 0, 0))
        return attn, KVPool(kd, ks), KVPool(vd, vs)
    record_choice("decode", f"pallas-{mode}", "fused write+attend kernel" + note)
    attn, kd, vd = _per_kv_head_shard(
        lambda *a: pallas_paged.pallas_paged_attention_write(*a, **kw),
        kd_shape[0],
        (q, getattr(k_pages, "data", k_pages),
         getattr(v_pages, "data", v_pages), page_table, lengths, k_new, v_new),
        (1, 0, 0, None, None, 1, 1), (1, 0, 0))
    if hasattr(k_pages, "data"):
        return attn, KVPool(kd), KVPool(vd)
    return attn, kd, vd


def write_then_attend(q, k_pages, v_pages, page_table, lengths, k_new, v_new,
                      write_positions, *, scale, sliding_window=None,
                      attn_softcap=None):
    """The two-op decode step: the token's row written by
    cp.dispatch_write_tokens (the per-slot dynamic_update_slice loop of
    cache._write_rows), then dispatch_paged_attention over the pool. What
    dispatch_paged_attention_write falls back to, with its operands and
    result, and the reference the write-and-attend kernels are tested
    against."""
    from llms_on_kubernetes_tpu.ops.cp import dispatch_write_tokens

    k_pages, v_pages = dispatch_write_tokens(
        k_pages, v_pages, k_new[:, None], v_new[:, None], page_table,
        write_positions)
    attn = dispatch_paged_attention(
        q, k_pages, v_pages, page_table, lengths, scale=scale,
        sliding_window=sliding_window, attn_softcap=attn_softcap)
    return attn, k_pages, v_pages


def dispatch_paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                             scale, sliding_window=None, attn_softcap=None):
    from llms_on_kubernetes_tpu.parallel.mesh import seq_parallelism

    if seq_parallelism() > 1:
        # context-parallel decode: the pool is sharded over the seq axis,
        # so max context exceeds one device's page share; each device
        # attends over its own pages and one psum merges the partials
        # (traced gemma window sizes hoist through the shard_map fine)
        from llms_on_kubernetes_tpu.ops.cp import cp_paged_attention

        record_choice("decode", "cp", f"seq-parallel mesh ({seq_parallelism()})")
        return cp_paged_attention(
            q, k_pages, v_pages, page_table, lengths, scale=scale,
            sliding_window=sliding_window, attn_softcap=attn_softcap)
    mode, note = _paged_kernel_mode(q, k_pages, page_table, sliding_window)
    if mode is None:
        record_choice("decode", "xla", note)
        return paged_attention(q, k_pages, v_pages, page_table, lengths,
                               scale=scale, sliding_window=sliding_window,
                               attn_softcap=attn_softcap)

    from llms_on_kubernetes_tpu.ops import pallas_paged

    kw = dict(scale=scale, sliding_window=sliding_window,
              attn_softcap=attn_softcap, interpret=mode == "interpret")
    kd = getattr(k_pages, "data", k_pages)
    if getattr(k_pages, "quantized", False):
        record_choice("decode", f"pallas-{mode}", "int8 paged kernel")
        return _per_kv_head_shard(
            lambda *a: pallas_paged.pallas_paged_attention_int8(*a, **kw),
            kd.shape[0],
            (q, k_pages.data, k_pages.scale, v_pages.data, v_pages.scale,
             page_table, lengths),
            (1, 0, 0, 0, 0, None, None), 1)
    record_choice("decode", f"pallas-{mode}", "paged kernel" + note)
    return _per_kv_head_shard(
        lambda *a: pallas_paged.pallas_paged_attention(*a, **kw),
        kd.shape[0],
        (q, kd, getattr(v_pages, "data", v_pages), page_table, lengths),
        (1, 0, 0, None, None), 1)


# ---------------------------------------------------------------------------
# The Mamba layers' token step (no attention: it chooses as the others do)
# ---------------------------------------------------------------------------

def live_first(live: jnp.ndarray):
    """(slots [B] int32, n [1] int32): the numbers of the live rows first,
    in slot order, then the idle ones; and how many are live. What the
    state-space step kernel walks, made once a step for every layer."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return order, jnp.sum(live, dtype=jnp.int32).reshape(1)


def ssm_step_mode(ssm):
    """(mode, why not) for the state-space step kernel
    (pallas_ssm.pallas_ssm_step) on the state array ``ssm`` [n_layers,
    slots + 1, N, Di], as ``_latent_kernel_mode`` is for the latent pool's:
    from the array's type and widths, the active mesh and the VMEM a
    program needs. The engine asks too: where the kernel runs, a token step
    visits the live rows alone."""
    from llms_on_kubernetes_tpu.ops.pallas_ssm import (
        LANES, ssm_step_vmem_bytes,
    )
    from llms_on_kubernetes_tpu.parallel.mesh import get_active_mesh

    mode = pallas_mode()
    if mode is None:
        return None, _no_pallas_why()
    mesh = get_active_mesh()
    if mesh is not None and mesh.size > 1:
        return None, (f"a mesh of {mesh.size} devices: the kernel walks one "
                      "chip's state and is not partitioned")
    N, Di = ssm.shape[2:]
    if ssm.dtype != jnp.float32:
        return None, f"the state is kept in {ssm.dtype}, the kernel's is float32"
    if mode == "compiled":
        # Mosaic's tiling: a slot's block is whole (8, 128) float32 tiles,
        # worked through LANES lanes at a time
        if Di % LANES != 0:
            return None, f"{Di} channels are not a multiple of {LANES}"
        if N % 8 != 0:
            return None, f"{N} states are not a multiple of 8"
    need = ssm_step_vmem_bytes(ssm.shape[1] - 1, N, Di)
    if need > VMEM_BUDGET_BYTES:
        return None, (f"a slot's [{N}, {Di}] block needs {_mib(need)} VMEM > "
                      f"{_mib(VMEM_BUDGET_BYTES)} budget")
    return mode, ""


def dispatch_ssm_step(delta, A, x, Bm, Cm, ssm, layer, live, first):
    """One token's state-space update of one Mamba layer on the WHOLE state
    array: the kernel over the live slots (pallas_ssm.pallas_ssm_step: each
    live slot's block through VMEM once, in place, ``y`` summed from the
    block in hand; an idle slot and the trash row neither read nor written)
    wherever ``ssm_step_mode`` lets it, else the XLA step
    (decoder._ssm_scan's one-step branch on all B rows, old selected
    against new for the idle ones, all B written back), with its reason.

    delta, x [B, 1, Di] float32; A [N, Di]; Bm, Cm [B, 1, N]; ssm
    [n_layers, slots + 1, N, Di] (row i is slot i); ``layer`` its index;
    live [B] bool; ``first`` = ``live_first(live)``. An idle row's delta is
    0 and its x, B and C may be anything (``dispatch_conv_step``). Returns
    (y [B, 1, Di] float32, zeros for an idle row; ssm)."""
    B, _, Di = delta.shape
    N = A.shape[0]
    mode, why = ssm_step_mode(ssm)
    if mode is None:
        from llms_on_kubernetes_tpu.models.decoder import _ssm_scan

        record_choice("ssm_step", "xla",
                      f"every slot's [{N}, {Di}] block read, {B} rows "
                      f"computed, old selected against new and written "
                      f"back; {why}")
        old = jax.lax.dynamic_index_in_dim(ssm, layer, 0, False)[:B]
        y, h = _ssm_scan(delta, A, x, Bm, Cm, old.astype(jnp.float32))
        rows = jnp.where(live[:, None, None], h, old)
        return (jnp.where(live[:, None, None], y, 0.0),
                jax.lax.dynamic_update_slice(
                    ssm, rows.astype(ssm.dtype)[None], (layer, 0, 0, 0)))

    from llms_on_kubernetes_tpu.ops.pallas_ssm import pallas_ssm_step

    record_choice("ssm_step", f"pallas-{mode}",
                  f"live slots only: a slot's [{N}, {Di}] float32 block "
                  f"through VMEM once, updated and summed in place, of {B} "
                  "rows")
    y, ssm = pallas_ssm_step(delta[:, 0], A, x[:, 0], Bm[:, 0], Cm[:, 0], ssm,
                             layer, *first, interpret=mode == "interpret")
    # an idle row's y is whatever its buffer held
    return jnp.where(live[:, None], y, 0.0)[:, None], ssm


def conv_token_step(past, x0: jnp.ndarray, w, live: jnp.ndarray):
    """One token of a causal depthwise convolution over a kept window, in
    one pass: ``past`` the window's taps - 1 inputs [B, D] each, oldest
    first; x0 [B, D] the token's input; ``w`` the taps' weights, oldest
    first, each [D] float32; live [B, 1]. Returns (the sum at the token
    [B, D] float32, added oldest tap first as the general ``T``-position
    code adds it; the window after the token, tap by tap in x0's type:
    shifted by one with x0 last for a live row, as it was for an idle
    one). That select is the token step's ONE: nothing downstream selects
    old against new again."""
    taps = [*(t.astype(x0.dtype) for t in past), x0]
    c = sum(t.astype(jnp.float32) * w[j] for j, t in enumerate(taps))
    return c, [jnp.where(live, new, old) for old, new in zip(taps, taps[1:])]


def _conv_tile_rows(B: int) -> int:
    """Rows of a tile of the convolution step kernel at B rows (the
    interpreter takes rows that no tile divides as one tile)."""
    from llms_on_kubernetes_tpu.ops.pallas_conv import TILE_ROWS

    return TILE_ROWS if B % TILE_ROWS == 0 else B


def live_tiles_first(live: jnp.ndarray):
    """(tiles [B / rows] int32, n [1] int32): the tiles of rows that hold a
    live row first, in order, then the others; and how many hold one. What
    the convolution step kernel visits, made once a step for every
    layer."""
    held = live.reshape(-1, _conv_tile_rows(live.shape[0])).any(axis=1)
    order = jnp.argsort(~held, stable=True).astype(jnp.int32)
    return order, jnp.sum(held, dtype=jnp.int32).reshape(1)


def conv_step_mode(conv, x_dtype, B: int, Di: int):
    """(mode, why not) for the convolution step kernel
    (pallas_conv.pallas_conv_step) on the window array ``conv`` [n_layers,
    slots + 1, (taps - 1) Di] with B rows of x in ``x_dtype``, as
    ``ssm_step_mode`` is for the state-space step's: from the array's type
    and widths and the active mesh."""
    from llms_on_kubernetes_tpu.ops.pallas_conv import TILE_ROWS
    from llms_on_kubernetes_tpu.parallel.mesh import get_active_mesh

    mode = pallas_mode()
    if mode is None:
        return None, _no_pallas_why()
    mesh = get_active_mesh()
    if mesh is not None and mesh.size > 1:
        return None, (f"a mesh of {mesh.size} devices: the kernel walks one "
                      "chip's windows and is not partitioned")
    if conv.dtype != x_dtype:
        return None, f"the window is kept in {conv.dtype}, x comes in {x_dtype}"
    if mode == "compiled":
        # Mosaic's tiling: whole tiles of sublanes, whole lanes a tap
        if conv.dtype.itemsize not in (2, 4):
            return None, f"no tile of {TILE_ROWS} rows in {conv.dtype}"
        if B % TILE_ROWS != 0:
            return None, f"{B} rows are not a multiple of {TILE_ROWS}"
        if Di % 128 != 0:
            return None, f"{Di} channels are not a multiple of 128"
    return mode, ""


def dispatch_conv_step(xz, w, b, conv, layer, live, tiles):
    """One token's convolution of one Mamba layer on the WHOLE window
    array, in one pass either way: the kernel over the tiles that hold a
    live row (pallas_conv.pallas_conv_step: a tile of rows through VMEM
    once, in place; a tile with no live row and the trash row neither read
    nor written) wherever ``conv_step_mode`` lets it, else the XLA form
    (``conv_token_step`` on all B rows, written back), with its reason.

    xz [B, 2 Di]: the in-projection's result, x in its first Di lanes;
    w [taps, Di], b [Di] float32; conv [n_layers, slots + 1, (taps - 1) Di]
    (row i is slot i); ``layer`` its index; live [B] bool; ``tiles`` =
    ``live_tiles_first(live)``. Returns (xc = silu(the sum + b) [B, Di] in
    xz's type; the same in float32 for the state-space step, of an idle row
    anything; conv)."""
    B = xz.shape[0]
    taps, Di = w.shape
    mode, why = conv_step_mode(conv, xz.dtype, B, Di)
    if mode is None:
        record_choice("conv_step", "xla",
                      f"one pass, T = 1: {B} rows of {taps - 1} taps read, "
                      f"shifted, selected by liveness and written back; {why}")
        rows = jax.lax.dynamic_index_in_dim(conv, layer, 0, False)[:B]
        c, kept = conv_token_step(
            [rows[:, j * Di:(j + 1) * Di] for j in range(taps - 1)],
            xz[:, :Di], w, live[:, None])
        xc = jax.nn.silu(c + b).astype(xz.dtype)
        rows = jnp.concatenate(kept, axis=-1).astype(conv.dtype)
        return xc, xc.astype(jnp.float32), jax.lax.dynamic_update_slice(
            conv, rows[None], (layer, 0, 0))

    from llms_on_kubernetes_tpu.ops.pallas_conv import pallas_conv_step

    record_choice("conv_step", f"pallas-{mode}",
                  f"live slots only, a tile of {_conv_tile_rows(B)} rows at "
                  f"a time: {taps - 1} taps of {Di} a row through VMEM once, "
                  f"convolved and shifted in place, of {B} rows")
    xc, xf, conv = pallas_conv_step(xz, w, b, conv, layer, live, *tiles,
                                    interpret=mode == "interpret")
    # a row of a tile that was not visited is whatever its buffer held
    return jnp.where(live[:, None], xc, 0), xf, conv
