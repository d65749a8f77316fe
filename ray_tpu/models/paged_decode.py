"""Paged KV cache + paged decode for the serving engine.

Reference capability: the reference serves LLMs through vLLM's PagedAttention
(external engine); here paging is first-class and TPU-native. The KV cache
is ONE PAGE POOL a side, [n_kv, L * total_pages, page_size, D]: layer ``l``
owns pages ``l*P .. l*P + P - 1`` (P = total_pages) and a page id means the
same page of every layer's block. Each slot owns a list of page ids
recorded in a device block table [num_slots, max_pages_per_slot].
HBM is committed per-request (ceil((prompt+max_tokens)/page_size) pages),
not per-slot*max_seq — so slot count is bounded by real demand, and short
requests do not pay for max_seq rows.

Prefill is one compiled program per prompt bucket and row count, and what a
call of it costs follows the prompts' LENGTHS, not the bucket: the work of a
layer that is independent row by row (everything but attention) walks each
prompt in pieces of ``PREFILL_PIECE`` rows and skips the pieces past the
prompt's length, and the head runs over the one row a prompt reads. Decode is
ONE compiled program for the whole batch: ``paged_decode_steps`` lax.scans T
greedy/temperature ticks on the device, feeding each sampled token into the
next, so one host round trip buys T tokens a slot.

Decode attention runs the repo's Pallas kernel (``ops/paged_attention.py``):
reads of exactly the pages that hold a live slot's rows, no gather
materialization, and nothing at all for a slot that is not active: the
decode step hands it a length of 0 there and gets zeros back. A reference
gather path computes the same thing for CPU tests and for head dims the
kernel does not tile. Which of the two a decode program holds is its
builder's decision (``use_kernel``), made once and visible in the lowered
text (``tpu_custom_call``); nothing inside the traced function asks the
backend. Both read the same pool through the same table, offset by ``l*P``.
This family's own rows follow the same decision (``_write_token_rows``): one
in-place Pallas call a layer for K and V (``ops/token_rows.py``) beside the
attention kernel, a scatter a pool beside the gather.

Layout notes:
- the pool's shape is the kernel's operand shape, so the layer scan hands it
  the whole pool and ``table + l*P``; no layer is sliced out, re-laid or
  written back. A decode step costs what it touches, not what is reserved
  (a 5-D [L, n_kv, P, ps, D] carry cost six copies of a pool layer a layer a
  step: 28 of 38 ms at 1537 pages on a v5e).
- page_size is a multiple of 8 (TPU sublane) and prefill buckets are
  multiples of page_size so prompt K/V scatter is a clean reshape-scatter.
- the pool rides the layer scan as CARRY (not xs/ys, which would stack a
  copy of it a layer) and is DONATED through jit, so XLA updates it in place.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig, llama_init as init_params  # noqa: F401
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.ops.token_rows import fits as _row_writer_fits, token_rows_write


class PagedKVCache(NamedTuple):
    k: jax.Array  # [n_kv, L * total_pages, page_size, D]
    v: jax.Array  # [n_kv, L * total_pages, page_size, D]


def init_paged_cache(config: LlamaConfig, total_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> PagedKVCache:
    shape = (config.num_kv_heads, config.num_layers * total_pages, page_size,
             config.head_dim_)
    return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


# what ``serve/llm.py`` asks of a model's module (``_model_of``) beside the
# two ``make_*`` functions and ``paged_kernel_fits``: this family keeps pages
# only, nothing a slot
SLOT_STATE = False
# what the prefill program counts, its third result: the rows its row-wise
# work ran (live pieces x piece rows, summed over the call's rows, ONE layer's:
# every layer runs the same; a pad row has length 1 and costs one piece)
PREFILL_COUNTERS = ("prefill_rows_computed",)
# rows of ONE prompt the row-wise work of a prefill layer takes at a time
# (``_walk``: norm, q/k/v and rotary in front of attention; W_o, norm and the
# MLP behind it): a piece that starts at or past its prompt's length is not
# computed, so a prompt of 600 tokens in the 2,048 bucket runs 1,024 rows. A
# bucket of one piece has nothing to skip and runs whole, all rows in one
# product. 512 is the smallest piece that keeps the MXU's rate (a v5e, W_o +
# MLP of a Mistral-7B layer over 8,192 rows, PERF.md 6, PR 45: whole 165
# TFLOP/s, pieces of 1,024 187, of 512 183, of 256 130, of 128 78), and the
# 4 x 2048 program with every prompt full costs 185.9 ms at 512 where it
# costs 183.5 unwalked and 201.6 at 256
PREFILL_PIECE = 512


def init_cache(config: LlamaConfig, num_slots: int, total_pages: int,
               page_size: int) -> PagedKVCache:
    return init_paged_cache(config, total_pages, page_size)


def _project_qkv(config: LlamaConfig, lp: Dict[str, Any], x):
    """x: [B, T, H] -> q [B,T,nh,hd], k/v [B,T,nkv,hd] (pre-rope)."""
    b, t, _ = x.shape
    nh, nkv, hd = config.num_heads, config.num_kv_heads, config.head_dim_
    y = rms_norm(x, lp["attn_norm"], config.rms_eps)
    q = (y @ lp["wq"]).reshape(b, t, nh, hd)
    k = (y @ lp["wk"]).reshape(b, t, nkv, hd)
    v = (y @ lp["wv"]).reshape(b, t, nkv, hd)
    return y, q, k, v


def _mlp(config: LlamaConfig, lp: Dict[str, Any], x):
    y = rms_norm(x, lp["mlp_norm"], config.rms_eps)
    gate = jax.nn.silu(y @ lp["w_gate"])
    up = y @ lp["w_up"]
    return (gate * up) @ lp["w_down"]


def _lm_head(params, x, config: LlamaConfig):
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T.astype(config.dtype)
    return (x @ head).astype(jnp.float32)


def sample_token(logits, key, temperature: float):
    """logits: [B, V]. temperature <= 0 -> greedy."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


def _pages_per_layer(pool, config: LlamaConfig) -> int:
    return pool.shape[1] // config.num_layers


def _scatter_token_rows(pool, rows, pages, rownum):
    """pool: [n_kv, L*P, ps, D]; rows: [B, n_kv, D]; pages (already offset
    to the layer's block) / rownum: [B]. One decoded token per slot, written
    into (head, page, row) of the whole donated pool.

    The index runs over n_kv as well, so the scatter's update window is D
    alone and XLA:TPU keeps the operand in row-major layout, the one the
    paged-attention kernel reads: the compiled decode program updates the
    pool in place and holds no operation the size of a pool layer. The
    shorter ``pool.at[:, pages, rownum]`` has a window of [n_kv, D]; XLA
    then wants n_kv next to D in the operand's layout and re-lays the whole
    pool around every scatter (tests/test_chip_compile.py holds the form).
    On a v5e the 512 row writes of 64 slots x 8 heads take 0.043 ms a call
    whatever the pool's size.

    This is the write of a decode program that holds no Pallas kernel
    (``use_kernel`` false: CPU tests, a head width the kernels do not tile),
    of pools or a batch the row writer does not take and of every family but
    this module's own, and it is what the kernel of this family's other
    programs is held to, bit for bit (``_write_token_rows``)."""
    vals = rows.transpose(1, 0, 2)  # [n_kv, B, D]
    heads = jnp.arange(pool.shape[0], dtype=jnp.int32)[:, None]
    return pool.at[heads, pages[None, :], rownum[None, :]].set(
        vals.astype(pool.dtype))


def _write_token_rows(pools, rows, pages, rownum, live, use_kernel: bool):
    """A tick's new rows into their pools: ``pools`` of one shape (K and V),
    as many ``rows`` [B, n_kv, D], ``pages`` / ``rownum`` as
    ``_scatter_token_rows``, ``live`` [B] the tick's ``active``. Returns the
    pools, a tuple.

    Where the decode program runs the Pallas kernels (``use_kernel``) and the
    kernel takes the pools (``ops/token_rows.py`` ``fits``: bfloat16, pages
    of whole 16-row tiles, whole lanes, the batch's tiles in VMEM), ONE
    in-place kernel call writes every pool's rows (``token_rows_write`` in a
    profile) and skips the slots that are not live; else a scatter a pool,
    above, which puts those slots' rows on the trash page their zeroed table
    rows name. Nothing reads that page: on every other page both leave the
    same bits in the same places.

    This family's decode tick alone calls it (PR 53). The other five serving
    families call ``_scatter_token_rows`` as they did (PERF.md 6, PR 53
    (v))."""
    if use_kernel and _row_writer_fits(pools, rows[0].shape[0]):
        return token_rows_write(pools, rows, pages, rownum, live)
    return tuple(_scatter_token_rows(pool, new, pages, rownum)
                 for pool, new in zip(pools, rows))


def _paged_attention_reference(q, k_pool, v_pool, table, lengths, scale,
                               starts=None):
    """Gather-based paged attention (CPU tests / non-TPU fallback).
    q: [B, nh, D]; pools: [n_kv, P_total, ps, D]; table: [B, max_pages];
    lengths: [B] (inclusive count of valid rows; 0: the slot holds nothing
    and its output is zeros, as the kernel's); starts: optional [B], the
    first row attended (a sliding window), as the kernel's."""
    b, nh, d = q.shape
    nkv, _, ps, _ = k_pool.shape
    max_pages = table.shape[1]
    # gather each slot's pages -> [B, n_kv, max_pages*ps, D]
    kg = k_pool[:, table]            # [n_kv, B, max_pages, ps, D]
    vg = v_pool[:, table]
    kg = kg.transpose(1, 0, 2, 3, 4).reshape(b, nkv, max_pages * ps, d)
    vg = vg.transpose(1, 0, 2, 3, 4).reshape(b, nkv, max_pages * ps, d)
    rep = nh // nkv
    qg = q.reshape(b, nkv, rep, d)
    logits = jnp.einsum("bnrd,bnsd->bnrs", qg, kg,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(max_pages * ps)[None, :] < lengths[:, None]  # [B, S]
    if starts is not None:
        mask = mask & (jnp.arange(max_pages * ps)[None, :] >= starts[:, None])
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnrs,bnsd->bnrd", probs.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(b, nh, d).astype(q.dtype)


def _paged_attention(q, k_pool, v_pool, table, lengths, scale,
                     use_kernel: bool, starts=None):
    """q: [B, 1, nh, D] -> [B, 1, nh, D]. ``starts``: the first row each
    slot attends over (a sliding layer), else row 0."""
    qs = (q[:, 0] * scale).astype(q.dtype)  # kernel does NOT scale q
    if use_kernel:
        return paged_attention(qs, k_pool, v_pool, lengths, table,
                               starts=starts)[:, None]
    out = _paged_attention_reference(qs, k_pool, v_pool, table, lengths, 1.0,
                                     starts)
    return out[:, None]


def _live_lengths(safe_pos, active):
    """Rows a slot attends over this tick: its clamped position inclusive,
    and 0 for an inactive slot. A never-used slot sits at position 0 and a
    retired one keeps the position it ended at: either would read as a
    length of 1 or more, a block of kernel work a head a layer a step over
    trash-page rows."""
    return jnp.where(active, safe_pos + 1, 0)


# --------------------------------------------------------------------------- #
# Window rings (models/laguna.py, models/phi4flash.py): a fixed ring of pages
# a slot a sliding layer, beside the pool and unseen by the allocator
# --------------------------------------------------------------------------- #
def ring_pages_of(window: int, page_size: int) -> int:
    """Pages that hold any ``window`` consecutive rows and the page being
    written: the window's first and last row are at most this many pages
    apart, whatever the alignment."""
    return (window - 1) // page_size + 2


def ring_prompt_pages(lengths, slots, ring: int, n_pages: int,
                      rings_per_layer: int, page_size: int):
    """What a prefill writes into the rings of one sliding layer: (src, the
    logical pages a ring keeps of each row, the last ``ring`` up to the page
    of the last real token, as an index for ``ring_rows``; dst [PB, ring],
    the ring page of each within the layer's block: logical page ``p`` at
    ring page ``p % ring`` of the row's slot, and those before the prompt's
    first page, which do not exist, in the trash ring)."""
    src = ((lengths - 1) // page_size)[:, None] - (ring - 1) \
        + jnp.arange(ring, dtype=jnp.int32)[None, :]
    dst = jnp.where(src >= 0, slots[:, None] * ring + src % ring,
                    rings_per_layer - ring + jnp.arange(ring)[None, :])
    return jnp.clip(src, 0, n_pages - 1)[:, :, None, None, None], dst


def ring_rows(rows, src, page_size: int):
    """rows: [PB, S, n_kv, D] -> the pages ``src`` names, as rows."""
    pb, s = rows.shape[:2]
    paged = rows.reshape(pb, s // page_size, page_size, *rows.shape[2:])
    return jnp.take_along_axis(paged, src, axis=1).reshape(
        pb, src.shape[1] * page_size, *rows.shape[2:])


def ring_tick(lengths, active, page_idx, window: int, ring: int,
              page_size: int):
    """A decode tick's view of every slot's ring: a sliding layer attends
    over rows [start, length) of it, handed to the kernel in logical order
    from the page that holds ``start``. Returns (start, table [B, ring] of
    ring pages within a layer's block, lengths and starts counted from the
    table's first page, the ring page the tick's token is written to: the
    trash ring's first for an inactive slot)."""
    nb = lengths.shape[0]
    start = jnp.maximum(lengths - window, 0)
    first_page = start // page_size
    slot_ring = jnp.arange(nb, dtype=jnp.int32) * ring
    table = slot_ring[:, None] + (
        first_page[:, None] + jnp.arange(ring, dtype=jnp.int32)[None, :]) % ring
    from_first = (lengths - first_page * page_size,
                  start - first_page * page_size)
    write = jnp.where(active, slot_ring + page_idx % ring, nb * ring)
    return (start, table, *from_first, write)


# --------------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------------- #
def _walk(fn, arrays, lengths, most: int):
    """``fn``: pieces [piece, ...] of ``arrays`` -> (a pytree of [piece, ...]
    arrays, counts int32 [k]), over the rows of ``arrays`` ([PB, S, ...]
    each) in the fewest equal pieces of at most ``most`` rows of ONE prompt
    (whole sublanes; a prompt whole where no such split exists), one piece
    after another inside the program. A piece that starts at or past its
    prompt's ``lengths`` entry is padding: it is NOT computed, its outputs
    are zeros and it counts nothing. Returns (the outputs as [PB, S, ...],
    the counts summed)."""
    pb, s = arrays[0].shape[:2]
    n = next((n for n in range(-(-s // most), s // 8 + 1)
              if s % n == 0 and (s // n) % 8 == 0), 1)
    piece = s // n
    cut = tuple(a.reshape(pb * n, piece, *a.shape[2:]) for a in arrays)
    live = (jnp.arange(n) * piece)[None, :] < lengths[:, None]      # [PB, n]
    blank = jax.tree.map(
        lambda x: jnp.zeros(x.shape, x.dtype),
        jax.eval_shape(fn, *(jax.ShapeDtypeStruct(c.shape[1:], c.dtype)
                             for c in cut)))

    def one(args):
        alive, *parts = args
        return jax.lax.cond(alive, lambda: fn(*parts), lambda: blank)

    out, counts = jax.lax.map(one, (live.reshape(-1), *cut))
    return (jax.tree.map(lambda a: a.reshape(pb, s, *a.shape[2:]), out),
            jnp.sum(counts, axis=0))


def _scatter_prompt_rows_full(pool, rows, pages):
    """pool: [n_kv, L*P, ps, D]; rows: [PB, S, n_kv, D] (S = NP*ps); pages:
    [PB, NP], already offset to the layer's block. Scatters every prompt's
    K/V pages into the whole pool in place (one scatter per layer, a page
    of every head, [n_kv, ps, D], as the window)."""
    *_, nkv, d = rows.shape
    page_size = pool.shape[2]
    vals = rows.reshape(pages.size, page_size, nkv, d).transpose(2, 0, 1, 3)
    return pool.at[:, pages.reshape(-1)].set(vals.astype(pool.dtype))


def _prompt_rows(fn, arrays, lengths):
    """A stretch of a prefill layer that is independent row by row. ``fn``:
    [B, T, ...] arrays -> a pytree of [B, T, ...] arrays; ``arrays``:
    [PB, S, ...] each. A bucket of one piece runs whole; a longer one walks
    each prompt in pieces of ``PREFILL_PIECE`` rows and skips those past its
    length, whose outputs are zeros. Returns (the outputs as [PB, S, ...],
    the rows computed, int32)."""
    pb, s = arrays[0].shape[:2]
    if s <= PREFILL_PIECE:
        return fn(*arrays), jnp.int32(pb * s)

    def piece(*parts):
        out = fn(*(part[None] for part in parts))
        return (jax.tree.map(lambda a: a[0], out),
                jnp.full((1,), parts[0].shape[0], jnp.int32))

    out, rows = _walk(piece, arrays, lengths, PREFILL_PIECE)
    return out, rows[0]


def paged_prefill(params, cache: PagedKVCache, tokens, pages, lengths,
                  config: LlamaConfig, page_size: int):
    """BATCHED prefill: tokens [PB, S_bucket] (padded, S_bucket %
    page_size == 0); pages [PB, S_bucket // page_size] page ids per prompt;
    lengths [PB] true prompt lengths (a pad row: 1 and the trash page); layer
    ``l`` writes them at ``l*P + pages``. Returns (last-token logits [PB, V],
    cache, int32 [1]: the ``PREFILL_COUNTERS`` of this call). Batching
    prompts of the same bucket into one program is what keeps admission off
    the serving critical path: 64 slots admit in ~8 programs instead of 64
    (the reference's analogue is vLLM's batched prefill scheduling).

    What a call costs follows ``lengths``: the row-wise work of a layer
    (``_prompt_rows``) skips a prompt's pieces of ``PREFILL_PIECE`` rows past
    its length, and the head runs over each prompt's last row alone. What
    the bucket still costs: attention, ONE causal call over all S_bucket rows
    (8% of a 2,048-row call); the rest of a prompt's last piece; one piece a
    pad row. K and V of a skipped piece are written as zeros, to pages no
    decode step reads: ``lengths`` bound every read."""
    from ray_tpu.ops.attention import attention

    pb, s = tokens.shape
    cos, sin = rope_frequencies(config.head_dim_, s, config.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (pb, s))
    x = params["embed_tokens"][tokens].astype(config.dtype)
    per_layer = _pages_per_layer(cache.k, config)

    def body(carry, layer):
        x, ck_full, cv_full = carry
        layer_pages = pages + layer * per_layer

        def of_layer(*names):
            return {name: params["layers"][name][layer] for name in names}

        # where a layer's weights are sliced out of the stack is what a walk
        # costs beside its products (a v5e's profile, PERF.md 6, PR 45): the
        # MLP's 0.35 GB and W_o fuse into their products as slices INSIDE a
        # piece, and handed to the walk from here they are copied a layer
        # (1 ms); q/k/v's 50 MB are a copy wherever they are sliced (the
        # product reads them a head at a time), so here, once a layer, and
        # not a piece (70 us x 16 pieces: the whole of what a full 4 x 2048
        # call lost against the unwalked program)
        attn = of_layer("attn_norm", "wq", "wk", "wv")

        def front(x, pos):
            _, q, k, v = _project_qkv(config, attn, x)
            return (apply_rope(q, cos, sin, positions=pos),
                    apply_rope(k, cos, sin, positions=pos), v)

        def back(x, o):
            lp = of_layer("wo", "mlp_norm", "w_gate", "w_up", "w_down")
            x = x + o @ lp["wo"]
            return x + _mlp(config, lp, x)

        (q, k, v), rows = _prompt_rows(front, (x, positions), lengths)
        o = attention(q, k, v, causal=True, impl=config.attention_impl)
        x, _ = _prompt_rows(back, (x, o.reshape(pb, s, -1)), lengths)
        ck_full = _scatter_prompt_rows_full(ck_full, k, layer_pages)
        cv_full = _scatter_prompt_rows_full(cv_full, v, layer_pages)
        return (x, ck_full, cv_full), rows

    (x, new_k, new_v), rows = jax.lax.scan(
        body, (x, cache.k, cache.v),
        jnp.arange(config.num_layers, dtype=jnp.int32))
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    logits = _lm_head(params, last, config)[:, 0]  # [PB, V]
    return logits, PagedKVCache(k=new_k, v=new_v), rows[:1]


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def paged_decode_one(params, cache: PagedKVCache, tokens, positions, active,
                     table, config: LlamaConfig, page_size: int,
                     use_kernel: bool) -> Tuple[jax.Array, PagedKVCache]:
    """One decode tick. tokens/positions/active: [B]; table: [B, max_pages].
    positions[b] = cache index the current token writes to; attention spans
    [0, positions[b]] inclusive. An inactive slot's table row is zeros, so
    its frozen write lands in page ``l*P + 0``, each layer's own trash page;
    it attends over nothing and its attention output is zeros."""
    scale = config.head_dim_ ** -0.5
    max_ctx = table.shape[1] * page_size
    cos, sin = rope_frequencies(config.head_dim_, max_ctx, config.rope_theta)
    x = params["embed_tokens"][tokens[:, None]].astype(config.dtype)  # [B,1,H]
    # clamp: a slot finishing mid-chunk keeps ticking to the chunk end (the
    # host truncates its output later); its position may overrun the table:
    # pin it to the table's last row
    safe_pos = jnp.minimum(positions, max_ctx - 1)
    pages = jnp.take_along_axis(
        table, (safe_pos // page_size)[:, None], axis=1)[:, 0]  # [B]
    rows = safe_pos % page_size
    lengths = _live_lengths(safe_pos, active)
    per_layer = _pages_per_layer(cache.k, config)

    def body(carry, lp):
        x, ck, cv, layer = carry
        y, q, _, v = _project_qkv(config, lp, x)
        q = apply_rope(q, cos, sin, positions=positions[:, None])
        # K's product goes into the rotary in float32 and is rounded ONCE,
        # after it: what XLA:TPU made of the bfloat16 product while the
        # rotary was its only reader (it fused the two and never rounded
        # between them); beside the row writer's custom call it would round
        # twice, and a reply would part from PR 52's on a near-tie
        # (_project_qkv's bfloat16 K is not used: dead code to the compiler)
        k = jnp.matmul(y, lp["wk"], preferred_element_type=jnp.float32)
        k = apply_rope(k.reshape(v.shape), cos, sin,
                       positions=positions[:, None]).astype(v.dtype)
        # layer l owns pages [l*P, (l+1)*P) of the one pool: the write and
        # the read both go through the offset, nothing is sliced out
        base = layer * per_layer
        ck, cv = _write_token_rows((ck, cv), (k[:, 0], v[:, 0]),
                                   pages + base, rows, active, use_kernel)
        o = _paged_attention(q, ck, cv, table + base, lengths, scale,
                             use_kernel)
        b, t, nh, hd = q.shape
        x = x + o.reshape(b, t, nh * hd) @ lp["wo"]
        x = x + _mlp(config, lp, x)
        return (x, ck, cv, layer + 1), None

    (x, new_k, new_v, _), _ = jax.lax.scan(
        body, (x, cache.k, cache.v, jnp.int32(0)), params["layers"]
    )
    logits = _lm_head(params, x, config)[:, 0]  # [B, V]
    return logits, PagedKVCache(k=new_k, v=new_v)


def paged_decode_steps(params, cache: PagedKVCache, tokens, positions, active,
                       table, key, config: LlamaConfig, num_steps: int,
                       page_size: int, use_kernel: bool,
                       temperature: float = 0.0):
    """T decode ticks on the device. tokens/positions/active: [B]; returns
    (sampled [B, T], last tokens [B], new positions [B], cache). An inactive
    slot still flows through the projections and the MLP: its position does
    not advance, its writes land in the trash page and its attention is
    skipped (length 0). The host has given every active slot the
    pages that cover positions+T (``PageAllocator``: at admission)."""

    def tick(carry, k_):
        toks, pos, cache = carry
        logits, cache = paged_decode_one(params, cache, toks, pos, active,
                                         table, config, page_size, use_kernel)
        nxt = sample_token(logits, k_, temperature)
        nxt = jnp.where(active, nxt, toks)
        new_pos = jnp.where(active, pos + 1, pos)
        return (nxt, new_pos, cache), nxt

    keys = jax.random.split(key, num_steps)
    (last, pos, cache), sampled = jax.lax.scan(
        tick, (tokens, positions, cache), keys
    )
    return sampled.T, last, pos, cache


def counted_decode_steps(decode_one, cache, tokens, positions, active, key,
                         num_steps: int, temperature: float, n_counters: int):
    """``paged_decode_steps`` for a family whose tick also counts:
    ``decode_one(cache, tokens, positions) -> (logits, cache, int32
    [n_counters])``. Returns (sampled [B, T], last tokens, new positions,
    cache, the counters summed over the ticks)."""

    def tick(carry, k_):
        toks, pos, cache, counts = carry
        logits, cache, step_counts = decode_one(cache, toks, pos)
        nxt = sample_token(logits, k_, temperature)
        nxt = jnp.where(active, nxt, toks)
        new_pos = jnp.where(active, pos + 1, pos)
        return (nxt, new_pos, cache, counts + step_counts), nxt

    keys = jax.random.split(key, num_steps)
    (last, pos, cache, counts), sampled = jax.lax.scan(
        tick, (tokens, positions, cache,
               jnp.zeros((n_counters,), jnp.int32)), keys)
    return sampled.T, last, pos, cache, counts


def paged_kernel_fits(config: LlamaConfig) -> bool:
    """The Pallas kernel (``ops/paged_attention.py``) tiles head_dim onto
    the 128-lane register file."""
    return config.head_dim_ % 128 == 0


def make_paged_decode_fn(config: LlamaConfig, num_steps: int, page_size: int,
                         temperature: float = 0.0, *, use_kernel: bool):
    """``use_kernel``: Pallas paged attention (a TPU, ``paged_kernel_fits``)
    or the gather reference. Required: the caller knows what it runs on.
    The partial is given the function's name, so a profile's module line
    reads ``jit_paged_decode_steps`` (a bare partial reads ``jit__unknown``)."""
    fn = functools.partial(paged_decode_steps, config=config,
                           num_steps=num_steps, page_size=page_size,
                           use_kernel=use_kernel, temperature=temperature)
    fn.__name__ = paged_decode_steps.__name__
    return jax.jit(fn, donate_argnums=(1,))


def make_paged_prefill_fn(config: LlamaConfig, page_size: int):
    fn = functools.partial(paged_prefill, config=config, page_size=page_size)
    fn.__name__ = paged_prefill.__name__  # jit_paged_prefill in a profile
    return jax.jit(fn, donate_argnums=(1,))


class PageAllocator:
    """Host-side free-list of KV pages (the vLLM block-manager analogue).
    Worst-case commitment at admission: a request takes
    ceil((prompt+max_tokens)/page_size) pages up front, so decode can never
    hit an out-of-pages condition mid-flight.

    PAGE 0 IS THE TRASH PAGE and is never handed out: inactive slots keep
    block-table rows of zeros, so their frozen-position writes inside the
    compiled decode loop land in page 0 (of each layer's block of the pool)
    instead of stomping a live slot's pages. Page ids are per layer: the
    allocator knows nothing of the pool's layer blocks."""

    TRASH_PAGE = 0

    def __init__(self, total_pages: int):
        self.total = total_pages
        self._free = list(range(total_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[list]:
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        return out

    def release(self, pages) -> None:
        for p in pages:
            assert p != self.TRASH_PAGE
        self._free.extend(pages)
