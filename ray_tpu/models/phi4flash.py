"""Phi-4-mini-flash class decoder for the serving engine (``model_type``
phi4flash, microsoft/Phi-4-mini-flash-reasoning; the SambaY
decoder-hybrid-decoder of arXiv:2507.06607 with differential attention):
pre-LayerNorm residual blocks, ``h += Mix_l(LN(h)); h += MLP(LN'(h))``, whose
mixer differs BY LAYER. With ``half = num_hidden_layers // 2``:

- ``mamba``  (even ``l <= half``): a Mamba-1 selective scan (``ops/ssm.py``
  ``mamba1_prefill`` / ``mamba1_step``) behind a causal convolution. Layer
  ``half`` also hands its scan output ``m`` (before the ``z`` gate) to the
  layers below it.
- ``window`` (odd ``l < half``): differential attention over the
  ``sliding_window`` newest keys.
- ``full``   (``l = half + 1``): the same over all keys. Its K/V are the
  model's ONLY global cache.
- ``gmu``    (even ``l >= half + 2``): a Gated Memory Unit,
  ``(m * silu(y W_1)) W_2`` with ``m`` of the same token. No state.
- ``cross``  (odd ``l >= half + 3``): differential attention with a query
  projection only, against the ``full`` layer's K/V.

No layer has a positional encoding. The head is the embedding, tied.

Differential attention: heads pair as ``(2j, 2j + 1)``; ``a_i = softmax(q_i
k_i^T / sqrt(D)) [v_1 | v_2]`` and the pair's output is ``RMSNorm_2D(a_1 -
lam a_2) (1 - lam0)``. A KV pair is stored PACKED, ``[k_1 | k_2]`` and ``[v_1
| v_2]`` as one row of ``2 D`` (128), and a query enters zero-padded on the
other half, ``[q_1 | 0]`` or ``[0 | q_2]``: the product with the packed row
is then ``q_i . k_i``, V is the 128 wide row the form asks for, and the
flash and paged kernels (``ops/attention.py``, ``ops/paged_attention.py``)
run as they are, at 10 KV heads of 128 and a query group of 4. The zero
half doubles the score product's operations and none of its bytes; a
contraction of 64 would fill half of a 128 deep MXU pass.

What a request keeps between steps, in one donated cache (``SambaCache``):

- PAGES from the engine's allocator for ONE layer (``full``), in
  ``models/paged_decode.py``'s pool layout ``[n_pairs, total_pages, ps, 2 D]``,
  written by that layer and read by it and by every ``cross`` layer;
- per SLOT, for every ``window`` layer, a RING of pages the allocator never
  sees, as ``models/laguna.py`` keeps them (``RING_FIELDS``);
- per SLOT, for every ``mamba`` layer, the scan state in float32,
  ``[L_m, slots + 1, N, Din // 128, 128]`` (``ops/ssm.py`` ``lanes``), and
  the convolution's last ``K - 1`` inputs. Row ``slots`` is the trash row.

A PROMPT NEVER GOES THROUGH THE CROSS-DECODER. ``paged_prefill`` runs layers
``0 .. half`` over every row, projects the ``full`` layer's K/V for every
row, and runs layers ``half + 1 .. L - 1`` over each prompt's LAST row only:
its query attends all of the prompt's K/V, its Gated Memory Units take ``m``
of that row. The first token needs no more, and decode needs of a prompt
only the cache. ``PREFILL_COUNTERS`` says which rows ran where.

Training of this family is not written.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models.paged_decode import (
    _live_lengths, _paged_attention, _scatter_prompt_rows_full,
    _scatter_token_rows, counted_decode_steps, ring_pages_of,
    ring_prompt_pages, ring_rows, ring_tick)
from ray_tpu.ops import ssm
from ray_tpu.ops.norms import layer_norm

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


@dataclasses.dataclass(frozen=True, eq=False)
class Phi4FlashConfig:
    """The source's key names (``config.json`` of ``model_type`` phi4flash
    and its configuration class's defaults); the defaults are
    Phi-4-mini-flash-reasoning whole."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    layer_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    scan_impl: str = "auto"

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.num_hidden_layers % 4:
            raise ValueError("the layer kinds are written for mb_per_layer 2 "
                             "and a depth that is a multiple of 4")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("differential attention pairs the query and the "
                             "KV heads, and query pairs divide over KV pairs")
        if self.mamba_inner % ssm.LANES:
            raise ValueError("the scan lays its channels out in 128 lanes")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_pairs(self) -> int:
        return self.num_key_value_heads // 2

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self):
        half = self.num_hidden_layers // 2

        def kind(l):
            if l <= half:
                return WINDOW if l % 2 else MAMBA
            if l == half + 1:
                return FULL
            return CROSS if l % 2 else GMU

        return tuple(kind(l) for l in range(self.num_hidden_layers))

    def count(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    @property
    def page_readers(self) -> int:
        """Layers that READ the one layer's pages in a decode tick."""
        return 1 + self.count(CROSS)

    @classmethod
    def tiny(cls, **kw) -> "Phi4FlashConfig":
        """CPU tests: every kind of layer (M W M W M F G X), two KV pairs of
        two query pairs each, a window of 32, 128 scan channels of 4 states."""
        kw.setdefault("max_seq_len", 512)
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
            sliding_window=32, mamba_d_state=4, mamba_dt_rank=8), **kw})


SLOT_STATE = True  # serve/llm.py: prefill is told each row's slot
RING_FIELDS = ("k_win", "v_win")  # the cache's fields that are window rings
# what the decode program counts on the device, its fifth result: the K/V
# rows attended in the ONE layer's pages over live slots, ticks and the
# layers that read them (``length`` x ``page_readers``), those attended in
# the rings (``min(length, window)`` a window layer), and the slot states
# the scan moved (active slots x scan layers)
DECODE_COUNTERS = ("attn_rows_shared", "attn_rows_window", "scan_slots")
# what the prefill program counts, its third result: prompt rows that ran the
# self-decoder (every real token of every real row) and rows that ran the
# cross-decoder (one a prompt: the last)
PREFILL_COUNTERS = ("prefill_rows_self", "prefill_rows_cross")
# rows of a prefill the MLP takes at a time: [g, u] of 16,384 rows is 0.67 GB
MLP_PREFILL_ROWS = 4096
# rows of a prefill the scan takes at a time, the state carried from piece to
# piece: dt, x and y in float32 and in the kernel's layout are 0.34 GB each
# at 16,384 rows, and two or three of each are alive around the call
SCAN_PREFILL_ROWS = 4096


class SambaCache(NamedTuple):
    k: jax.Array      # [n_pairs, total_pages, page_size, 2 D]: ONE layer's
    v: jax.Array
    k_win: jax.Array  # [n_pairs, L_win * (slots + 1) * ring, page_size, 2 D]
    v_win: jax.Array
    ssm: jax.Array    # [L_m, slots + 1, N, Din // 128, 128] float32
    conv: jax.Array   # [L_m, slots + 1, K - 1, Din]


def ring_pages(config: Phi4FlashConfig, page_size: int) -> int:
    return ring_pages_of(config.sliding_window, page_size)


def init_cache(config: Phi4FlashConfig, num_slots: int, total_pages: int,
               page_size: int) -> SambaCache:
    pairs, wide = config.kv_pairs, 2 * config.head_dim
    pool = (pairs, total_pages, page_size, wide)
    rings = (pairs, config.count(WINDOW) * (num_slots + 1)
             * ring_pages(config, page_size), page_size, wide)
    lm, din = config.count(MAMBA), config.mamba_inner
    return SambaCache(
        k=jnp.zeros(pool, config.dtype), v=jnp.zeros(pool, config.dtype),
        k_win=jnp.zeros(rings, config.dtype),
        v_win=jnp.zeros(rings, config.dtype),
        ssm=jnp.zeros((lm, num_slots + 1, config.mamba_d_state,
                       din // ssm.LANES, ssm.LANES), jnp.float32),
        conv=jnp.zeros((lm, num_slots + 1, config.mamba_d_conv - 1, din),
                       config.dtype))


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _normal(k, shape, fan_in, dtype):
    return (jax.random.normal(k, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def _norm_params(config: Phi4FlashConfig):
    h, dt = config.hidden_size, config.dtype
    return {"w": jnp.ones((h,), dt), "b": jnp.zeros((h,), dt)}


@functools.partial(jax.jit, static_argnames=("config", "kind"))
def init_layer(key, *, config: Phi4FlashConfig, kind: str) -> Dict[str, Any]:
    """One layer of ``kind``. Jitted by kind: ``init_params`` called eagerly
    compiles five small programs and not one of 32 unrolled layers (15.6 MB
    in the persistent compile cache: PERF.md 6, PR 38)."""
    h, dt, hd = config.hidden_size, config.dtype, config.head_dim
    nq, nkv = config.num_attention_heads, config.num_key_value_heads
    din, n, r, kc = (config.mamba_inner, config.mamba_d_state,
                     config.mamba_dt_rank, config.mamba_d_conv)
    f = config.intermediate_size
    ks = jax.random.split(key, 12)

    def bias(k, width):
        return (0.02 * jax.random.normal(k, (width,), jnp.float32)).astype(dt)

    if kind == MAMBA:
        step = jnp.exp(jax.random.uniform(
            ks[0], (din,), jnp.float32, math.log(0.001), math.log(0.1)))
        mixer = {
            "w_in": _normal(ks[1], (h, 2 * din), h, dt),
            "conv_w": _normal(ks[2], (kc, din), kc, dt),
            "conv_b": bias(ks[3], din),
            "w_x": _normal(ks[4], (din, r + 2 * n), din, dt),
            "w_dt": _normal(ks[5], (r, din), r, dt),
            "b_dt": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "a_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=jnp.float32), (din, n))),
            "d": jnp.ones((din,), jnp.float32),
            "w_out": _normal(ks[6], (din, h), din, dt),
        }
    elif kind == GMU:
        mixer = {"w1": _normal(ks[0], (h, din), h, dt),
                 "w2": _normal(ks[1], (din, h), din, dt)}
    else:
        cross = kind == CROSS
        mixer = {"w_o": _normal(ks[0], (nq * hd, h), nq * hd, dt),
                 "b_o": bias(ks[1], h), "subln": jnp.ones((2 * hd,), dt)}
        for i, name in enumerate(("lq1", "lk1", "lq2", "lk2")):
            mixer[name] = 0.1 * jax.random.normal(ks[2 + i], (hd,), jnp.float32)
        width = nq * hd if cross else (nq + 2 * nkv) * hd
        mixer["w_q" if cross else "w_qkv"] = _normal(ks[6], (h, width), h, dt)
        mixer["b_q" if cross else "b_qkv"] = bias(ks[7], width)
    return {"norm1": _norm_params(config), "norm2": _norm_params(config),
            "mixer": mixer, "w1": _normal(ks[8], (h, 2 * f), h, dt),
            "w2": _normal(ks[9], (f, h), f, dt)}


def init_params(config: Phi4FlashConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal / sqrt(fan_in) matrices, biases normal x 0.02,
    norms of one and zero; the differential vectors normal x 0.1 (the
    published initialisation); the scan's ``b_dt`` so that softplus gives a
    step log-uniform in [0.001, 0.1], ``A = -(1 .. N)`` a channel, ``D`` one
    (Mamba-1's). Traceable, and cheap to call eagerly (``init_layer``)."""
    return {
        "embed_tokens": _normal(jax.random.fold_in(key, 1000),
                                (config.vocab_size, config.hidden_size),
                                config.hidden_size, config.dtype),
        "layers": [init_layer(jax.random.fold_in(key, i), config=config,
                              kind=kind)
                   for i, kind in enumerate(config.layer_kinds)],
        "final_norm": _norm_params(config),
    }


# --------------------------------------------------------------------------- #
# Layer parts
# --------------------------------------------------------------------------- #
def _ln(config: Phi4FlashConfig, x, p):
    return layer_norm(x, p["w"], p["b"], config.layer_norm_eps)


def _mlp(lp, y):
    """y: [..., h] normed: ``[g, u] = y W1`` (gate first), ``(u silu(g)) W2``."""
    g, u = jnp.split(y @ lp["w1"], 2, axis=-1)
    return (u * jax.nn.silu(g)) @ lp["w2"]


def _mlp_rows(config: Phi4FlashConfig, lp, x):
    """``MLP(LN'(x))`` of a prefill's rows [T, h], ``MLP_PREFILL_ROWS`` at a
    time."""
    def rows(part):
        return _mlp(lp, _ln(config, part, lp["norm2"]))

    t = x.shape[0]
    if t > MLP_PREFILL_ROWS and t % MLP_PREFILL_ROWS == 0:
        return jax.lax.map(
            rows, x.reshape(-1, MLP_PREFILL_ROWS, x.shape[1])).reshape(x.shape)
    return rows(x)


def _padded_queries(q):
    """q: [..., n_q, D], head ``2j`` a pair's ``q_1`` and ``2j + 1`` its
    ``q_2`` -> [..., n_q, 2 D]: ``[q_1 | 0]`` and ``[0 | q_2]``, which against
    a packed row ``[k_1 | k_2]`` give ``q_1 . k_1`` and ``q_2 . k_2``."""
    *lead, nq, d = q.shape
    pairs = q.reshape(*lead, nq // 2, 2, d)
    zero = jnp.zeros_like(pairs[..., 0, :])
    return jnp.stack([
        jnp.concatenate([pairs[..., 0, :], zero], axis=-1),
        jnp.concatenate([zero, pairs[..., 1, :]], axis=-1)],
        axis=-2).reshape(*lead, nq, 2 * d)


def _queries(config: Phi4FlashConfig, mp, y):
    """y: [..., h] normed -> padded queries [..., n_q, 2 D], by a ``cross``
    layer's ``W_q`` or the query columns of ``W_qkv``."""
    nq, d = config.num_attention_heads, config.head_dim
    if "w_q" in mp:
        q = y @ mp["w_q"] + mp["b_q"]
    else:
        q = y @ mp["w_qkv"][:, :nq * d] + mp["b_qkv"][:nq * d]
    return _padded_queries(q.reshape(*y.shape[:-1], nq, d))


def _pack(config: Phi4FlashConfig, kv):
    """[..., 2 x n_kv x D], K's heads then V's -> K and V rows
    [..., n_pairs, 2 D], a pair's two heads side by side as they lie."""
    k, v = jnp.split(kv, 2, axis=-1)
    shape = (*kv.shape[:-1], config.kv_pairs, 2 * config.head_dim)
    return k.reshape(shape), v.reshape(shape)


def _packed_kv(config: Phi4FlashConfig, mp, y):
    """The K/V columns of ``W_qkv`` alone (the ``full`` layer in prefill,
    where every row needs K/V and one row a query)."""
    width = config.num_attention_heads * config.head_dim
    return _pack(config, y @ mp["w_qkv"][:, width:] + mp["b_qkv"][width:])


def _qkv(config: Phi4FlashConfig, mp, y):
    """One product: padded queries, K and V rows."""
    nq, d = config.num_attention_heads, config.head_dim
    qkv = y @ mp["w_qkv"] + mp["b_qkv"]
    q = qkv[..., :nq * d].reshape(*y.shape[:-1], nq, d)
    return (_padded_queries(q), *_pack(config, qkv[..., nq * d:]))


def _diff_out(config: Phi4FlashConfig, mp, o, layer: int):
    """o: [..., n_q, 2 D] attended, head ``2j`` the pair's ``a_1`` and
    ``2j + 1`` its ``a_2`` -> the layer's output [..., h]."""
    lam0 = lambda_init(layer)
    lam = jnp.exp(jnp.sum(mp["lq1"] * mp["lk1"])) \
        - jnp.exp(jnp.sum(mp["lq2"] * mp["lk2"])) + lam0
    *lead, nq, wide = o.shape
    a = o.astype(jnp.float32).reshape(*lead, nq // 2, 2, wide)
    diff = a[..., 0, :] - lam * a[..., 1, :]
    var = jnp.mean(diff * diff, axis=-1, keepdims=True)
    normed = diff * jax.lax.rsqrt(var + 1e-5) * mp["subln"].astype(jnp.float32)
    out = (normed * (1.0 - lam0)).astype(config.dtype)
    return out.reshape(*lead, nq * wide // 2) @ mp["w_o"] + mp["b_o"]


def _scan_inputs(config: Phi4FlashConfig, mp, xc):
    """xc: [..., Din] convolved and activated -> dt [..., Din] float32 after
    softplus, B and C [..., N]."""
    r, n = config.mamba_dt_rank, config.mamba_d_state
    parts = xc @ mp["w_x"]
    dt = jax.nn.softplus(
        jnp.matmul(parts[..., :r], mp["w_dt"],
                   preferred_element_type=jnp.float32) + mp["b_dt"])
    return dt, parts[..., r:r + n], parts[..., r + n:]


def _scan_rows(config: Phi4FlashConfig, mp, xin, lengths, state0):
    """The selective scan over a prefill's rows. xin: [PB, S, Din] convolved
    and activated -> (s [PB, S, Din], the state after each row's last real
    token). ``SCAN_PREFILL_ROWS`` at a time, each piece resuming from the
    state the one before left (``mamba1_prefill``'s ``state0``)."""
    a = -jnp.exp(mp["a_log"])

    def piece(state, part):
        rows, offset = part
        dt, b, c = _scan_inputs(config, mp, rows)
        y, state = ssm.mamba1_prefill(
            rows, dt, a, b, c, mp["d"], state,
            jnp.clip(lengths - offset, 0, rows.shape[1]), impl=config.scan_impl)
        return state, y

    pb, s, din = xin.shape
    n = SCAN_PREFILL_ROWS
    if s <= n or s % n:
        state, y = piece(state0, (xin, 0))
        return y, state
    state, y = jax.lax.scan(piece, state0, (
        xin.reshape(pb, s // n, n, din).swapaxes(0, 1),
        jnp.arange(0, s, n, dtype=jnp.int32)))
    return y.swapaxes(0, 1).reshape(pb, s, din), state


def _gated(mp, s, z):
    return (s * jax.nn.silu(z)) @ mp["w_out"]


def _memory(s, z):
    """What the last scan layer hands the Gated Memory Units: its scan
    output BEFORE the ``z`` gate."""
    return s


def _head(config: Phi4FlashConfig, params, x):
    y = _ln(config, x, params["final_norm"])
    return jnp.einsum("bh,vh->bv", y, params["embed_tokens"],
                      preferred_element_type=jnp.float32)


def _one_row_attention(q, k, v, lengths, scale):
    """q: [PB, n_q, 2 D], one query a prompt; k, v: [PB, S, n_pairs, 2 D] the
    prompt's rows -> [PB, n_q, 2 D] over rows ``[0, length)``."""
    pb, nq, wide = q.shape
    pairs = k.shape[2]
    qg = q.reshape(pb, pairs, nq // pairs, wide)
    scores = jnp.einsum("bngd,bsnd->bngs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, None, :], scores, -1e30), axis=-1)
    out = jnp.einsum("bngs,bsnd->bngd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(pb, nq, wide).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------------- #
def paged_prefill(params, cache: SambaCache, tokens, pages, lengths, slots,
                  config: Phi4FlashConfig, page_size: int,
                  cross_over_all_rows: bool = False):
    """BATCHED prefill: tokens [PB, S_bucket] right-padded; pages
    [PB, S_bucket // page_size]; lengths [PB] true lengths; slots [PB] the
    slot each row was admitted to (a pad row: the trash row). Layers
    ``0 .. half`` run over every row and leave the rings, the scan state and
    the convolution rows; the ``full`` layer writes every row's K/V pages;
    it and the layers below it run over each prompt's LAST row. Returns
    (last-token logits [PB, V], cache, int32 [2]: ``PREFILL_COUNTERS``).

    ``cross_over_all_rows``: the program that does NOT skip, every layer over
    every row, for the test that ties the skip to the model; the engine never
    builds it."""
    from ray_tpu.ops.attention import attention

    pb, s = tokens.shape
    ring = ring_pages(config, page_size)
    scale = config.head_dim ** -0.5
    x = params["embed_tokens"][tokens].astype(config.dtype)
    ck, cv, ckw, cvw, cs, cc = cache
    rings_per_layer = ckw.shape[1] // config.count(WINDOW)
    src, dst = ring_prompt_pages(lengths, slots, ring, s // page_size,
                                 rings_per_layer, page_size)
    last_row = (lengths - 1)[:, None, None]

    def last(rows):  # [PB, S, ...] -> the prompt's last real row [PB, ...]
        return jnp.take_along_axis(rows, last_row, axis=1)[:, 0]

    m = k = v = None
    w_idx = m_idx = 0
    for l, (kind, lp) in enumerate(zip(config.layer_kinds, params["layers"])):
        mp = lp["mixer"]
        if kind == FULL and not cross_over_all_rows:
            # the self-decoder is done: K/V of every row, then the last row
            with jax.named_scope("full_kv"):
                k, v = _packed_kv(config, mp, _ln(config, x, lp["norm1"]))
                ck = _scatter_prompt_rows_full(ck, k, pages)
                cv = _scatter_prompt_rows_full(cv, v, pages)
            x, m = last(x), last(m)
        with jax.named_scope(kind):
            y = _ln(config, x, lp["norm1"])
            if kind == MAMBA:
                xz = y @ mp["w_in"]
                xin, z = jnp.split(xz, 2, axis=-1)
                xin, kept = ssm.causal_conv_prefill(
                    xin, mp["conv_w"], mp["conv_b"], lengths)
                m, state = _scan_rows(
                    config, mp, jax.nn.silu(xin), lengths,
                    jnp.zeros((pb,) + cs.shape[2:], jnp.float32))
                cs = cs.at[m_idx, slots].set(state)
                cc = cc.at[m_idx, slots].set(kept.astype(cc.dtype))
                m_idx += 1
                out, m = _gated(mp, m, z), _memory(m, z)
            elif kind == WINDOW:
                q, kw, vw = _qkv(config, mp, y)
                o = attention(q, kw, vw, causal=True, scale=scale,
                              impl=config.attention_impl,
                              window=config.sliding_window)
                layer_rings = dst + w_idx * rings_per_layer
                ckw = _scatter_prompt_rows_full(
                    ckw, ring_rows(kw, src, page_size), layer_rings)
                cvw = _scatter_prompt_rows_full(
                    cvw, ring_rows(vw, src, page_size), layer_rings)
                w_idx += 1
                out = _diff_out(config, mp, o, l)
            elif kind == GMU:
                out = (m * jax.nn.silu(y @ mp["w1"])) @ mp["w2"]
            elif cross_over_all_rows:  # FULL or CROSS, every row a query
                if kind == FULL:
                    q, k, v = _qkv(config, mp, y)
                    ck = _scatter_prompt_rows_full(ck, k, pages)
                    cv = _scatter_prompt_rows_full(cv, v, pages)
                else:
                    q = _queries(config, mp, y)
                o = attention(q, k, v, causal=True, scale=scale,
                              impl=config.attention_impl)
                out = _diff_out(config, mp, o, l)
            else:                      # FULL or CROSS, the last row's query
                o = _one_row_attention(_queries(config, mp, y), k, v, lengths,
                                       scale)
                out = _diff_out(config, mp, o, l)
            x = x + out
            if x.ndim == 3:
                x = x + _mlp_rows(config, lp, x.reshape(pb * s, -1)).reshape(
                    x.shape)
            else:
                x = x + _mlp(lp, _ln(config, x, lp["norm2"]))
    if x.ndim == 3:
        x = last(x)
    real = slots < cs.shape[1] - 1  # a pad row's slot is the trash row
    crossed = lengths if cross_over_all_rows else jnp.ones_like(lengths)
    counts = jnp.stack([jnp.sum(jnp.where(real, lengths, 0)),
                        jnp.sum(jnp.where(real, crossed, 0))]).astype(jnp.int32)
    return (_head(config, params, x), SambaCache(ck, cv, ckw, cvw, cs, cc),
            counts)


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def paged_decode_one(params, cache: SambaCache, tokens, positions, active,
                     table, config: Phi4FlashConfig, page_size: int,
                     use_kernel: bool):
    """One decode tick over every slot. tokens / positions / active: [B];
    table: [B, max_pages]. Returns (logits [B, V], cache, int32 [3]: the
    ``DECODE_COUNTERS`` of this tick). An inactive slot's K/V writes land in
    the trash page and the trash ring, it attends over nothing, and its scan
    state and convolution rows do not move (``dt = 0``)."""
    nb = tokens.shape[0]
    ring = ring_pages(config, page_size)
    scale = config.head_dim ** -0.5
    max_ctx = table.shape[1] * page_size
    x = params["embed_tokens"][tokens].astype(config.dtype)          # [B, h]
    safe_pos = jnp.minimum(positions, max_ctx - 1)
    page_idx = safe_pos // page_size
    pages = jnp.take_along_axis(table, page_idx[:, None], axis=1)[:, 0]
    rows = safe_pos % page_size
    lengths = _live_lengths(safe_pos, active)
    start, ring_table, win_lengths, win_starts, win_pages = ring_tick(
        lengths, active, page_idx, config.sliding_window, ring, page_size)
    ck, cv, ckw, cvw, cs, cc = cache
    rings_per_layer = ckw.shape[1] // config.count(WINDOW)
    m = None
    w_idx = m_idx = 0
    for l, (kind, lp) in enumerate(zip(config.layer_kinds, params["layers"])):
        mp = lp["mixer"]
        with jax.named_scope(kind):
            y = _ln(config, x, lp["norm1"])
            if kind == MAMBA:
                xin, z = jnp.split(y @ mp["w_in"], 2, axis=-1)
                xin, kept = ssm.causal_conv_step(
                    xin, cc[m_idx, :nb], mp["conv_w"], mp["conv_b"])
                kept = jnp.where(active[:, None, None], kept, cc[m_idx, :nb])
                cc = cc.at[m_idx, :nb].set(kept)
                xin = jax.nn.silu(xin)
                dt, b, c = _scan_inputs(config, mp, xin)
                dt = jnp.where(active[:, None], dt, 0.0)
                m, cs = ssm.mamba1_step(
                    xin, dt, -jnp.exp(mp["a_log"]), b, c, mp["d"], cs,
                    layer=m_idx, impl=config.scan_impl)
                m_idx += 1
                out, m = _gated(mp, m, z), _memory(m, z)
            elif kind == GMU:
                out = (m * jax.nn.silu(y @ mp["w1"])) @ mp["w2"]
            elif kind == WINDOW:
                q, kw, vw = _qkv(config, mp, y)
                base = w_idx * rings_per_layer
                ckw = _scatter_token_rows(ckw, kw, win_pages + base, rows)
                cvw = _scatter_token_rows(cvw, vw, win_pages + base, rows)
                o = _paged_attention(q[:, None], ckw, cvw, ring_table + base,
                                     win_lengths, scale, use_kernel,
                                     starts=win_starts)
                w_idx += 1
                out = _diff_out(config, mp, o[:, 0], l)
            else:
                if kind == FULL:
                    q, k, v = _qkv(config, mp, y)
                    ck = _scatter_token_rows(ck, k, pages, rows)
                    cv = _scatter_token_rows(cv, v, pages, rows)
                else:
                    q = _queries(config, mp, y)
                o = _paged_attention(q[:, None], ck, cv, table, lengths,
                                     scale, use_kernel)
                out = _diff_out(config, mp, o[:, 0], l)
            x = x + out
            x = x + _mlp(lp, _ln(config, x, lp["norm2"]))
    counts = jnp.stack([
        config.page_readers * jnp.sum(lengths),
        config.count(WINDOW) * jnp.sum(lengths - start),
        config.count(MAMBA) * jnp.sum(active)]).astype(jnp.int32)
    return (_head(config, params, x), SambaCache(ck, cv, ckw, cvw, cs, cc),
            counts)


def paged_decode_steps(params, cache: SambaCache, tokens, positions, active,
                       table, key, config: Phi4FlashConfig, num_steps: int,
                       page_size: int, use_kernel: bool,
                       temperature: float = 0.0):
    """``num_steps`` decode ticks on the device, as
    ``models/paged_decode.py`` ``paged_decode_steps``; the fifth result is
    ``DECODE_COUNTERS`` summed over ticks."""
    return counted_decode_steps(
        lambda cache, toks, pos: paged_decode_one(
            params, cache, toks, pos, active, table, config, page_size,
            use_kernel),
        cache, tokens, positions, active, key, num_steps, temperature,
        len(DECODE_COUNTERS))


def paged_kernel_fits(config: Phi4FlashConfig) -> bool:
    """The Pallas paged-attention kernel tiles a packed row, two heads wide,
    onto 128 lanes."""
    return (2 * config.head_dim) % 128 == 0


def make_paged_decode_fn(config: Phi4FlashConfig, num_steps: int,
                         page_size: int, temperature: float = 0.0, *,
                         use_kernel: bool):
    fn = functools.partial(paged_decode_steps, config=config,
                           num_steps=num_steps, page_size=page_size,
                           use_kernel=use_kernel, temperature=temperature)
    fn.__name__ = "phi4flash_decode"  # jit_phi4flash_decode in a profile
    return jax.jit(fn, donate_argnums=(1,))


def make_paged_prefill_fn(config: Phi4FlashConfig, page_size: int,
                          cross_over_all_rows: bool = False):
    fn = functools.partial(paged_prefill, config=config, page_size=page_size,
                           cross_over_all_rows=cross_over_all_rows)
    fn.__name__ = "phi4flash_prefill"  # jit_phi4flash_prefill in a profile
    return jax.jit(fn, donate_argnums=(1,))
