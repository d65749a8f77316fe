"""Llama-class decoder, TPU-first.

Pure-functional JAX (param pytrees, no framework classes):

- layers are STACKED along a leading axis and iterated with ``lax.scan`` —
  one compiled layer body regardless of depth (fast compile, XLA-friendly);
- every weight/activation carries logical axis names mapped to mesh axes by
  ``parallel.sharding.ShardingRules`` (dp/fsdp/tp/sp/cp switchable without
  touching the model);
- attention uses ops.attention (Pallas flash on TPU);
- rematerialization via ``jax.checkpoint`` on the layer body
  (``remat="full" | "nothing_saveable" | None``);
- bfloat16 activations/weights, fp32 RMSNorm statistics and logits.

This is the flagship train/serve model named in BASELINE.json
("Llama-3-8B ... no GPU in the loop"); the reference has no native model
stack (it orchestrates torch), so this file cites capability, not code.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES, ShardingRules, shard_constraint


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: Optional[str] = "nothing_saveable"
    attention_impl: str = "auto"

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def num_params(self) -> int:
        h, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hd = self.head_dim_
        attn = h * (self.num_heads * hd) * 2 + h * (self.num_kv_heads * hd) * 2
        mlp = 3 * h * f
        per_layer = attn + mlp + 2 * h
        embed = v * h * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + embed + h

    # ---- preset family (sizes used by bench/tests) ----
    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                   num_layers=32, num_heads=32, num_kv_heads=8, **kw)

    @classmethod
    def llama_1b(cls, **kw) -> "LlamaConfig":
        return cls(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                   num_layers=22, num_heads=16, num_kv_heads=4, **kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        kw.setdefault("max_seq_len", 512)
        kw.setdefault("rope_theta", 10000.0)
        return cls(vocab_size=256, hidden_size=128, intermediate_size=256,
                   num_layers=2, num_heads=4, num_kv_heads=2, **kw)


def llama_logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    """Pytree of logical-axis tuples, parallel to the params pytree.
    Leading 'layers' axis on stacked per-layer weights."""
    axes = {
        "embed_tokens": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
    }
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def llama_init(config: LlamaConfig, key) -> Dict[str, Any]:
    h = config.hidden_size
    hd = config.head_dim_
    nh, nkv = config.num_heads, config.num_kv_heads
    f = config.intermediate_size
    L = config.num_layers
    dt = config.dtype

    keys = jax.random.split(key, 8)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)).astype(dt)

    params = {
        "embed_tokens": normal(keys[0], (config.vocab_size, h), h),
        "layers": {
            "attn_norm": jnp.ones((L, h), dt),
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "mlp_norm": jnp.ones((L, h), dt),
            "w_gate": normal(keys[5], (L, h, f), h),
            "w_up": normal(keys[6], (L, h, f), h),
            "w_down": normal(keys[7], (L, f, h), f),
        },
        "final_norm": jnp.ones((h,), dt),
    }
    if not config.tie_embeddings:
        params["lm_head"] = normal(jax.random.fold_in(key, 99), (h, config.vocab_size), h)
    return params


def _attention_dispatch(config: LlamaConfig, rules: ShardingRules, mesh, q, k, v):
    """Route attention by parallelism layout. With the sequence sharded over
    a >1-sized cp mesh axis, plain attention can't see the full sequence:
    use ring attention (ppermute K/V ring, O(S/cp) memory per device).
    Otherwise attention is independent across batch and heads, so under a
    mesh each device runs it on its own (batch, heads) block inside a
    shard_map: XLA cannot partition a Mosaic kernel on its own, and a bare
    flash call in a jit with NamedSharding inputs does not lower."""
    if mesh is None:
        return attention(q, k, v, causal=True, impl=config.attention_impl)
    seq_axis = rules.lookup("seq")
    if isinstance(seq_axis, str) and dict(mesh.shape).get(seq_axis, 1) > 1:
        from ray_tpu.parallel.ring_attention import ring_attention_sharded

        return ring_attention_sharded(
            q, k, v, mesh, causal=True, axis_name=seq_axis,
            q_spec=rules.spec(("batch", "seq", "act_heads", "head_dim")),
            kv_spec=rules.spec(("batch", "seq", "act_kv_heads", "head_dim")),
        )
    # seq stays whole inside each block (None), whatever the rules say
    q_spec = rules.spec(("batch", None, "act_heads", "head_dim"))
    kv_spec = rules.spec(("batch", None, "act_kv_heads", "head_dim"))
    local = functools.partial(attention, causal=True, impl=config.attention_impl)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
        check_vma=False,
    )(q, k, v)


def _layer(
    config: LlamaConfig,
    rules: ShardingRules,
    mesh,
    cos,
    sin,
    x,
    lp: Dict[str, Any],
):
    """One decoder layer. x: [B, S, H]; lp: per-layer params (no leading L)."""
    b, s, h = x.shape
    nh, nkv, hd = config.num_heads, config.num_kv_heads, config.head_dim_

    def cstr(t, axes):
        if mesh is None:
            return t
        return shard_constraint(t, mesh, rules, axes)

    # --- attention block ---
    y = rms_norm(x, lp["attn_norm"], config.rms_eps)
    q = (y @ lp["wq"]).reshape(b, s, nh, hd)
    k = (y @ lp["wk"]).reshape(b, s, nkv, hd)
    v = (y @ lp["wv"]).reshape(b, s, nkv, hd)
    q = cstr(q, ("batch", "seq", "act_heads", "head_dim"))
    k = cstr(k, ("batch", "seq", "act_kv_heads", "head_dim"))
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = _attention_dispatch(config, rules, mesh, q, k, v)
    o = o.reshape(b, s, nh * hd)
    x = x + cstr(o @ lp["wo"], ("batch", "seq", "act_embed"))

    # --- mlp block (SwiGLU) ---
    def mlp(x_in, norm_w, w_gate, w_up, w_down):
        y = rms_norm(x_in, norm_w, config.rms_eps)
        gate = jax.nn.silu(y @ w_gate)
        up = y @ w_up
        return (gate * up) @ w_down

    if config.remat == "mlp_only":
        # Recompute only the MLP in the backward pass: its [B,S,F]
        # intermediates are the bulk of layer activation memory (3F vs ~5H
        # per token) but cost only the gate/up matmuls to rebuild, while the
        # attention path (flash kernel, 2x the recompute FLOPs/byte) stays
        # saved. Middle ground between remat=None (OOM at 1B/seq2k on 16G)
        # and whole-layer remat (re-runs the flash kernel).
        mlp = jax.checkpoint(mlp, policy=jax.checkpoint_policies.nothing_saveable)
    down = mlp(x, lp["mlp_norm"], lp["w_gate"], lp["w_up"], lp["w_down"])
    x = x + cstr(down, ("batch", "seq", "act_embed"))
    return x


def llama_hidden(
    params: Dict[str, Any],
    tokens,
    config: LlamaConfig,
    mesh=None,
    rules: ShardingRules = DEFAULT_LLM_RULES,
):
    """tokens: [B, S] int32 -> final-norm hidden states [B, S, H]."""
    b, s = tokens.shape
    cos, sin = rope_frequencies(config.head_dim_, s, config.rope_theta)

    table = params["embed_tokens"]
    if mesh is not None:
        # One-hot matmul instead of gather: the table is sharded
        # (vocab->tp, embed->fsdp) and a gather from it forces SPMD full
        # rematerialization (replicate-then-repartition). The one-hot
        # contraction over vocab partitions cleanly (psum over tp) and rides
        # the MXU — the standard TPU embedding pattern.
        onehot = jax.nn.one_hot(tokens, config.vocab_size, dtype=config.dtype)
        x = onehot @ table.astype(config.dtype)
        x = shard_constraint(x, mesh, rules, ("batch", "seq", "act_embed"))
    else:
        x = table[tokens].astype(config.dtype)

    layer_fn = functools.partial(_layer, config, rules, mesh, cos, sin)
    if config.remat == "full":
        layer_fn = jax.checkpoint(layer_fn)
    elif config.remat == "nothing_saveable":
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    elif config.remat == "save_attn":
        # Save only the flash-attention output + logsumexp per layer (the
        # values whose recompute re-runs the Pallas kernel); everything else
        # — norms, q/k/v projections, rope, the whole MLP — rematerializes in
        # bwd. ~2.8 GB saved residuals/step on the 1B bench config vs ~9 GB
        # for mlp_only, while refwd skips the attention kernel.
        layer_fn = jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"
            ),
        )

    def scan_body(carry, lp):
        return layer_fn(carry, lp), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])

    return rms_norm(x, params["final_norm"], config.rms_eps)


def _lm_head(params: Dict[str, Any], config: LlamaConfig):
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T.astype(config.dtype)
    return head


def llama_forward(
    params: Dict[str, Any],
    tokens,
    config: LlamaConfig,
    mesh=None,
    rules: ShardingRules = DEFAULT_LLM_RULES,
):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (fp32)."""
    x = llama_hidden(params, tokens, config, mesh=mesh, rules=rules)
    logits = (x @ _lm_head(params, config)).astype(jnp.float32)
    if mesh is not None:
        logits = shard_constraint(logits, mesh, rules, ("batch", "seq", "act_vocab"))
    return logits


def llama_loss(
    params: Dict[str, Any],
    tokens,
    targets,
    config: LlamaConfig,
    mesh=None,
    rules: ShardingRules = DEFAULT_LLM_RULES,
    mask=None,
):
    """Train loss via the fused, seq-chunked LM-head + CE (ops/loss.py):
    full [B, S, V] logits are never materialized — the dominant transient
    at vocab 32k+ — at the cost of re-running the head matmul in bwd."""
    from ray_tpu.ops.loss import fused_cross_entropy

    x = llama_hidden(params, tokens, config, mesh=mesh, rules=rules)
    return fused_cross_entropy(x, _lm_head(params, config), targets, mask)


def cross_entropy_loss(logits, targets, mask=None):
    """logits: [B, S, V] fp32; targets: [B, S] int32.

    The gold-logit pick is a one-hot select-reduce, not take_along_axis: a
    gather over the tp-sharded vocab axis would force SPMD replication; the
    masked sum partitions cleanly (local select + psum)."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab = logits.shape[-1]
    onehot = jax.nn.one_hot(targets, vocab, dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


# what ``train/step.py`` asks a family's module for (``_family``)
init_params = llama_init
logical_axes = llama_logical_axes
loss = llama_loss
