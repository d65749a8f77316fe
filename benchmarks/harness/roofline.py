"""Peaks, and the operations and bytes an algorithm needs, from shapes.

One table of peaks keyed by ``device_kind``; a device that is not in it is
an error, never a default. The FLOPs a trained token needs depend on the
family's layer and are the family's (``families/<name>.py``)."""

from __future__ import annotations

from typing import Any, Dict

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30, "source": "Google Cloud TPU v5e"},
}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return PEAKS[device_kind]


def causal_attention_flops_fwd(seq: int, heads: int, head_dim: int) -> float:
    """QK^T and PV over the causal half: 2 matmuls x 2 flops x S^2/2 x D."""
    return 2 * 2 * heads * head_dim * seq * (seq + 1) / 2


def flash_fwd_flops(batch: int, seq: int, heads: int, head_dim: int) -> float:
    return batch * causal_attention_flops_fwd(seq, heads, head_dim)


def flash_bwd_flops(batch: int, seq: int, heads: int, head_dim: int) -> float:
    """dq, dk, dv and the recomputed scores: 2.5x the forward's matmuls
    is what a flash backward NEEDS (S = QK^T again, dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q: five matmuls against the forward's two)."""
    return 2.5 * flash_fwd_flops(batch, seq, heads, head_dim)


def paged_attention_bytes(live_tokens: float, batch: int, cfg: Dict[str, Any],
                          kv_itemsize: int = 2, q_itemsize: int = 2) -> float:
    """One decode call over one layer: K and V rows of the live tokens,
    plus q and the output."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return 2 * live_tokens * nkv * hd * kv_itemsize \
        + 2 * batch * nh * hd * q_itemsize
