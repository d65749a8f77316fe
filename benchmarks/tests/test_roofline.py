"""FLOP and byte functions against hand counts at the Mistral-7B widths:
the kernels' in ``harness/roofline.py``, a trained token's in the family."""
import json
import os

import pytest

from benchmarks.families import llama
from benchmarks.harness import roofline
from benchmarks.harness.manifest import BENCH_DIR


def cfg(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_layer_parameters_by_hand():
    c = cfg("mistral-7b-v0.3-train")
    # wq, wo: 4096 x 4096 each; wk, wv: 4096 x 1024 each; three 4096 x 14336
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert by_hand == 218_103_808
    assert llama.layer_matmul_params(c) == by_hand


def test_train_flops_per_token_by_hand():
    c = cfg("mistral-7b-v0.3-train")
    dense = 4 * 218_103_808 + 4096 * 32768          # no embedding gather
    attn_fwd_per_seq = 2 * 2 * 32 * 128 * 4096 * 4097 / 2
    by_hand = 6 * dense + 4 * 3 * attn_fwd_per_seq / 4096
    assert llama.train_flops_per_token(c, 4096) == pytest.approx(by_hand, rel=1e-12)
    # the strict count is below 6N with the embedding table in N
    n_all = 4 * (218_103_808 + 2 * 4096) + 2 * 4096 * 32768 + 4096
    assert 6 * dense < 6 * n_all


def test_flash_flops_by_hand():
    fwd = roofline.flash_fwd_flops(4, 4096, 32, 128)
    assert fwd == pytest.approx(4 * 4 * 32 * 128 * 4096 * 4097 / 2)
    assert roofline.flash_bwd_flops(4, 4096, 32, 128) == pytest.approx(2.5 * fwd)


def test_paged_attention_bytes_by_hand():
    c = cfg("mistral-7b-v0.3-serve")
    # 10,000 live tokens: K and V rows of 8 heads x 128 x 2 bytes each;
    # q and out for 64 slots x 32 heads x 128 x 2 bytes
    by_hand = 2 * 10_000 * 8 * 128 * 2 + 2 * 64 * 32 * 128 * 2
    assert roofline.paged_attention_bytes(10_000, 64, c) == by_hand


def test_unknown_device_is_an_error():
    assert roofline.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
