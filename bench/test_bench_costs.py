"""Operations and bytes from shapes, and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import costs

ROOT = Path(__file__).resolve().parent
Q05 = json.loads((ROOT / "configs" / "qwen2.5-0.5b.json").read_text())["config"]
Q15 = json.loads((ROOT / "configs" / "qwen2.5-1.5b.json").read_text())["config"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        costs.peaks("TPU v9 imaginary")


def test_v5e_peaks_are_the_published_ones():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_qwen05b_weight_bytes_by_hand():
    # per layer: 2 norms 2*896, wq 896*896, bq 896, wk and wv 896*128 each,
    # bk and bv 128 each, wo 896*896, gate/up/down 3*896*4864
    layer = (2 * 896 + 896 * 896 + 896 + 2 * (896 * 128 + 128) + 896 * 896
             + 3 * 896 * 4864)
    assert layer == 14_912_384
    params = 24 * layer + 151_936 * 896 + 896     # + tied embed, final norm
    assert params == 494_032_768
    assert costs.weight_bytes(Q05) == 2 * params == 988_065_536


def test_qwen05b_decode_bytes_by_hand():
    # one row at 256 positions: every weight once, then K and V of 256
    # positions over 24 layers, 2 KV heads of 64, bf16
    flops, nbytes = costs.decode_step(Q05, [256])
    kv = 256 * 24 * 2 * 2 * 64 * 2
    assert kv == 3_145_728
    assert nbytes == 988_065_536 + kv
    # two rows: the weights are still read once
    _, two = costs.decode_step(Q05, [256, 100])
    assert two == 988_065_536 + (256 + 100) * 24 * 2 * 2 * 64 * 2


def test_qwen05b_decode_flops_by_hand():
    matmul = 896 * (896 + 2 * 128) + 896 * 896 + 3 * 896 * 4864
    per_row = 2 * 24 * matmul + 2 * 896 * 151_936
    attn = 24 * 4 * 14 * 64 * 256
    flops, _ = costs.decode_step(Q05, [256])
    assert flops == per_row + attn


def test_prefill_chunk_attends_causally_over_the_prefix():
    f0, b0 = costs.prefill_chunk(Q15, pos0=0, valid=256)
    f1, b1 = costs.prefill_chunk(Q15, pos0=512, valid=256)
    per_key = 28 * 4 * 12 * 128
    assert f1 - f0 == per_key * 256 * 512       # every query sees 512 more
    assert b1 - b0 == 512 * costs.kv_bytes_per_token(Q15)


def test_least_time_names_its_bound():
    p = costs.peaks("TPU v5 lite")
    t, bound = costs.least_time(*costs.decode_step(Q05, [256]), p)
    assert bound == "memory"
    assert t == pytest.approx((988_065_536 + 3_145_728) / 819e9)
    _, bound = costs.least_time(*costs.prefill_chunk(Q15, 0, 4096), p)
    assert bound == "compute"
