"""Operation and byte counters of bench/counts.py against hand-computed
values for both configurations."""
import os

import pytest

from bench import cells, counts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def qwen3():
    return cells.load_config(ROOT, "qwen3-1.7b")


@pytest.fixture(scope="module")
def qwen15():
    return cells.load_config(ROOT, "qwen1.5-4b")


def test_layer_params_by_hand(qwen3, qwen15):
    # q 2048x2048, k and v 2048x1024, o 2048x2048, MLP 3 x 2048x6144
    assert counts.layer_matrix_params(qwen3) == (
        2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144)
    # q, k, v, o all 2560x2560 (MHA), MLP 3 x 2560x6912
    assert counts.layer_matrix_params(qwen15) == (
        4 * 2560 * 2560 + 3 * 2560 * 6912)


def test_weight_bytes_by_hand(qwen3, qwen15):
    # qwen3: 28 x (50,331,648 + two norms 4,096 + qk-norms 256)
    # + tied head 2048 x 151936 + final norm 2048, two bytes each
    assert counts.weight_bytes(qwen3) == 2 * (
        28 * (50_331_648 + 4_096 + 256) + 311_164_928 + 2048)
    # qwen1.5: 40 x (79,298,560 + norms 5,120 + qkv bias 7,680)
    # + untied head 2560 x 151936 + final norm 2560
    assert counts.weight_bytes(qwen15) == 2 * (
        40 * (79_298_560 + 5_120 + 7_680) + 388_956_160 + 2560)


def test_kv_bytes_by_hand(qwen3, qwen15):
    assert counts.kv_bytes_per_token(qwen3) == 28 * 2 * 8 * 128 * 2
    assert counts.kv_bytes_per_token(qwen3) == 112 * 1024
    assert counts.kv_bytes_per_token(qwen15) == 40 * 2 * 20 * 128 * 2
    assert counts.kv_bytes_per_token(qwen15) == 400 * 1024


def test_token_and_step_flops_by_hand(qwen3):
    mats = 28 * 50_331_648 + 311_164_928
    # position 255 attends to 256 keys: 4 * 16 heads * 128 * 256 per layer
    assert counts.token_flops(qwen3, 255) == 2 * mats + 28 * 4 * 16 * 128 \
        * 256
    assert counts.decode_step_flops(qwen3, 32, 300) == \
        32 * counts.token_flops(qwen3, 300)
    # decode bytes: weights once plus 32 lanes x 301 filled positions
    assert counts.decode_step_bytes(qwen3, 32, 300) == \
        counts.weight_bytes(qwen3) + 32 * 301 * 114_688


def test_prefill_flops_by_hand(qwen15):
    L, P = 40, 1024
    per_seq = (2 * L * 79_298_560 * P + L * 4 * 20 * 128 * P * (P + 1) // 2
               + 2 * 388_956_160)
    assert counts.prefill_flops(qwen15, P, 4) == 4 * per_seq


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert cells.least_time_s(1000.0, 10.0, peaks) == 10.0
    assert cells.least_time_s(100.0, 100.0, peaks) == 10.0
    assert cells.least_time_s(100.0, 50.0, peaks) == 5.0


def test_peak_table_knows_v5e_and_refuses_others():
    row = cells.load_peaks(ROOT, "TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        cells.load_peaks(ROOT, "TPU v9 imaginary")
