"""Peaks and the FLOP/byte arithmetic, against hand-worked values."""

import pytest

import _paths  # noqa: F401
from benchmarks import peaks


def test_gpt2_medium_flops_per_token_hand_worked():
    # N = 12 * 24 * 1024^2 + 50304 * 1024 = 301,989,888 + 51,511,296
    assert peaks.gpt2_matmul_params(24, 1024, 50304) == 353_501_184
    # 6N = 2,121,007,104; attention 6 * 24 * 1024 * 1024 = 150,994,944
    assert peaks.gpt2_train_flops_per_token(24, 1024, 50304, 1024) == \
        2_272_002_048.0


def test_repo_flops_function_overstates_attention():
    """`models/gpt2.py flops_per_token` counts attention at 4x the causal
    requirement: why the benchmark keeps its own."""
    from ray_tpu.models.gpt2 import GPT2Config, flops_per_token

    theirs = flops_per_token(GPT2Config.medium(), 1024)
    ours = peaks.gpt2_train_flops_per_token(24, 1024, 50304, 1024)
    assert theirs - ours == pytest.approx(3 * 6 * 24 * 1024 * 1024)


@pytest.mark.parametrize("kernel,matmuls,operands,rows", [
    ("flash_fwd", 2, 4, 1), ("flash_bwd_dq", 3, 5, 2),
    ("flash_bwd_dkv", 4, 6, 2)])
def test_flash_required_at_24_16_1024_64(kernel, matmuls, operands, rows):
    need = peaks.flash_required(24, 16, 1024, 64)[kernel]
    one_matmul = 2 * 24 * 16 * 1024 * 1024 * 64 / 2     # 25,769,803,776
    assert one_matmul == 25_769_803_776
    assert need["flops"] == matmuls * one_matmul
    operand = 24 * 16 * 1024 * 64 * 2                   # 50,331,648 B
    assert need["bytes"] == operands * operand + rows * 24 * 16 * 1024 * 4


def test_roofline_floor_says_which_peak_bounds():
    v5e = peaks.peaks_for("TPU v5 lite")
    fwd = peaks.flash_required(24, 16, 1024, 64)["flash_fwd"]
    floor = peaks.roofline_floor_s(fwd["flops"], fwd["bytes"], v5e)
    assert floor["bound"] == "compute"
    assert floor["floor_s"] == pytest.approx(2 * 25_769_803_776 / 197e12)
    assert peaks.roofline_floor_s(1.0, 819e9, v5e) == {
        "floor_s": pytest.approx(1.0), "bound": "memory"}


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks.peaks_for("cpu")
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
