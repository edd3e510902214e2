"""benchmark/flops.py against numbers worked by hand."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmark"))
import flops  # noqa: E402


def test_gpt2_small_flops_per_token():
    # per layer: 8*768^2 = 4718592; causal scores+values 4*768*1025/2 =
    # 1574400; MLP 4*768*3072 = 9437184 -> 15730176; x12 = 188762112;
    # head 2*768*50304 = 77266944; forward 266029056; training x3.
    got = flops.gpt2_train_flops_per_token(768, 12, 3072, 50304, 1024)
    assert got == pytest.approx(3 * 266029056)


def test_resnet50_flops_per_image():
    # torchvision's table: 4.09 GMACs forward at 224x224 (v1.5) = 8.18 GFLOP
    got = flops.resnet50_train_flops_per_image(224, 1000) / 3.0
    assert got == pytest.approx(2 * 4.09e9, rel=0.01)


def test_causal_kernel_counts_only_the_pairs_under_the_diagonal():
    c = flops.causal_flash_attention_cost(16, 12, 1024, 64)
    pairs = 1024 * 1025 // 2
    assert c["flops"] == 3 * 4 * 16 * 12 * 64 * pairs  # 77 384 908 800
    assert c["flops"] < 3 * 4 * 16 * 12 * 64 * 1024 * 1024  # never S^2
    assert c["bytes"] == 8 * 16 * 1024 * 12 * 64 * 2


def test_roofline_says_which_bound_and_unknown_chip_raises():
    c = flops.causal_flash_attention_cost(16, 12, 1024, 64)
    r = flops.roofline_seconds(c["flops"], c["bytes"], "TPU v5 lite")
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(77384908800 / 197e12)
    assert flops.roofline_seconds(1.0, 1e6, "TPU v5e")["bound"] == "memory"
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
