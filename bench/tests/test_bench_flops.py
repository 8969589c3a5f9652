"""``bench/flops.py`` against the published parameter counts and the hand
counts of the round's work."""
import pytest

import tiny  # noqa: F401
import flops

WRN40 = dict(depth=40, widen=1, num_classes=10, image_size=32, channels=3,
             split_group=1)
WRN28 = dict(WRN40, depth=28, widen=10)
PAPER_FL = dict(clients_per_round=1, local_batch_size=50, local_epochs=1,
                clusters_per_class=10, classes_per_client=2,
                meta_batch_size=50, meta_epochs=2)


def test_parameter_counts():
    assert flops.param_count(WRN40) == 563_930
    assert flops.param_count(WRN28) == 36_479_194        # ~36.5 M


def test_split_features():
    assert flops.split_features(WRN40) == 16 * 32 * 32
    assert flops.split_features(WRN28) == 160 * 32 * 32


def test_forward_and_training_counts():
    # ~170 MFLOP forward per image for WRN-40-1, ~10.5 GFLOP for WRN-28-10
    assert flops.forward_flops(WRN40) == 166_561_024
    assert flops.forward_flops(WRN28) == 10_486_657_536
    r = flops.round_model_flops(WRN40, PAPER_FL, 2500)
    # one client's LocalUpdate: 3 x fwd x 2,500 images ~ 1.25 TFLOP
    assert r["local_update"] == 3 * 166_561_024 * 2500
    assert r["extract"] == flops.forward_flops(WRN40, (0, 1), False) * 2500
    # 20 knowledge rows, one batch of 20, two epochs
    assert r["meta_train"] == 3 * flops.forward_flops(WRN40, (2, 3)) * 40


def test_kernel_counts():
    f, b = flops.lloyd_step(2500, 200, 100)
    assert f == 4 * 2500 * 100 * 200
    assert b == 4 * (2500 * 200 + 2500 * 100 + 100 * 200 + 2 * 2500
                     + 100 * 200 + 100)
    f, b = flops.quantize(100, 16384)
    assert b == 2 * 4 * 100 * 16384 + 100 * 16384 + 400


def test_gram_hand_count():
    # the exact PCA's Gram at N = 2,500, D = 16,384: 2 N^2 D = 0.2 TFLOP
    n, d = 2500, flops.split_features(WRN40)
    assert 2 * n * n * d == pytest.approx(0.2048e12)
