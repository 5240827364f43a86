"""Operation and byte counts, checked by hand at a small shape."""
import pytest

from bench import flops

MODEL = {"in_dim": 4, "hidden": [3], "rep_dim": 2, "num_classes": 5}


def test_matmul_params_by_hand():
    # 4x3 + 3x2 + 2x5 weights take part in products
    assert flops.mlp_matmul_params(MODEL) == 12 + 6 + 10


@pytest.mark.parametrize("samples,fwd", [(1, 56), (7, 392)])
def test_forward_and_train_flops(samples, fwd):
    assert flops.forward_flops(MODEL, samples) == fwd      # 2 x 28 x samples
    assert flops.train_flops(MODEL, samples) == 3 * fwd


def test_serve_flops_runs_every_model_over_the_bucket():
    assert flops.serve_flops(MODEL, n_models=5, bucket=4) == 5 * 2 * 28 * 4


@pytest.mark.parametrize("rows,n,expect", [
    # 3 rows pad to 8; N=200 pads to one 256-lane block
    (3, 200, 4 * (8 * 256 + 2 * 256 + 8 * 256)),
    # the configuration's cohort: 300 rows -> 304, N=6570 -> 4 x 2048
    (300, 6570, 4 * (304 * 8192 + 2 * 8192 + 304 * 256)),
])
def test_fingerprint_bytes(rows, n, expect):
    assert flops.fingerprint_bytes(rows, n) == expect


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("not a chip")
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
