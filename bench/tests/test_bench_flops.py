"""The operation counter and the table of peaks."""
import pytest

from bench import flops


def test_resnet20_flops_per_sample():
    from repro.core import apps

    expr, _ = apps.build_resnet20(img=12, cin=3, width=16, blocks=3, n_classes=10)
    stem = 2 * 12 * 12 * 3 * 3 * 3 * 16          # padded 3x3 conv, 12x12 out
    block_convs = 6 * (2 * 12 * 12 * 3 * 3 * 16 * 16)
    head = 2 * 16 * 10
    assert flops.flops_per_sample(expr) == stem + block_convs + head == 4_106_048


def test_lstm_wlm_flops_per_sample():
    from repro.core import apps

    expr, _ = apps.build_lstm_wlm(embed=128, hidden=64, T=35, vocab=128)
    gates = 2 * 35 * (4 * 64) * (128 + 64)       # input and recurrent matmuls
    head = 2 * 35 * 64 * 128
    assert flops.flops_per_sample(expr) == gates + head == 4_014_080


def test_mnist_rnn_flops_per_sample():
    from bench import cell as C

    cell = C.resolve("mnist_rnn.closed")
    app = C.load_module(cell.config_dir / cell.config["app"])
    gates = 2 * 28 * (4 * 64) * (28 + 64)        # input and recurrent matmuls
    fc1, fc2 = 2 * 64 * 32, 2 * 32 * 10
    assert flops.flops_per_sample(app.build(cell.config)) == gates + fc1 + fc2 == 1_323_648


def test_v5e_peak():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peak("TPU v99")
