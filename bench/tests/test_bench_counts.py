"""Operation and byte counts, against hand-worked numbers."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import counts, spec  # noqa: E402
from bench.references.vgg9 import Net  # noqa: E402


def net(config):
    cfg = spec.load_benchmark()["configs"]
    entry = next(c for c in cfg if c["name"] == config)
    import json
    return Net.from_model(json.loads((ROOT / entry["file"]).read_text())["model"])


@pytest.mark.parametrize("config,gflop", [("vgg9_cifar10_fp32", 2.337),
                                          ("vgg9_cifar100_int4", 2.354)])
def test_dense_work_per_image(config, gflop):
    # conv0 32*32*27*64*2 once; conv1..conv6 and fc0, fc1 twice (T = 2):
    # 3.54 + 264.2 + 198.2 + 382.2 + 238.9 + 557.4 + 650.3 + 38.1 + 4.26
    # (CIFAR10) MFLOP; CIFAR100's fc1 has 5000 outputs, +17.0 MFLOP
    assert counts.dense_flops_per_image(net(config)) / 1e9 == pytest.approx(
        gflop, abs=5e-4)


def test_conv_shapes_follow_the_stages():
    shapes = counts.conv_shapes(net("vgg9_cifar10_fp32"))
    assert shapes == [("conv0", 32, 3, 64), ("conv1", 32, 64, 112),
                      ("conv2", 16, 112, 192), ("conv3", 16, 192, 216),
                      ("conv4", 8, 216, 480), ("conv5", 8, 480, 504),
                      ("conv6", 8, 504, 560)]


def test_spike_matmul_count_on_a_silent_batch_is_its_outputs_and_weights():
    n = net("vgg9_cifar10_fp32")
    layers = [s[0] for s in counts.conv_shapes(n)[1:]]
    ops, nbytes = counts.spike_matmul_work(n, 64, dict.fromkeys(layers, 0.0))
    assert ops == 0
    outputs = sum(2 * 64 * hw * hw * cout for _, hw, _, cout in counts.conv_shapes(n)[1:])
    weights = sum(9 * cin * cout for _, _, cin, cout in counts.conv_shapes(n)[1:])
    assert nbytes == 4 * (outputs + weights)
    # the least time the chip could take for that work reads 100%, so a
    # measured kernel time (never below it) reads at most 100%
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    least = nbytes / peak["bytes_per_s"]
    assert counts.roofline_share(ops, nbytes, least, peak) == pytest.approx(100.0)
    assert counts.roofline_share(ops, nbytes, 3 * least, peak) < 100.0


def test_spike_matmul_count_never_exceeds_the_dense_count():
    n = net("vgg9_cifar10_fp32")
    t, b = n.timesteps, 64
    full = {name: float(t * b * hw * hw * cin)
            for name, hw, cin, _ in counts.conv_shapes(n)[1:]}
    ops, _ = counts.spike_matmul_work(n, b, full)
    dense = sum(2.0 * t * b * hw * hw * 9 * cin * cout
                for _, hw, cin, cout in counts.conv_shapes(n)[1:])
    assert ops == pytest.approx(dense)


def test_dense_core_and_lif_counts_from_shapes():
    n = net("vgg9_cifar10_fp32")
    ops, nbytes = counts.dense_conv_lif_work(n, 1)
    m = 32 * 32
    assert ops == 2 * m * 27 * 64 + 6 * 2 * m * 64
    assert nbytes == 4 * (m * 27 + 27 * 64 + 64 + 2 * m * 64 + m * 64)
    ops, nbytes = counts.lif_epilogue_work(n, 1)
    neurons = (1024 * 112 + 256 * (192 + 216) + 64 * (480 + 504 + 560)
               + 1064 + 1000)
    assert ops == 6 * 2 * neurons
    assert nbytes == pytest.approx(4 * 2 * (2 * neurons + 112 + 192 + 216 + 480
                                            + 504 + 560 + 1064 + 1000))
