"""CPU rehearsal of `chip_smoke.py`: its phases at `vgg9_snn.TINY` size with
the kernels in interpret mode, and its refusal to run without a TPU.

The phases that need the chip's compiler (counting Mosaic kernels in the
compiled graph) are rehearsed in tests/test_tpu_compile.py instead."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import vgg9_snn

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_main_refuses_cpu(smoke):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "needs a TPU" in str(exc.value) and "'cpu'" in str(exc.value)


def test_script_exits_nonzero_without_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)            # the script finds src/ itself
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_kernel_checks_pass_at_tiny_width(smoke, capsys):
    models = smoke.build(0, (vgg9_snn.TINY, vgg9_snn.TINY_INT4))
    cfg, params = models["fp32"]
    images, _ = smoke.make_images(cfg, 4, 1)
    smoke.check_kernels(cfg, params, images)
    out = capsys.readouterr().out
    for name in ("dense_conv_lif", "spike_conv2d_mapped", "lif_epilogue"):
        assert f"kernel {name}" in out


@pytest.mark.parametrize("cfg", [vgg9_snn.TINY, vgg9_snn.TINY_INT4],
                         ids=["fp32", "int4"])
def test_served_results_match_forward_at_tiny_size(smoke, cfg, capsys):
    params = smoke.build(0, (cfg,))[("int4" if cfg.quant_bits else "fp32")][1]
    images, kinds = smoke.make_images(cfg, 6, 2)      # 4 slots, 2 batches
    results, first_s, warm_s = smoke.serve(cfg, params, images, 4)
    assert len(results) == 6 and first_s > 0 and warm_s > 0
    smoke.report_serving("tiny", results, kinds)
    skip = {k: results[kinds.index(k)].stats["skip_rate"]["conv1"]
            for k in smoke.KINDS}
    assert skip["silent"] == 1.0 and skip["dense"] < skip["patch"] < 1.0
    metrics = smoke.compare(cfg, params, images, results)
    smoke.check_compare(metrics, "tiny")
    # interpret mode runs the same float32 arithmetic as the reference
    assert metrics["logits_equal"] == 1.0
    assert all(v == 0 for v in metrics["count_rel"].values())


def test_compare_refuses_a_silent_network(smoke):
    metrics = {"layers": ["conv0", "fc1"],
               "served_counts": {"conv0": 10.0, "fc1": 0.0},
               "count_rel": {"conv0": 0.0, "fc1": 0.0},
               "logit_max_abs": 0.0, "logits_equal": 1.0, "top1_agree": 1.0}
    with pytest.raises(AssertionError, match="never spiked"):
        smoke.check_compare(metrics, "silent")


HLO = "\n".join([
    '  %a = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", '
    'metadata={op_name="jit(f)/jit(g)/dense_conv_lif/pallas_call"}',
    '  %b = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", '
    'metadata={op_name="jit(f)/spike_matmul_mapped/pallas_call"}',
    '  %c = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", '
    'metadata={op_name="jit(f)/while/body/lif_epilogue/pallas_call"}',
    '  %d = f32[8] add(%a, %b)',
])


def test_kernel_launches_counts_custom_calls_by_name(smoke):
    counts = smoke.kernel_launches(HLO)
    assert counts == {"tpu_custom_call": 3, "dense_conv_lif": 1,
                      "spike_matmul_mapped": 1, "lif_epilogue": 1}


def test_check_launches_wants_one_sparse_kernel_per_layer(smoke):
    counts = smoke.kernel_launches(HLO)
    with pytest.raises(AssertionError, match="spike_matmul_mapped"):
        smoke.check_launches(counts, vgg9_snn.TINY)     # 3 sparse layers
    counts.update(spike_matmul_mapped=3, lif_epilogue=5, tpu_custom_call=9)
    smoke.check_launches(counts, vgg9_snn.TINY)


def test_last_line_shape_is_json(smoke, monkeypatch, capsys):
    """main() on a (faked) TPU device prints the contract's last line."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 1}

    monkeypatch.setattr(smoke.jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(smoke, "run_one_chip", lambda seed: None)
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "cache")
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
