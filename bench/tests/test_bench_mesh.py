"""The harness on the data-mesh path (``chips: 4``), on four CPU devices
at `vgg9_snn.TINY_INT4` size, with its look for a chip skipped: the slot
batch is split over a ``('data',)`` mesh as ``launch/serve.py --data-shard``
serves it. A sound run is correct; a run whose answers are not gathered
across the chips (every chip's rows answered by the first chip's) is not.
Each case runs in a process of its own, which JAX starts with four
devices."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import dataclasses, io, json, sys, time
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax, jax.numpy as jnp
assert len(jax.devices()) == 4, jax.devices()
from bench import harness, spec
from repro.configs import vgg9_snn
from repro.serve.runners import snn

cell = spec.load_cell("cifar100_int4.sparse.offline")
model = dataclasses.asdict(vgg9_snn.TINY_INT4)
cell.config = dict(cell.config, model=dict(model, stages=list(model["stages"])))
cell.traffic = dict(cell.traffic, slots=8, max_queue=16, pool=16,
                    kinds={{"silent": 1, "patch": 1, "dense": 1}})
cell.chips = 4
if {fault!r}:
    sharded = snn.vgg9_infer_hybrid_sharded

    def not_gathered(params, images, cfg, **kw):
        logits, *rest = sharded(params, images, cfg, **kw)
        local = logits.shape[0] // 4
        return (jnp.tile(logits[:local], (4, 1)), *rest)

    snn.vgg9_infer_hybrid_sharded = not_gathered
out = io.StringIO()
harness.run_cell(cell, 2**31 + 77, 1.0, False, time.perf_counter(),
                 require_chip=False, out=out)
print(out.getvalue().splitlines()[-1])
"""


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "not_gathered"])
def test_the_data_mesh_path_on_four_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(ROOT), fault=fault)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (not fault), proc.stderr[-2000:]
    assert result["failed"] == 0
