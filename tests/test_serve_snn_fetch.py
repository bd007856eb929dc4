"""`SNNRunner`'s stats fetch: one batched device-to-host read per step.

The runner brings the logits and every stat it reads back to the host in
one `jax.device_get`. These tests hold it to the per-leaf reads it replaced
(`np.asarray` / `float` / `int` on each device array): every `Result` field
is bit-identical, and the ``snn.fetch`` stage counts one wait, the arrays
and their bytes per step.
"""
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg9_snn
from repro.models.vgg9 import init_vgg9, vgg9_infer_hybrid
from repro.serve.api import EngineConfig, Request
from repro.serve.core import EngineCore
from repro.serve.runners.snn import (SNNRunner, _per_request_skip,
                                     _per_timestep_occupancy)

#: the stat leaves a step reads back, per layer that has them
READ_LEAVES = ("skip_rate", "out_spikes_per_image", "in_spikes_per_image",
               "row_occ", "block_m", "rows")


class PerLeafRunner(SNNRunner):
    """The reference: the runner as it read its stats before the batched
    fetch, one blocking read per device array."""

    def _run_unsharded(self, images, n: int):
        plan = self.plan(n)
        logits, _, stats = jax.block_until_ready(vgg9_infer_hybrid(
            self.params, images, self.cfg, plan=plan, return_stats=True))
        logits = np.asarray(logits)
        batch_skip = {k: float(v["skip_rate"]) for k, v in stats.items()
                      if "skip_rate" in v}
        out_spikes = {k: np.asarray(v["out_spikes_per_image"], np.float64)
                      for k, v in stats.items()}
        in_spikes = {k: np.asarray(v["in_spikes_per_image"], np.float64)
                     for k, v in stats.items() if "in_spikes_per_image" in v}
        occ = {name: (np.asarray(st["row_occ"]), int(st["block_m"]),
                      int(st["rows"]))
               for name, st in stats.items() if "occ_map" in st}
        per_req_skip: Dict[str, np.ndarray] = {}
        ts_occ: Dict[str, np.ndarray] = {}
        t = self.cfg.timesteps
        for name, (row_occ, block_m, rows) in occ.items():
            rps = plan.layer(name).kernel.m // (t * n)
            per_req_skip[name] = _per_request_skip(
                row_occ, block_m, rows, rows_per_slice=rps, batch=n)
            ts_occ[name] = _per_timestep_occupancy(
                row_occ, rows, rows_per_slice=rps, batch=n)
        return (logits, batch_skip, out_spikes, in_spikes, per_req_skip,
                ts_occ)


def _mixed_batch(runner, key) -> list:
    """Silent, patch and dense images, then two of the runner's zero-image
    fillers."""
    hw, ch = runner.cfg.img_hw, runner.cfg.in_ch
    k_patch, k_dense, k_dense2 = jax.random.split(key, 3)
    silent = jnp.zeros((hw, hw, ch))
    patch = jnp.zeros((hw, hw, ch)).at[4:9, 5:11].set(
        jax.random.uniform(k_patch, (5, 6, ch)))
    dense = jax.random.uniform(k_dense, (hw, hw, ch))
    dense2 = jax.random.uniform(k_dense2, (hw, hw, ch))
    batch = [Request(i, im) for i, im in
             enumerate((silent, patch, dense, dense2))]
    return batch + [runner.filler(batch[0]), runner.filler(batch[0])]


@pytest.mark.parametrize("cfg", [vgg9_snn.TINY, vgg9_snn.TINY_INT4],
                         ids=["fp32", "int4"])
def test_batched_fetch_stats_bit_identical_to_per_leaf_reads(cfg):
    params = init_vgg9(jax.random.PRNGKey(0), cfg)
    runner = SNNRunner(cfg, params)
    batch = _mixed_batch(runner, jax.random.PRNGKey(3))
    got = runner.run(batch)
    want = PerLeafRunner(cfg, params).run(batch)
    assert len(got) == len(want) == len(batch)
    for a, b in zip(got, want):
        assert a.request_id == b.request_id
        assert np.asarray(a.outputs).dtype == np.asarray(b.outputs).dtype
        np.testing.assert_array_equal(np.asarray(a.outputs),
                                      np.asarray(b.outputs))
        assert a.stats == b.stats
        # same fields in the same order, same float bits
        assert json.dumps(a.stats) == json.dumps(b.stats)
    # the batch really mixes sparsity: the silent image skips every tile
    # its own rows hold, the dense one does not
    assert all(v == 1.0 for v in got[0].stats["skip_rate"].values())
    assert got[2].stats["spike_total"] > got[1].stats["spike_total"] > 0


def test_fetch_counts_one_wait_and_every_read_array_per_step():
    cfg = vgg9_snn.TINY
    slots = 2
    params = init_vgg9(jax.random.PRNGKey(0), cfg)
    runner = SNNRunner(cfg, params)
    core = EngineCore(runner, EngineConfig(slots=slots))
    for k in jax.random.split(jax.random.PRNGKey(1), 5):
        core.submit(jax.random.uniform(k, (cfg.img_hw, cfg.img_hw, cfg.in_ch)))
    results = core.run_until_complete()
    assert all(r.status == "ok" for r in results.values())
    n = core.stats()["steps_run"]
    assert n == 3

    # the arrays one step reads: the logits and the read stat leaves
    images = jax.ShapeDtypeStruct((slots, cfg.img_hw, cfg.img_hw, cfg.in_ch),
                                  jnp.float32)
    logits, _, stats = jax.eval_shape(
        lambda im: vgg9_infer_hybrid(params, im, cfg, plan=runner.plan(slots),
                                     return_stats=True), images)
    read = [logits] + [st[f] for st in stats.values() for f in READ_LEAVES
                       if f in st]
    per_step_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                         for x in read)
    # TINY (conv0-conv3, fc0, fc1; conv1-conv3 mapped): the logits, 6 out-
    # and 5 in-spike vectors, and 3 skip rates + 3 x (row_occ, block_m, rows)
    assert len(read) == 1 + 6 + 5 + 3 + 3 * 3

    fetch = core.stats()["host_stages"]["snn.fetch"]
    assert fetch["waits"] == fetch["calls"] == n
    assert fetch["arrays"] == n * len(read)
    assert fetch["bytes"] == n * per_step_bytes
    # the counts ride the JSON-able stats export
    assert json.loads(json.dumps(fetch)) == fetch
