"""Unified serving API: EngineCore scheduling + LM/SNN runner equivalence.

The engine must serve both workloads through the same submit()/poll()
surface: fixed-slot padding and per-request results under either admission
policy — run-to-completion FIFO bucketed batching (``admission='batch'``,
pinned explicitly where the test asserts its semantics) or the default
step-level continuous admission. SNN serving must be bit-identical to a
direct `vgg9_infer_hybrid` call with the fused pipeline's occupancy/skip
counters split back out per request, and the dense-core conv0 launch must
take its block configuration from the plan. Continuous-admission-specific
behaviour (mid-stream joins, the sparsity-aware scheduler) is covered in
test_serve_continuous.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg9_snn
from repro.configs.base import ArchConfig
from repro.core.hybrid import plan_vgg9_inference
from repro.kernels.dense_conv_lif import ops as dense_ops
from repro.models import transformer as tf
from repro.models.vgg9 import init_vgg9, vgg9_infer_hybrid
from repro.serve.api import EngineConfig, QueueFull, Request
from repro.serve.core import EngineCore
from repro.serve.runners.lm import LMRunner
from repro.serve.runners.snn import SNNRunner

LM_CFG = ArchConfig(name="t-core", family="dense", n_layers=2, d_model=32,
                    n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=61,
                    dtype="float32", remat="none", q_chunk=8, kv_chunk=8)
SNN_CFG = vgg9_snn.TINY


@pytest.fixture(scope="module")
def lm_setup():
    params = tf.init_params(jax.random.PRNGKey(0), LM_CFG)
    return LMRunner(LM_CFG, params, max_seq=32)


@pytest.fixture(scope="module")
def snn_setup():
    params = init_vgg9(jax.random.PRNGKey(0), SNN_CFG)
    imgs = jax.random.uniform(jax.random.PRNGKey(1),
                              (4, SNN_CFG.img_hw, SNN_CFG.img_hw, 3))
    return params, imgs


# ---------------------------------------------------------------------------
# EngineCore scheduling (workload-agnostic, exercised through the LM runner)
# ---------------------------------------------------------------------------

def test_submit_poll_lifecycle(lm_setup):
    core = EngineCore(lm_setup, EngineConfig(slots=2, admission="batch"))
    rid = core.submit([1, 2, 3], max_new_tokens=3)
    assert core.poll(rid) is None and core.pending() == 1
    assert core.step() == 1
    res = core.poll(rid)
    assert res is not None and res.request_id == rid
    assert len(res.outputs) == 3 + 3
    assert res.stats["prompt_len"] == 3
    assert core.poll(rid) is None                     # results retire on poll


def test_fifo_bucketed_batching(lm_setup):
    """Batch admission: same-bucket requests batch together up to the slot
    count; a different bucket (different decode budget) waits for its own
    run-to-completion batch."""
    core = EngineCore(lm_setup, EngineConfig(slots=2, admission="batch"))
    a = core.submit([1, 2], max_new_tokens=2)
    b = core.submit([3], max_new_tokens=4)            # different bucket
    c = core.submit([4, 5], max_new_tokens=2)         # batches with `a`
    assert core.step() == 2                           # a + c (FIFO, same key)
    assert core.poll(a) is not None and core.poll(c) is not None
    assert core.poll(b) is None
    assert core.step() == 1
    assert core.poll(b) is not None
    stats = core.stats()
    assert stats["batches_run"] == 2 and stats["requests_done"] == 3


def test_queue_admission_bound(lm_setup):
    core = EngineCore(lm_setup, EngineConfig(slots=2, max_queue=2))
    core.submit([1], max_new_tokens=1)
    core.submit([2], max_new_tokens=1)
    with pytest.raises(QueueFull):
        core.submit([3], max_new_tokens=1)


def test_run_until_complete_drains(lm_setup):
    core = EngineCore(lm_setup, EngineConfig(slots=2))
    ids = [core.submit([i + 1], max_new_tokens=2) for i in range(5)]
    results = core.run_until_complete()
    assert set(results) == set(ids) and core.pending() == 0
    occ = core.stats()["slot_occupancy"]
    assert 0 < occ <= 1.0                             # 5 requests over 2-wide slots


# ---------------------------------------------------------------------------
# SNN serving equivalence (fp32 and int4): engine == direct fused call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [SNN_CFG, vgg9_snn.TINY_INT4], ids=["fp32", "int4"])
def test_snn_engine_matches_direct_call(snn_setup, cfg):
    params, imgs = snn_setup
    runner = SNNRunner(cfg, params)
    core = EngineCore(runner, EngineConfig(slots=4))
    ids = [core.submit(imgs[i]) for i in range(4)]
    results = core.run_until_complete()

    direct_logits, direct_counts, direct_stats = vgg9_infer_hybrid(
        params, imgs, cfg, plan=runner.plan(4), return_stats=True)
    direct_logits = np.asarray(direct_logits)

    for i, rid in enumerate(ids):
        res = results[rid]
        # logits bit-identical to the direct fused call on the same batch
        np.testing.assert_array_equal(np.asarray(res.outputs), direct_logits[i])
        # batch-level skip rates identical to the kernel-reported stats
        for name, skip in res.stats["batch_skip_rate"].items():
            assert skip == float(direct_stats[name]["skip_rate"]), name
        # per-request stats attached for every layer
        assert set(res.stats["skip_rate"]) == {
            n for n, s in direct_stats.items() if "skip_rate" in s}
        assert res.stats["energy_j"] > 0 and res.stats["latency_s"] > 0

    # per-request spike splits recombine exactly (0/1 spikes -> exact sums)
    for name in direct_counts:
        total = sum(results[r].stats["out_spikes"][name] for r in ids)
        assert total == float(direct_counts[name]), name


def test_snn_partial_batch_pads_with_zero_images(snn_setup):
    """3 requests into 4 slots: the engine zero-pads the batch; all layers
    are row-independent, so real rows match the direct padded-batch call."""
    params, imgs = snn_setup
    runner = SNNRunner(SNN_CFG, params)
    core = EngineCore(runner, EngineConfig(slots=4))
    ids = [core.submit(imgs[i]) for i in range(3)]
    results = core.run_until_complete()
    assert set(results) == set(ids)

    padded = jnp.concatenate([imgs[:3], jnp.zeros_like(imgs[:1])])
    direct_logits, _ = vgg9_infer_hybrid(params, padded, SNN_CFG,
                                         plan=runner.plan(4))
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(np.asarray(results[rid].outputs),
                                      np.asarray(direct_logits)[i])
    assert core.stats()["slot_occupancy"] == 0.75


def test_snn_per_request_skip_rates_see_sparsity(snn_setup):
    """An all-zero image must report a strictly higher per-request skip rate
    than a dense random image in the same batch (the per-request sparsity
    signal the co-design stack schedules on)."""
    params, _ = snn_setup
    hw = SNN_CFG.img_hw
    runner = SNNRunner(SNN_CFG, params)
    core = EngineCore(runner, EngineConfig(slots=2))
    zero = core.submit(jnp.zeros((hw, hw, 3)))
    dense = core.submit(jax.random.uniform(jax.random.PRNGKey(7), (hw, hw, 3)))
    results = core.run_until_complete()
    z = results[zero].stats
    d = results[dense].stats
    assert z["spike_total"] == 0.0
    assert d["spike_total"] > 0.0
    for name, zskip in z["skip_rate"].items():
        assert zskip == 1.0, name                     # nothing to do for layer
        assert zskip >= d["skip_rate"][name]
    assert z["energy_j"] < d["energy_j"]              # Eq. 3: work scales with spikes


# ---------------------------------------------------------------------------
# Dense-core conv0: plan-driven blocks + launch counter
# ---------------------------------------------------------------------------

def test_conv0_blocks_come_from_plan_and_launch_counted(snn_setup):
    params, imgs = snn_setup
    plan = plan_vgg9_inference(SNN_CFG, batch=4)
    ks0 = plan.layer("conv0").kernel
    # shrink the plan's conv0 N tile; the kernel launch must follow it
    small = dataclasses.replace(plan, layers=tuple(
        dataclasses.replace(l, kernel=dataclasses.replace(l.kernel, block_n=64))
        if l.name == "conv0" else l for l in plan.layers))

    jax.clear_caches()
    dense_ops.reset_launch_counts()
    a, _ = vgg9_infer_hybrid(params, imgs, SNN_CFG, plan=small)
    assert dense_ops.launch_counts() == {"dense_conv_lif": 1}
    assert dense_ops.LAUNCH_LOG == [{"block_m": min(ks0.block_m, 4 * 16 * 16),
                                     "block_n": 64}]

    jax.clear_caches()
    dense_ops.reset_launch_counts()
    b, _ = vgg9_infer_hybrid(params, imgs, SNN_CFG, plan=plan)
    assert dense_ops.LAUNCH_LOG[0]["block_n"] == min(ks0.block_n, 128)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # blocks don't change numerics


def test_lm_filler_requests_are_invisible(lm_setup):
    """A partial LM batch is padded with zero-length filler prompts whose
    results never surface."""
    core = EngineCore(lm_setup, EngineConfig(slots=4))
    rid = core.submit([5, 6], max_new_tokens=3)
    results = core.run_until_complete()
    assert set(results) == {rid}
    filler = lm_setup.filler(Request(rid, [5, 6], {"max_new_tokens": 3}))
    assert filler.is_pad and filler.payload == []
