"""Unified serving engine benchmark: admission, schedulers, budgets, SLOs,
and goodput under injected faults.

Eight experiments — six through one `EngineCore`, the last two through the
supervised multi-replica `Router`:

* LM — ragged greedy generation with *mixed decode budgets*: run-to-completion
  bucketed batching (``admission='batch'``, the PR-2 policy) vs step-level
  continuous admission (requests join freed KV-cache slots between decode
  steps). Reports requests/sec, tokens/sec and slot occupancy for both; the
  occupancy gap is the price of bucketing ragged budgets.
* SNN — batched spiking-VGG9 inference on a *mixed-sparsity trace*
  (interleaved near-silent and dense images, tagged by source): FIFO vs the
  sparsity-aware scheduler vs `slo:sparsity` (the SLO wrapper composed over
  it), all under continuous admission. Reports req/s, Eq. 3 energy/image —
  intrinsic (`energy_j`, invariant by construction) and as-served
  (`served_energy_j`, the request's share of the batch it rode in) — split
  by class, plus batch purity and the per-layer batch skip rates.
  Co-batching sparse with sparse is the paper's co-design loop closed in
  software: the sparse class's served energy drops toward its intrinsic
  cost instead of averaging with dense stragglers — and composing the SLO
  layer on top must not give that win back (asserted).
* LM chunked prefill — a long prompt joins a full decode batch; goodput
  (resident decode tokens per engine step) is swept over ``prefill_chunk``.
  Token-by-token (chunk 1, the old behavior) pins the joiner in its slot
  for prompt-length steps; chunking packs the same decode work into far
  fewer steps, outputs asserted bit-identical at every chunk size.
* LM latency SLOs — a mixed bulk/interactive trace on a deterministic
  step-counting engine clock: FIFO misses the interactive class's deadline
  (requests expire behind bulk residents), the `SLOScheduler` meets it by
  admitting tightest-deadline-first.
* Precision — adaptive per-request fp32/int4 selection (`serve.precision`)
  vs pinned single-precision fleets on the mixed-sparsity trace: served
  energy under both the Eq. 3 FPGA model and the analytical per-op model,
  accuracy proxies vs the fp32 reference, pinned requests asserted
  never-switched and all outputs asserted bit-identical per precision.
* Speculative — n-gram self-drafting verified on the `decode_chunk` seam
  vs plain one-token decode on the same greedy trace: outputs asserted
  bit-identical, accept rate > 0 (tiny-model token cycles are prompt-
  lookup's best case), and decode-tokens-per-step goodput strictly up.
  The sampled variant asserts seed determinism across engines and runs.
* Faults — chaos scenarios through a 3-replica router fleet: a wedged
  replica is condemned by the heartbeat and its in-flight request replays
  bit-identically on a healthy replica (recovery latency in router steps);
  a NaN-poisoned request retires ``'failed'`` with clean partials intact;
  a queue flood sheds overflow as ``'rejected'`` while high-priority work
  completes. Reports goodput under failure vs a fault-free fleet.
* Fleet — the same LM trace through an in-process 2-replica fleet and a
  2-worker *subprocess* fleet built from one wire-encodable `RunnerSpec`,
  reporting per-router-step IPC overhead; a chaos pass SIGKILLs a worker
  holding in-flight requests and asserts every request still completes
  bit-identical to the fault-free in-process run.

Both schedulers must return bit-identical outputs per request (asserted);
only composition, latency and energy attribution may differ.

Shapes are CPU/interpret friendly (``--smoke`` shrinks them further for CI);
as with the other interpret-mode benchmarks, absolute wall-clock is a
correctness harness, not a TPU perf signal — the portable signals are the
skip rates, energy attribution, batch purity and slot occupancy. Emits via
`common.emit` into ``BENCH_results.json``.
"""
import argparse
import json
import time

import jax
import numpy as np

from repro.configs import vgg9_snn
from repro.configs.base import ArchConfig
from repro.kernels.dense_conv_lif import ops as dense_ops
from repro.kernels.spike_conv import ops as sc_ops
from repro.models import transformer as tf
from repro.models.vgg9 import init_vgg9
from repro.serve.api import EngineConfig
from repro.serve.core import EngineCore, StepClock
from repro.serve.runners.lm import LMRunner
from repro.serve.runners.snn import SNNRunner

from .common import append_result, emit


def _drain(core, payloads, options=None):
    """Submit everything, drain the queue, return (results, seconds)."""
    options = options or [{}] * len(payloads)
    ids = [core.submit(p, **o) for p, o in zip(payloads, options)]
    t0 = time.perf_counter()
    results = core.run_until_complete()
    dt = time.perf_counter() - t0
    return [results[i] for i in ids], dt


# ---------------------------------------------------------------------------
# LM: batch vs continuous admission on mixed decode budgets
# ---------------------------------------------------------------------------

def _lm_cfg():
    return ArchConfig(name="bench-serve", family="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                      d_ff=64, vocab=61, dtype="float32", remat="none",
                      q_chunk=8, kv_chunk=8)


def bench_lm(smoke: bool) -> dict:
    cfg = _lm_cfg()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    slots, tokens = (2, 4) if smoke else (4, 8)
    runner = LMRunner(cfg, params, max_seq=64)

    rng = np.random.default_rng(0)
    n_req = slots + 1 if smoke else 2 * slots + 1      # forces partial batches
    prompts = [list(rng.integers(1, cfg.vocab, size=rng.integers(1, 6)))
               for _ in range(n_req)]
    # alternating decode budgets: two buckets for batch admission, co-resident
    # slot-mates under continuous admission
    options = [{"max_new_tokens": tokens if i % 2 == 0 else 2 * tokens}
               for i in range(n_req)]

    # warm the jit caches on a throwaway core so the measured cores'
    # occupancy/step stats cover only the timed drains
    for admission in ("batch", "continuous"):
        _drain(EngineCore(runner, EngineConfig(slots=slots, admission=admission)),
               prompts[:1], [options[0]])

    modes = {}
    outputs = {}
    for admission in ("batch", "continuous"):
        core = EngineCore(runner, EngineConfig(slots=slots, admission=admission))
        results, dt = _drain(core, prompts, options)
        stats = core.stats()
        total_tokens = sum(o["max_new_tokens"] for o in options)
        modes[admission] = {
            "req_per_s": round(n_req / dt, 2),
            "tok_per_s": round(total_tokens / dt, 1),
            "slot_occupancy": round(stats["slot_occupancy"], 3),
            "steps_run": stats["steps_run"],
        }
        outputs[admission] = [r.outputs for r in results]
        assert all(len(r.outputs) == r.stats["prompt_len"] + o["max_new_tokens"]
                   for r, o in zip(results, options))
    # continuous admission must not change a single token
    assert outputs["batch"] == outputs["continuous"]

    rec = {"name": "serve_engine_lm", "requests": n_req, "slots": slots,
           "admission": modes}
    emit("serve_engine_lm", 0.0,
         f"occ batch={modes['batch']['slot_occupancy']} "
         f"continuous={modes['continuous']['slot_occupancy']}",
         **{k: v for k, v in rec.items() if k != "name"})
    return rec


# ---------------------------------------------------------------------------
# SNN: FIFO vs sparsity-aware scheduling on a mixed-sparsity trace
# ---------------------------------------------------------------------------

def _mixed_trace(cfg, n_req: int):
    """Interleaved near-silent ('sparse') and dense requests, source-tagged."""
    keys = jax.random.split(jax.random.PRNGKey(1), n_req)
    payloads, options = [], []
    for i, k in enumerate(keys):
        img = jax.random.uniform(k, (cfg.img_hw, cfg.img_hw, cfg.in_ch))
        if i % 2 == 0:
            payloads.append(img * 0.05)        # rarely crosses the LIF threshold
            options.append({"source": "sparse"})
        else:
            payloads.append(img)
            options.append({"source": "dense"})
    return payloads, options


def _class_mean(results, options, source, field):
    vals = [r.stats[field] for r, o in zip(results, options)
            if o["source"] == source]
    return float(np.mean(vals)) if vals else 0.0


def bench_snn(smoke: bool) -> dict:
    import dataclasses
    cfg = vgg9_snn.TINY if smoke else dataclasses.replace(
        vgg9_snn.TINY, img_hw=32, stages=(16, 24, "MP", 32, 32, "MP"), fc_dim=64)
    params = init_vgg9(jax.random.PRNGKey(0), cfg)
    slots = 2 if smoke else 4
    runner = SNNRunner(cfg, params)
    n_req = 3 * slots
    payloads, options = _mixed_trace(cfg, n_req)

    jax.clear_caches()                                 # count trace-time launches
    sc_ops.reset_launch_counts()
    dense_ops.reset_launch_counts()
    # warm (and trace) the fused graph on a throwaway core; measured below
    _drain(EngineCore(runner, EngineConfig(slots=slots)), payloads[:1],
           options[:1])
    sparse_launches = sc_ops.launch_counts().get("spike_matmul_mapped", 0)
    dense_launches = dense_ops.launch_counts().get("dense_conv_lif", 0)

    scheds = {}
    outputs = {}
    for scheduler in ("fifo", "sparsity", "slo:sparsity"):
        core = EngineCore(runner, EngineConfig(slots=slots, scheduler=scheduler))
        results, dt = _drain(core, payloads, options)
        stats = core.stats()
        groups = [g for _, g in core.admission_log if len(g) > 1]
        klass = {r.request_id: o["source"]           # results in submit order
                 for r, o in zip(results, options)}
        purity = (sum(len({klass[r] for r in g}) == 1 for g in groups)
                  / len(groups) if groups else 1.0)
        skip = {}
        for layer in results[0].stats["skip_rate"]:
            skip[layer] = round(float(np.mean(
                [r.stats["skip_rate"][layer] for r in results])), 4)
        scheds[scheduler] = {
            "req_per_s": round(n_req / dt, 2),
            "slot_occupancy": round(stats["slot_occupancy"], 3),
            "steps_run": stats["steps_run"],
            "batch_purity": round(purity, 3),
            # intrinsic Eq. 3 energy: request served alone — invariant
            "energy_per_image_j": float(np.mean(
                [r.stats["energy_j"] for r in results])),
            # as-served: the request's share of the batch it actually rode in
            "served_energy_per_image_j": float(np.mean(
                [r.stats["served_energy_j"] for r in results])),
            "served_energy_sparse_j": _class_mean(results, options, "sparse",
                                                  "served_energy_j"),
            "served_energy_dense_j": _class_mean(results, options, "dense",
                                                 "served_energy_j"),
            "mean_skip_rate": skip,
        }
        outputs[scheduler] = [np.asarray(r.outputs) for r in results]

    # scheduling may change composition and energy attribution — never logits
    for name in ("sparsity", "slo:sparsity"):
        for a, b in zip(outputs["fifo"], outputs[name]):
            np.testing.assert_array_equal(a, b)
    # composing the SLO layer over the sparsity policy must keep the sparse
    # class's served-energy win (no deadlines in the trace -> the wrapper
    # delegates composition to its inner scheduler untouched)
    assert (scheds["slo:sparsity"]["served_energy_sparse_j"]
            <= scheds["fifo"]["served_energy_sparse_j"] * 0.67), scheds

    rec = {
        "name": "serve_engine_snn",
        "requests": n_req,
        "slots": slots,
        "dense_launches_per_batch": dense_launches,
        "sparse_launches_per_batch": sparse_launches,
        "schedulers": scheds,
    }
    f, s = scheds["fifo"], scheds["sparsity"]
    emit("serve_engine_snn", 0.0,
         f"sparse E/img fifo={f['served_energy_sparse_j']:.2e}J "
         f"sparsity={s['served_energy_sparse_j']:.2e}J "
         f"purity {f['batch_purity']}->{s['batch_purity']}",
         **{k: v for k, v in rec.items() if k != "name"})
    return rec


# ---------------------------------------------------------------------------
# LM: chunked prefill — goodput vs chunk size while a long prompt joins
# ---------------------------------------------------------------------------

def bench_chunked_prefill(smoke: bool) -> dict:
    """A long prompt joins a full decode batch; sweep ``prefill_chunk``.

    Goodput = resident decode tokens per engine step (`EngineCore.stats`).
    Token-by-token prefill (chunk 1) holds the joiner's slot for
    prompt-length steps; every larger chunk packs the same decode work into
    fewer steps. Outputs are asserted bit-identical across all chunk sizes
    and to a solo run of the long prompt.
    """
    cfg = _lm_cfg()
    rng = np.random.default_rng(7)
    if smoke:
        slots, prompt_len, chunks, max_seq = 2, 48, (1, 4, 16), 96
        resident_budget, joiner_budget = 24, 4
    else:
        slots, prompt_len, chunks, max_seq = 4, 512, (1, 8, 64), 544
        resident_budget, joiner_budget = 96, 8
    runner = LMRunner(cfg, params=tf.init_params(jax.random.PRNGKey(0), cfg),
                      max_seq=max_seq)
    long_prompt = [int(t) for t in rng.integers(1, cfg.vocab, size=prompt_len)]
    short_prompts = [[int(t) for t in rng.integers(1, cfg.vocab, size=3)]
                     for _ in range(slots)]

    solo_core = EngineCore(runner, EngineConfig(slots=slots))
    solo_id = solo_core.submit(long_prompt, max_new_tokens=joiner_budget)
    solo = solo_core.run_until_complete()[solo_id].outputs

    sweep = {}
    outputs = {}
    for chunk in chunks:
        core = EngineCore(runner, EngineConfig(slots=slots,
                                               prefill_chunk=chunk))
        resident_ids = [core.submit(p, max_new_tokens=resident_budget)
                        for p in short_prompts]
        core.step()                     # decode batch is full and live
        joiner = core.submit(long_prompt, max_new_tokens=joiner_budget)
        t0 = time.perf_counter()
        results = core.run_until_complete()
        dt = time.perf_counter() - t0
        stats = core.stats()
        sweep[chunk] = {
            "steps_run": stats["steps_run"],
            "decode_tokens": stats["decode_tokens"],
            "goodput_decode_tok_per_step":
                round(stats["goodput_decode_tok_per_step"], 4),
            "joiner_ttft_steps": results[joiner].stats["ttft_steps"],
            "joiner_prefill_chunks": results[joiner].stats["prefill_chunks"],
            "wall_s": round(dt, 3),
        }
        outputs[chunk] = [results[i].outputs
                          for i in resident_ids + [joiner]]
        assert results[joiner].outputs == solo, chunk

    base = outputs[chunks[0]]
    for chunk in chunks[1:]:
        assert outputs[chunk] == base, chunk           # bit-identical sweep
        # the acceptance bar: chunked prefill strictly beats token-by-token
        assert (sweep[chunk]["goodput_decode_tok_per_step"]
                > sweep[chunks[0]]["goodput_decode_tok_per_step"]), sweep

    rec = {"name": "serve_engine_lm_chunked_prefill", "slots": slots,
           "prompt_len": prompt_len, "sweep": {str(c): sweep[c] for c in chunks}}
    g1 = sweep[chunks[0]]["goodput_decode_tok_per_step"]
    gN = sweep[chunks[-1]]["goodput_decode_tok_per_step"]
    emit("serve_engine_lm_chunked_prefill", 0.0,
         f"goodput tok/step chunk{chunks[0]}={g1} chunk{chunks[-1]}={gN}",
         **{k: v for k, v in rec.items() if k != "name"})
    return rec


# ---------------------------------------------------------------------------
# LM: latency SLOs — FIFO misses a per-class deadline the SLO scheduler meets
# ---------------------------------------------------------------------------

def bench_slo(smoke: bool) -> dict:
    """Mixed bulk/interactive LM trace under a per-class deadline.

    Bulk requests (long decode budgets, no deadline) arrive first and fill
    the queue; interactive requests (short budgets, tight ``deadline_s`` in
    engine steps, higher priority) arrive behind them. FIFO admits in
    arrival order, so the interactive class expires behind bulk residents;
    the `SLOScheduler` admits tightest-deadline-first and meets the class
    deadline — without touching the bulk outputs.
    """
    cfg = _lm_cfg()
    rng = np.random.default_rng(11)
    slots = 2
    n_bulk, bulk_tokens = (3, 16) if smoke else (4, 24)
    n_inter, inter_tokens = 2, 4
    # prefill(4) + decode steps + one admission step of slack, per class
    deadline = 4 + inter_tokens + 4
    runner = LMRunner(cfg, params=tf.init_params(jax.random.PRNGKey(0), cfg),
                      max_seq=64)
    bulk = [[int(t) for t in rng.integers(1, cfg.vocab, size=4)]
            for _ in range(n_bulk)]
    inter = [[int(t) for t in rng.integers(1, cfg.vocab, size=4)]
             for _ in range(n_inter)]

    policies = {}
    for scheduler in ("fifo", "slo"):
        clock = StepClock()     # deadlines in engine steps: deterministic
        core = EngineCore(runner, EngineConfig(slots=slots,
                                               scheduler=scheduler),
                          clock=clock)
        clock.attach(core)
        bulk_ids = [core.submit(p, max_new_tokens=bulk_tokens) for p in bulk]
        inter_ids = [core.submit(p, max_new_tokens=inter_tokens,
                                 deadline_s=deadline, priority=1)
                     for p in inter]
        results = core.run_until_complete()
        met = sum(results[i].status == "ok" for i in inter_ids)
        policies[scheduler] = {
            "interactive_met": met,
            "interactive_total": n_inter,
            "interactive_expired": sum(results[i].status == "expired"
                                       for i in inter_ids),
            "bulk_done": sum(results[i].status == "ok" for i in bulk_ids),
            "steps_run": core.stats()["steps_run"],
            "deadline_steps": deadline,
        }
    # the acceptance bar: the SLO scheduler meets the class deadline FIFO
    # misses, and bulk traffic still completes
    assert policies["slo"]["interactive_met"] == n_inter, policies
    assert policies["fifo"]["interactive_met"] < n_inter, policies
    assert policies["slo"]["bulk_done"] == n_bulk, policies

    rec = {"name": "serve_engine_lm_slo", "slots": slots,
           "bulk": n_bulk, "interactive": n_inter, "policies": policies}
    emit("serve_engine_lm_slo", 0.0,
         f"interactive met fifo={policies['fifo']['interactive_met']}"
         f"/{n_inter} slo={policies['slo']['interactive_met']}/{n_inter}",
         **{k: v for k, v in rec.items() if k != "name"})
    return rec


# ---------------------------------------------------------------------------
# Precision: adaptive per-request fp32/int4 vs pinned fleets (serve.precision)
# ---------------------------------------------------------------------------

def bench_precision(smoke: bool) -> dict:
    """Adaptive-precision serving vs pinned fp32/int4 fleets on the mixed
    dense/near-silent SNN trace.

    Three fleets share one pre-warmed fp32+int4 `VariantRegistry` behind a
    `PrecisionRunner` (``EngineConfig.precision`` = 'fp32' / 'int4' /
    'adaptive'), each with a fresh `PrecisionController` bound to its
    sparsity scheduler. Every third request carries
    ``options['pin_precision']='fp32'`` (the accuracy-pinned class). The
    trace is served in two waves so the second wave's decisions use the
    skip-rate EWMAs the first wave taught the scheduler — the
    quantization->sparsity loop closing online.

    Acceptance (asserted): the adaptive fleet serves the trace at lower
    mean served energy than the pinned-fp32 fleet under BOTH cost models
    (paper Eq. 3 and the analytical per-op model — reported side by side
    per fleet); pinned requests are served fp32 in every fleet; and every
    request's logits are bit-identical to a plain single-precision
    `SNNRunner` engine at the precision it was actually served (row
    independence + single-precision launches). Accuracy proxy: top-1
    agreement and mean |logit delta| vs the fp32 reference.
    """
    import dataclasses
    from repro.serve.precision import (PrecisionController, PrecisionRunner,
                                       bind_controller, make_snn_pricer,
                                       make_snn_variants)
    from repro.serve.scheduler import make_scheduler

    cfg = vgg9_snn.TINY if smoke else dataclasses.replace(
        vgg9_snn.TINY, img_hw=32, stages=(16, 24, "MP", 32, 32, "MP"), fc_dim=64)
    params = init_vgg9(jax.random.PRNGKey(0), cfg)
    slots = 2 if smoke else 4
    n_req = 3 * slots
    payloads, options = _mixed_trace(cfg, n_req)
    for i, o in enumerate(options):
        if i % 3 == 0:
            o["pin_precision"] = "fp32"
    pinned_idx = [i for i, o in enumerate(options) if "pin_precision" in o]

    # one registry for everything: the variants quantize once and their jit
    # caches stay warm across fleets, so the comparison times serving only
    registry = make_snn_variants(cfg, params)
    registry.prewarm(slots)
    pricer = make_snn_pricer(cfg)

    # single-precision reference engines: plain SNNRunner variants, no
    # controller anywhere near them — the bit-identity baseline
    refs = {}
    for prec in registry.precisions:
        core = EngineCore(registry.runner(prec), EngineConfig(slots=slots))
        res, _ = _drain(core, payloads, options)
        refs[prec] = [np.asarray(r.outputs) for r in res]

    half = n_req // 2
    fleets = {}
    adaptive_summary = None
    for mode in ("fp32", "int4", "adaptive"):
        controller = PrecisionController(pricer=pricer, dense_threshold=0.8)
        runner = PrecisionRunner(registry, controller, mode=mode)
        scheduler = make_scheduler("sparsity")
        bind_controller(scheduler, controller)
        core = EngineCore(runner, EngineConfig(slots=slots,
                                               scheduler="sparsity",
                                               precision=mode),
                          scheduler=scheduler)
        res1, dt1 = _drain(core, payloads[:half], options[:half])
        res2, dt2 = _drain(core, payloads[half:], options[half:])
        results, dt = res1 + res2, dt1 + dt2

        served = [r.stats["precision"] for r in results]
        # pinned requests never switch, in any fleet or controller state
        assert all(served[i] == "fp32" for i in pinned_idx), (mode, served)
        # within a precision, logits are bit-identical to the pinned
        # single-precision engine that never saw a controller
        for i, r in enumerate(results):
            np.testing.assert_array_equal(np.asarray(r.outputs),
                                          refs[served[i]][i],
                                          err_msg=f"{mode} req {i}")
        counts = {p: served.count(p) for p in registry.precisions}
        fleets[mode] = {
            "req_per_s": round(n_req / dt, 2),
            "precision_counts": counts,
            # both cost models, per fleet, on the same served trace
            "served_energy_j": float(np.mean(
                [r.stats["served_energy_j"] for r in results])),
            "served_energy_analytical_j": float(np.mean(
                [r.stats["served_energy_analytical_j"] for r in results])),
            # accuracy proxy vs the fp32 reference logits
            "top1_agreement_vs_fp32": float(np.mean(
                [np.argmax(np.asarray(r.outputs)) == np.argmax(refs["fp32"][i])
                 for i, r in enumerate(results)])),
            "mean_abs_logit_delta": float(np.mean(
                [np.abs(np.asarray(r.outputs) - refs["fp32"][i]).mean()
                 for i, r in enumerate(results)])),
        }
        if mode == "adaptive":
            adaptive_summary = controller.summary()
            assert counts["int4"] > 0, "adaptive never harvested int4"

    # the acceptance bar: adaptive beats the pinned-fp32 fleet on served
    # energy under BOTH models while its pinned class stayed fp32-identical
    win_eq3 = (fleets["fp32"]["served_energy_j"]
               / fleets["adaptive"]["served_energy_j"])
    win_ana = (fleets["fp32"]["served_energy_analytical_j"]
               / fleets["adaptive"]["served_energy_analytical_j"])
    assert win_eq3 > 1.0 and win_ana > 1.0, fleets

    rec = {"name": "serve_engine_precision", "requests": n_req,
           "slots": slots, "pinned_fp32": len(pinned_idx),
           "fleets": fleets,
           "adaptive": {"energy_win_vs_fp32_eq3": round(win_eq3, 3),
                        "energy_win_vs_fp32_analytical": round(win_ana, 3),
                        "pinned_bit_identical": True,
                        "per_precision_bit_identical": True,
                        "controller": adaptive_summary}}
    emit("serve_engine_precision", 0.0,
         f"served E adaptive={fleets['adaptive']['served_energy_j']:.2e}J "
         f"fp32={fleets['fp32']['served_energy_j']:.2e}J "
         f"(win eq3 {win_eq3:.2f}x / analytical {win_ana:.2f}x)",
         **{k: v for k, v in rec.items() if k != "name"})
    return rec


# ---------------------------------------------------------------------------
# Speculative decode: accepted-tokens-per-step goodput vs plain decode
# ---------------------------------------------------------------------------

def bench_speculative(smoke: bool) -> dict:
    """Self-speculative decode (n-gram prompt lookup, verified on the
    `decode_chunk` seam) vs plain one-token decode on the same trace.

    Greedy decode on the tiny bench model falls into token cycles within a
    few steps — exactly the repetitive structure prompt-lookup drafting
    exploits — so the speculative engine accepts multi-token prefixes and
    packs the same decode work into fewer engine steps. The headline is
    goodput: decode tokens emitted per engine step, plain vs speculative,
    with outputs asserted bit-identical (speculation may never change a
    token, only how many one launch emits).

    A second scenario runs the same prompts sampled (temperature/top-p,
    per-request seeds) through fresh plain and speculative engines twice:
    sampled speculative output must equal sampled plain output (the
    per-(seed, index) sampling contract survives verify launches), and a
    re-run with the same seeds must be identical (seed determinism).
    """
    cfg = _lm_cfg()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    slots, tokens = (2, 24) if smoke else (4, 48)
    spec_k = 4
    n_req = slots + 1
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab, size=rng.integers(2, 6))]
               for _ in range(n_req)]
    options = [{"max_new_tokens": tokens} for _ in range(n_req)]

    plain_runner = LMRunner(cfg, params, max_seq=128)
    spec_runner = LMRunner(cfg, params, max_seq=128, speculate_k=spec_k)

    # warm both runners' launch-width buckets on throwaway cores
    for r in (plain_runner, spec_runner):
        _drain(EngineCore(r, EngineConfig(slots=slots)), prompts[:1],
               [options[0]])

    modes = {}
    outputs = {}
    for label, runner in (("plain", plain_runner), ("speculative", spec_runner)):
        core = EngineCore(runner, EngineConfig(slots=slots))
        results, dt = _drain(core, prompts, options)
        stats = core.stats()
        modes[label] = {
            "req_per_s": round(n_req / dt, 2),
            "steps_run": stats["steps_run"],
            "decode_tokens": stats["decode_tokens"],
            "goodput_decode_tok_per_step":
                round(stats["goodput_decode_tok_per_step"], 4),
            "drafted_tokens": stats["drafted_tokens"],
            "accepted_tokens": stats["accepted_tokens"],
            "goodput_accepted_tok_per_step":
                round(stats["goodput_accepted_tok_per_step"], 4),
        }
        outputs[label] = [r.outputs for r in results]
        # per-request ledger closes exactly
        assert all(r.stats["accepted_tokens"] + r.stats["rejected_tokens"]
                   == r.stats["drafted_tokens"] for r in results)

    # the correctness bar: speculation never changes a token
    bit_identical = outputs["plain"] == outputs["speculative"]
    assert bit_identical, "speculative greedy diverged from plain greedy"
    accept_rate = (modes["speculative"]["accepted_tokens"]
                   / modes["speculative"]["drafted_tokens"])
    assert accept_rate > 0, modes
    # the goodput bar: accepted drafts pack decode into fewer steps
    goodput_win = (modes["speculative"]["goodput_decode_tok_per_step"]
                   / modes["plain"]["goodput_decode_tok_per_step"])
    assert goodput_win > 1.0, modes

    # sampled scenario: determinism across engines and across runs
    sampled_opts = [{"max_new_tokens": tokens, "temperature": 0.8,
                     "top_p": 0.95, "seed": 100 + i} for i in range(n_req)]
    sampled = {}
    for label, runner in (("plain", plain_runner), ("speculative", spec_runner)):
        runs = []
        for _ in range(2):
            core = EngineCore(runner, EngineConfig(slots=slots))
            results, _ = _drain(core, prompts, sampled_opts)
            runs.append([r.outputs for r in results])
        assert runs[0] == runs[1], f"sampled {label} not seed-deterministic"
        sampled[label] = runs[0]
    seed_deterministic = True
    assert sampled["plain"] == sampled["speculative"], (
        "sampled speculative diverged from sampled plain")

    rec = {"name": "serve_engine_speculative", "requests": n_req,
           "slots": slots, "speculate_k": spec_k,
           "plain": modes["plain"], "speculative": modes["speculative"],
           "accept_rate": round(accept_rate, 4),
           "goodput_win": round(goodput_win, 4),
           "bit_identical": bit_identical,
           "sampling": {"seed_deterministic": seed_deterministic,
                        "matches_plain": True}}
    emit("serve_engine_speculative", 0.0,
         f"accept_rate={accept_rate:.2f} goodput tok/step "
         f"plain={modes['plain']['goodput_decode_tok_per_step']} "
         f"spec={modes['speculative']['goodput_decode_tok_per_step']} "
         f"({goodput_win:.2f}x)",
         **{k: v for k, v in rec.items() if k != "name"})
    return rec


# ---------------------------------------------------------------------------
# Faults: goodput + recovery latency under injected failures (serve.router)
# ---------------------------------------------------------------------------

def bench_faults(smoke: bool) -> dict:
    """Chaos scenarios through the supervised 3-replica router.

    Scenario 1 (wedge + NaN, the ISSUE-6 acceptance shape): replica 0
    wedges mid-stream, replica 1 NaN-poisons a slot. Every in-flight
    request reaches a terminal result; the wedged replica's request is
    re-routed by deterministic replay and asserted *bit-identical* to a
    fault-free single-replica run; the poisoned request retires
    ``'failed'`` with its clean partial tokens intact. Reported metrics:
    recovery latency (router steps from the drain to the replayed
    request's completion) and goodput under failure (ok results per
    router step, vs the fault-free fleet).

    Scenario 2 (overload shedding): a single small-queue replica is
    flooded with low-priority work behind a high-priority batch; the high
    class completes, overflow is shed with ``status='rejected'``, and
    every submission still gets exactly one terminal result.
    """
    from repro.serve.core import all_finite
    from repro.serve.faults import flood_queue, parse_fleet_plan
    from repro.serve.router import make_router

    cfg = _lm_cfg()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    tokens = 6 if smoke else 10
    runner = LMRunner(cfg, params, max_seq=64)
    rng = np.random.default_rng(3)
    prompts = [list(int(t) for t in rng.integers(1, cfg.vocab, size=n))
               for n in (4, 3, 2)]

    # fault-free references: single replica for bit-identity, and a clean
    # 3-replica fleet for the goodput-under-failure comparison
    ref_core = EngineCore(runner, EngineConfig(slots=2), clock=StepClock())
    ref_ids = [ref_core.submit(p, max_new_tokens=tokens) for p in prompts]
    ref = ref_core.run_until_complete()
    clean = make_router(runner, 3, EngineConfig(slots=2))
    for i, p in enumerate(prompts):
        clean.submit(p, max_new_tokens=tokens, affinity=f"s{i}")
    clean.run_until_complete()
    clean_goodput = (clean.stats()["ok"] / clean.stats()["router_steps"])

    plans = parse_fleet_plan("0=wedge@4,1=nan@4:slot=0")
    router = make_router(runner, 3, EngineConfig(slots=2), plans=plans,
                         wedge_patience=3, obs=True)
    rids = [router.submit(p, max_new_tokens=tokens, affinity=f"s{i}")
            for i, p in enumerate(prompts)]
    a, b, c = rids
    streams = {rid: [] for rid in rids}
    for _ in range(400):
        router.step()
        for rid in rids:
            streams[rid].extend(router.poll_partial(rid))
        if not router._outstanding:
            break
    results = {rid: router.poll(rid) for rid in rids}
    stats = router.stats()

    # every in-flight request completed; re-route is bit-identical
    assert all(res is not None for res in results.values())
    assert results[a].status == "ok" and results[c].status == "ok"
    bit_identical = (results[a].outputs == ref[ref_ids[0]].outputs
                     and results[c].outputs == ref[ref_ids[2]].outputs)
    assert bit_identical, "replayed outputs diverged from fault-free run"
    # poisoned request: failed, clean partial prefix intact
    assert results[b].status == "failed"
    ref_b = ref[ref_ids[1]].outputs[len(prompts[1]):]
    partials_intact = (len(streams[b]) > 0 and all_finite(streams[b])
                      and streams[b] == ref_b[:len(streams[b])])
    assert partials_intact, "poisoned request lost its clean partials"

    wedge_drain = next(e for e in router.drain_log if e[1] == 0)
    recovery_steps = max((router.completed_at[rid] for rid in wedge_drain[3]),
                         default=wedge_drain[0]) - wedge_drain[0]
    wedge_reroute = {
        "reroutes": stats["rerouted"],
        "recovery_steps": recovery_steps,
        "bit_identical": bit_identical,
        "router_steps": stats["router_steps"],
        "goodput_ok_per_step": round(stats["ok"] / stats["router_steps"], 4),
        "goodput_fault_free_per_step": round(clean_goodput, 4),
        "replica_states": [r["state"] for r in stats["replicas"]],
    }
    nan_poison = {
        "failed": stats["failed"],
        "partials_intact": partials_intact,
        "clean_partial_tokens": len(streams[b]),
    }

    # the wedged replica's drain carries a flight-recorder postmortem: its
    # final StepReport frames (summaries), plus the heartbeat evidence the
    # router condemned it on
    detail = wedge_drain[4]
    dump = detail.get("dump")
    assert dump and dump.get("frames"), (
        "wedged replica drained without a flight-recorder dump")
    assert dump["frames"][-1]["step"] is not None
    flight_recorder = {
        "reason": dump["reason"],
        "frames": len(dump["frames"]),
        "notes": len(dump.get("notes", [])),
        "last_frame_step": dump["frames"][-1]["step"],
        "marker": list(detail["marker"]),
        "cost_finite": detail["cost_finite"],
    }

    # scenario 2: queue flood against one small replica
    shed_router = make_router(runner, 1,
                              EngineConfig(slots=2, max_queue=2),
                              max_waiting=2)
    high = [shed_router.submit(p, max_new_tokens=2, priority=5)
            for p in prompts]
    low = flood_queue(shed_router, prompts[0], count=8, max_new_tokens=2)
    shed_results = shed_router.run_until_complete()
    assert all(shed_results[r].status == "ok" for r in high)
    n_rejected = sum(shed_results[r].status == "rejected" for r in low)
    assert n_rejected > 0, "flood never triggered shedding"
    assert len(shed_results) == len(high) + len(low)    # exactly-once results
    overload = {
        "submitted": len(high) + len(low),
        "ok": sum(r.status == "ok" for r in shed_results.values()),
        "rejected": n_rejected,
        "high_priority_ok": len(high),
    }

    rec = {"name": "serve_engine_faults", "replicas": 3,
           "wedge_reroute": wedge_reroute, "nan_poison": nan_poison,
           "overload": overload, "flight_recorder": flight_recorder}
    emit("serve_engine_faults", 0.0,
         f"recovery={recovery_steps} steps, goodput "
         f"{wedge_reroute['goodput_ok_per_step']} vs clean "
         f"{wedge_reroute['goodput_fault_free_per_step']} ok/step, "
         f"rejected={n_rejected}, "
         f"recorder_frames={flight_recorder['frames']}",
         **{k: v for k, v in rec.items() if k != "name"})
    return rec


# ---------------------------------------------------------------------------
# Fleet: in-process replicas vs subprocess workers — IPC overhead + chaos
# ---------------------------------------------------------------------------

def bench_fleet(smoke: bool) -> dict:
    """In-process 2-replica fleet vs 2-worker *subprocess* fleet on the
    same LM trace, plus an observability-attached pass (tracing + metrics
    + flight recorder over the wire; measures the obs tax and asserts one
    merged cross-process trace) and a chaos pass with one worker killed
    mid-run.

    All three serving modes are built from one wire-encodable `RunnerSpec`
    (same seed -> same params in every process), so the comparison is pure
    transport: the subprocess fleet pays wire codec + pipe round trips per
    router step, reported as per-step wall time against the in-process
    fleet (``ipc_overhead_x``). The chaos pass kills a worker holding
    in-flight requests with SIGKILL; supervision condemns the dead replica
    and replays its work on the survivor. Acceptance (asserted): every
    request in every mode completes ``'ok'`` with outputs *bit-identical*
    to the fault-free in-process run.
    """
    from repro.serve.router import make_router, make_worker_fleet
    from repro.serve.worker import build_runner, lm_spec

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "bench_fleet runs an in-process fleet in this process and worker "
            "subprocesses beside it; a TPU belongs to one process, so run "
            "this pass on the CPU (JAX_PLATFORMS=cpu)")
    cfg = _lm_cfg()
    tokens = 4 if smoke else 8
    n_req = 4 if smoke else 6
    spec = lm_spec(cfg, seed=0, max_seq=64)
    config = EngineConfig(slots=2, max_queue=16)
    rng = np.random.default_rng(9)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab,
                                             size=rng.integers(2, 6))]
               for _ in range(n_req)]
    warm_prompt = [1, 2, 3]

    def serve(router, *, timed_after_warmup=True):
        if timed_after_warmup:      # compile jit caches outside the timing
            router.submit(warm_prompt, max_new_tokens=tokens)
            router.run_until_complete()
        rids = [router.submit(p, max_new_tokens=tokens) for p in prompts]
        t0 = time.perf_counter()
        results = router.run_until_complete()
        dt = time.perf_counter() - t0
        return [results[rid] for rid in rids], dt, router.stats()

    inproc = make_router(build_runner(spec), 2, config)
    res_in, dt_in, stats_in = serve(inproc)
    expected = [r.outputs for r in res_in]
    assert all(r.status == "ok" for r in res_in)

    t0 = time.perf_counter()
    fleet = make_worker_fleet(spec, 2, config)
    spawn_s = time.perf_counter() - t0
    try:
        res_sub, dt_sub, stats_sub = serve(fleet)
    finally:
        fleet.close()
    assert [r.outputs for r in res_sub] == expected, (
        "subprocess fleet outputs diverged from in-process fleet")

    # observability tax: the same 2-worker subprocess fleet with tracing,
    # metrics and flight recorders attached on both ends of the wire.
    # Contract (asserted): outputs stay bit-identical; the router merges
    # every worker's spans into one cross-process trace. Measured: per-step
    # wall overhead vs the detached subprocess fleet.
    fleet_obs = make_worker_fleet(spec, 2, config, obs=True)
    try:
        res_obs, dt_obs, stats_obs = serve(fleet_obs)
        tel = fleet_obs.telemetry()
    finally:
        fleet_obs.close()
    obs_identical = [r.outputs for r in res_obs] == expected
    assert obs_identical, "attached observability perturbed fleet outputs"
    span_replicas = sorted({str(s.get("replica")) for s in tel["trace"]})
    assert tel["trace"] and len(span_replicas) >= 2, (
        "router did not merge worker spans into one cross-process trace")
    step_ms_obs = 1e3 * dt_obs / max(1, stats_obs["router_steps"])

    # chaos pass: SIGKILL a worker that is holding in-flight requests
    chaos = make_worker_fleet(spec, 2, config)
    try:
        rids = [chaos.submit(p, max_new_tokens=tokens) for p in prompts]
        for _ in range(2):
            chaos.step()
        victim = chaos.replicas[0].transport
        assert victim.in_flight() > 0, "victim held no work before the kill"
        victim.kill()
        results = chaos.run_until_complete()
        res_chaos = [results[rid] for rid in rids]
        stats_chaos = chaos.stats()
    finally:
        chaos.close()
    assert len(chaos.drain_log) == 1, chaos.drain_log
    all_ok = all(r.status == "ok" for r in res_chaos)
    bit_identical = [r.outputs for r in res_chaos] == expected
    assert all_ok and bit_identical, (
        "killed-worker replay diverged from the fault-free in-process run")

    step_ms_in = 1e3 * dt_in / max(1, stats_in["router_steps"])
    step_ms_sub = 1e3 * dt_sub / max(1, stats_sub["router_steps"])
    rec = {
        "name": "serve_engine_fleet",
        "requests": n_req, "workers": 2, "tokens": tokens,
        "inproc": {"wall_s": round(dt_in, 3),
                   "router_steps": stats_in["router_steps"],
                   "step_ms": round(step_ms_in, 3),
                   "req_per_s": round(n_req / dt_in, 2)},
        "subprocess": {"wall_s": round(dt_sub, 3),
                       "router_steps": stats_sub["router_steps"],
                       "step_ms": round(step_ms_sub, 3),
                       "req_per_s": round(n_req / dt_sub, 2),
                       "spawn_s": round(spawn_s, 3)},
        "ipc_overhead_x": round(step_ms_sub / step_ms_in, 3),
        "bit_identical": bit_identical,
        "obs": {"wall_s": round(dt_obs, 3),
                "step_ms": round(step_ms_obs, 3),
                "overhead_x": round(step_ms_obs / step_ms_sub, 3),
                "merged_trace_spans": len(tel["trace"]),
                "trace_replicas": span_replicas,
                "engine_steps": tel["metrics"].get(
                    "engine_steps", {}).get("value", 0),
                "bit_identical": obs_identical},
        "chaos": {"drains": len(chaos.drain_log),
                  "rerouted": stats_chaos["rerouted"],
                  "router_steps": stats_chaos["router_steps"],
                  "all_ok": all_ok,
                  "bit_identical": bit_identical},
    }
    emit("serve_engine_fleet", 0.0,
         f"step {step_ms_in:.1f}ms inproc vs {step_ms_sub:.1f}ms subprocess "
         f"({rec['ipc_overhead_x']}x), kill->replay rerouted="
         f"{stats_chaos['rerouted']} bit_identical={bit_identical}",
         **{k: v for k, v in rec.items() if k != "name"})
    emit("serve_engine_obs", 0.0,
         f"obs tax {rec['obs']['overhead_x']}x/step over detached, "
         f"{rec['obs']['merged_trace_spans']} merged spans from "
         f"{len(span_replicas)} sources, bit_identical={obs_identical}",
         workers=2, obs=rec["obs"])
    return rec


def run(smoke: bool = False) -> dict:
    lm = bench_lm(smoke)
    snn = bench_snn(smoke)
    chunked = bench_chunked_prefill(smoke)
    slo = bench_slo(smoke)
    precision = bench_precision(smoke)
    speculative = bench_speculative(smoke)
    faults = bench_faults(smoke)
    fleet = bench_fleet(smoke)
    record = {"name": "serve_engine", "lm": lm, "snn": snn,
              "chunked_prefill": chunked, "slo": slo,
              "precision": precision, "speculative": speculative,
              "faults": faults, "fleet": fleet}
    print("SERVE_ENGINE_JSON " + json.dumps(record, sort_keys=True))
    append_result(record)
    return record


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (2 slots, fewer requests)")
    run(**vars(ap.parse_args()))
