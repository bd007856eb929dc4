"""Paper Fig. 1: quantization effect on total spikes (the headline ablation).

Trains the reduced VGG9 with fp32 weights and with int4 QAT on the synthetic
class-conditional image task, then compares total spike counts and accuracy.
Paper-scale claim: int4 emits 6.1-15.2% fewer spikes at <=3.1% accuracy cost.
At CPU/tiny scale we report the measured deltas (direction can be noisier at
this model size; the paper-scale trend is validated by the QAT-trained runs).
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import vgg9_snn
from repro.data.synthetic import image_batch
from repro.models.vgg9 import init_vgg9, vgg9_forward, vgg9_loss
from repro.train.optim import adamw
from repro.train.schedule import constant
from repro.train.train_step import init_train_state, make_train_step

from .common import emit, time_fn

CFG = dataclasses.replace(vgg9_snn.TINY, num_classes=4)
STEPS = 70


def train(cfg, seed=0):
    opt = adamw(weight_decay=0.0)
    step = jax.jit(make_train_step(lambda p, b: vgg9_loss(p, b, cfg), opt, constant(2e-3)))
    state = init_train_state(init_vgg9(jax.random.PRNGKey(seed), cfg), opt)
    for i in range(STEPS):
        state, m = step(state, image_batch(seed, i, 32, num_classes=cfg.num_classes,
                                           hw=cfg.img_hw))
    return state["params"]


def evaluate(params, cfg, n=4):
    correct = total = 0
    spikes = 0.0
    for i in range(n):
        b = image_batch(123, i, 32, num_classes=cfg.num_classes, hw=cfg.img_hw)
        logits, counts = vgg9_forward(params, b["images"], cfg)
        correct += int((jnp.argmax(logits, -1) == b["labels"]).sum())
        total += 32
        spikes += float(sum(counts.values()))
    return correct / total, spikes / total


def run():
    cfg_q = dataclasses.replace(CFG, quant_bits=4)
    p_f = train(CFG)
    p_q = train(cfg_q)
    us = time_fn(jax.jit(lambda im: vgg9_forward(p_f, im, CFG)[0]),
                 image_batch(0, 0, 32, num_classes=4, hw=CFG.img_hw)["images"])
    acc_f, spk_f = evaluate(p_f, CFG)
    acc_q, spk_q = evaluate(p_q, cfg_q)
    delta = (spk_f - spk_q) / spk_f * 100
    emit("fig1/fp32", us, f"acc={acc_f:.3f};spikes_per_img={spk_f:.0f}")
    emit("fig1/int4_qat", us, f"acc={acc_q:.3f};spikes_per_img={spk_q:.0f}")
    emit("fig1/quant_spike_reduction", us,
         f"pct={delta:.1f};paper_band=6.1-15.2;acc_delta={abs(acc_f-acc_q):.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
