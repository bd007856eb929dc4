"""Training driver: real training on the local device(s), or any mesh.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --steps 50 \
        --d-model 64 --n-layers 4 --vocab 512 --seq 128 --batch 8

    # data-parallel with error-feedback int8 gradient compression:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.train --steps 10 --compress-grads

Production posture: the same code path drives the 512-chip mesh (see
launch/dryrun.py for the compile-level proof); on this CPU container the
reduced configs actually train. Checkpoint/restart: --ckpt-dir + --resume.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_arch
from ..data.synthetic import token_batch
from ..dist import sharding as shd
from ..dist.context import compute_mesh
from ..models import transformer as tf
from ..models.frontends import synth_frontend
from ..train.loop import TrainLoop
from ..train.optim import make_optimizer
from ..train.schedule import warmup_cosine
from ..train.train_step import (init_train_state, make_train_step,
                                shard_map_compressed_step, stack_error_state)
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh


def reduce_cfg(cfg, args):
    kw = {"dtype": "float32", "remat": "none"}
    if args.d_model:
        hd = max(args.d_model // cfg.n_heads, 8)
        kw.update(d_model=args.d_model, head_dim=hd,
                  d_ff=0 if cfg.d_ff == 0 else 2 * args.d_model,
                  moe_d_ff=min(cfg.moe_d_ff, args.d_model) if cfg.moe_d_ff else 0,
                  d_rnn=args.d_model if cfg.d_rnn else 0)
    if args.n_layers:
        period = len(cfg.pattern)
        n = max(period, (args.n_layers // period) * period)
        kw.update(n_layers=n + len(cfg.tail))
    if args.vocab:
        kw.update(vocab=args.vocab)
    if cfg.n_frontend_tokens:
        kw.update(n_frontend_tokens=min(cfg.n_frontend_tokens, 8), d_frontend=16)
    if cfg.n_experts > 8:
        kw.update(n_experts=8, top_k=min(cfg.top_k, 2), n_experts_padded=0,
                  fsdp_experts=False)
    return cfg.with_(**kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-size", action="store_true",
                    help="use the arch's full config (needs real hardware)")
    ap.add_argument("--compress-grads", action="store_true",
                    help="error-feedback int8 gradient all-reduce over the "
                         "data axis (dist.compression; shard_map train step)")
    ap.add_argument("--compress-per-channel", action="store_true",
                    help="with --compress-grads: per-channel (leading-axis) "
                         "quantization scales instead of one per-tensor "
                         "scale — tighter for tensors with wide channel "
                         "magnitude spread")
    args = ap.parse_args()
    enable_compile_cache()
    if args.compress_per_channel and not args.compress_grads:
        ap.error("--compress-per-channel requires --compress-grads")

    cfg = get_arch(args.arch)
    if not args.full_size:
        cfg = reduce_cfg(cfg, args)
    mesh = make_host_mesh()

    opt = make_optimizer(cfg.optimizer)
    lr_fn = warmup_cosine(args.lr, 10, args.steps)
    loss_fn = functools.partial(tf.train_loss, cfg=cfg)
    n_data = int(mesh.shape["data"])
    if args.compress_grads:
        assert args.batch % n_data == 0, (args.batch, n_data)
        inner = make_train_step(lambda p, b: loss_fn(p, b), opt, lr_fn,
                                compress_axis="data",
                                compress_per_channel=args.compress_per_channel)
        step = jax.jit(shard_map_compressed_step(inner, mesh))
    else:
        step = jax.jit(make_train_step(lambda p, b: loss_fn(p, b), opt, lr_fn))

    def make_batch(i):
        s_tok = args.seq - (cfg.n_frontend_tokens if cfg.frontend else 0)
        b = token_batch(args.seed, i, args.batch, s_tok, cfg.vocab)
        if cfg.frontend:
            b["frontend_embeds"] = synth_frontend(
                jax.random.fold_in(jax.random.PRNGKey(args.seed), i), cfg, args.batch)
        return b

    # compressed steps are already manual over 'data' (shard_map): no ambient
    # mesh, or the model's internal sharding constraints would nest into it
    import contextlib
    mesh_ctx = contextlib.nullcontext() if args.compress_grads else compute_mesh(mesh)
    with mesh, mesh_ctx:
        params = tf.init_params(jax.random.PRNGKey(args.seed), cfg)
        state = init_train_state(params, opt, compress=args.compress_grads)
        if args.compress_grads:
            state = stack_error_state(state, n_data)
        loop = TrainLoop(step, make_batch, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every, log_every=5)
        restored, start = loop.maybe_restore(jax.eval_shape(lambda: state))
        if restored is not None:
            state, = (restored,)
            print(f"resumed from step {start}")
        state = loop.run(state, args.steps, start_step=start)
    print("final loss:", float(loop.history[-1][1]["loss"]))


if __name__ == "__main__":
    main()
