"""Mixture-of-Experts layer: sort-based routing + capacity grouped GEMM.

The hybrid dense/sparse insight of the paper maps structurally onto MoE: the
router is the event generator and experts are event-gated compute — work is
spent only where tokens are routed, the LM-scale analogue of event-driven
execution (DESIGN.md §4).

Implementation (TPU-canonical, GShard/MaxText-style dropped-token capacity):
  1. top-k route, flatten to T*k (token, expert) pairs, sort by expert id;
  2. gather each expert's contiguous rows into a fixed-capacity buffer
     [E, C, d] (C = T*k/E * capacity_factor; overflow rows dropped — the
     bounded-imbalance contract that keeps step shapes static at scale);
  3. three batched GEMMs `ecd,edf->ecf` on the MXU;
  4. masked scatter-back + gate-weighted combine.

`jax.lax.ragged_dot` was rejected: its CPU lowering materializes a dense
[E, T, ff] mask tensor (40 GiB/buffer at the production shapes); the
capacity formulation is also what real TPU MoE stacks ship.

Under an ambient compute mesh (dist.context), routing runs shard-locally via
shard_map (manual over DP axes, auto over 'model') so the sort/gather/scatter
never leave the data-parallel shard.

A Switch-style load-balancing auxiliary loss is returned alongside.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, mlp_apply, mlp_init


def moe_init(key, d: int, n_experts: int, d_ff_e: int, act: str, dtype,
             shared_expert: bool = False, d_ff_shared: int = 0,
             n_experts_padded: int = 0) -> Dict:
    n_experts = max(n_experts_padded, n_experts)  # padded experts router-masked
    ks = jax.random.split(key, 5)
    n_mats = 3 if act in ("swiglu", "geglu") else 2
    experts = {
        "w_in": jax.vmap(lambda k: dense_init(k, d, d_ff_e, dtype))(jax.random.split(ks[0], n_experts)),
        "w_out": jax.vmap(lambda k: dense_init(k, d_ff_e, d, dtype))(jax.random.split(ks[1], n_experts)),
    }
    if n_mats == 3:
        experts["w_gate"] = jax.vmap(lambda k: dense_init(k, d, d_ff_e, dtype))(
            jax.random.split(ks[2], n_experts))
    p = {"w_router": dense_init(ks[3], d, n_experts, dtype), "experts": experts}
    if shared_expert:
        p["shared"] = mlp_init(ks[4], d, d_ff_shared or d_ff_e, act, dtype)
    return p


def moe_apply(p: Dict, x: jax.Array, *, top_k: int, act: str, n_experts: int,
              capacity_factor: float = 1.25, unroll: bool = False,
              n_experts_padded: int = 0,
              fsdp_experts: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar)."""
    n_valid = n_experts
    n_experts = max(n_experts_padded, n_experts)
    from ..dist.context import current_mesh
    mesh = current_mesh()
    if mesh is not None and fsdp_experts:
        # FSDP gather: expert weights are stored 'data'-sharded on the expert
        # axis (dist.sharding.param_spec); constrain to the compute layout —
        # expert axis gathered, d_ff kept 'model'-sharded (column-parallel, so
        # the gather never crosses the tensor-parallel axis) — here so GSPMD
        # inserts one all-gather per layer (overlappable), instead of keeping
        # a full replica resident.
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..dist.sharding import _repair

        def _gather_spec(path, leaf):
            # mirror param_spec's matrix layout: w_out is row-parallel
            # ('model' on d_ff, dim -2); w_in/w_gate are column-parallel
            # ('model' on d_ff, the last dim) — only the expert axis moves.
            name = str(getattr(path[-1], "key", path[-1]))
            tp_dim = len(leaf.shape) - (2 if name == "w_out" else 1)
            axes = [None] * len(leaf.shape)
            axes[tp_dim] = "model"
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, P(*_repair(axes, tuple(leaf.shape), mesh))))

        p = dict(p)
        p["experts"] = jax.tree_util.tree_map_with_path(_gather_spec, p["experts"])
    if mesh is not None and "data" in mesh.axis_names:
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        ndp = 1
        for a in dp:
            ndp *= mesh.shape[a]
        if x.shape[0] % ndp == 0 and x.shape[0] >= ndp:
            pspec = jax.tree.map(lambda _: P(), p)
            dtype = x.dtype
            # f32 at the shard_map boundary: the replicated-param grad psum
            # otherwise lowers to a bf16 all-reduce, which trips an XLA-CPU
            # promotion-pass bug in this container (TPU target unaffected).
            p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)

            def local(p_, x_):
                p_ = jax.tree.map(lambda a: a.astype(dtype), p_)
                y, aux = _moe_core(p_, x_, top_k=top_k, act=act,
                                   n_experts=n_experts, n_valid=n_valid,
                                   capacity_factor=capacity_factor, unroll=unroll)
                return y, jax.lax.pmean(aux, dp)

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(pspec, P(dp, None, None)),
                out_specs=(P(dp, None, None), P()),
                axis_names=set(dp), check_vma=False,
            )(p32, x)
    return _moe_core(p, x, top_k=top_k, act=act, n_experts=n_experts,
                     n_valid=n_valid, capacity_factor=capacity_factor, unroll=unroll)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _moe_core(p: Dict, x: jax.Array, *, top_k: int, act: str, n_experts: int,
              n_valid: int, capacity_factor: float,
              unroll: bool) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    rows = t * top_k
    capacity = min(_round_up(int(rows / n_valid * capacity_factor) + 1, 8), rows)

    logits = (xt @ p["w_router"]).astype(jnp.float32)          # [T, E]
    if n_valid < n_experts:                                    # mask padded experts
        pad_mask = jnp.arange(n_experts) >= n_valid
        logits = jnp.where(pad_mask[None], -1e30, logits)
    gate_vals, idx = jax.lax.top_k(logits, top_k)              # [T, k]
    if top_k == 1:
        weights = jax.nn.sigmoid(gate_vals)                    # keep router gradient
    else:
        weights = jax.nn.softmax(gate_vals, axis=-1)

    flat_expert = idx.reshape(-1)                              # [T*k]
    token_idx = jnp.repeat(jnp.arange(t), top_k)               # [T*k]
    order = jnp.argsort(flat_expert)                           # int keys: cheap VJP
    sorted_expert = flat_expert[order]
    src_token = token_idx[order]
    group_sizes = jnp.bincount(flat_expert, length=n_experts).astype(jnp.int32)
    offsets = jnp.cumsum(group_sizes) - group_sizes            # [E]

    # rank of each sorted row within its expert; rows >= capacity are dropped
    rank = jnp.arange(rows, dtype=jnp.int32) - offsets[sorted_expert]
    valid = rank < capacity

    xs = xt[src_token]                                         # [T*k, d] sorted
    # pad so dynamic_slice never clamps (offset + capacity can exceed rows)
    xs_pad = jnp.pad(xs, ((0, capacity), (0, 0)))

    def gather_expert(e):
        blk = jax.lax.dynamic_slice(xs_pad, (offsets[e], 0), (capacity, d))
        mask = (jnp.arange(capacity, dtype=jnp.int32) < group_sizes[e])
        return blk * mask[:, None].astype(blk.dtype)

    # vmap (not a Python loop): lowers to one batched gather, which HLO cost
    # analysis charges once — an unrolled loop charges the full xs operand per
    # expert (48x bytes inflation in the dry-run accounting)
    xe = jax.vmap(gather_expert)(jnp.arange(n_experts, dtype=jnp.int32))

    h = jnp.einsum("ecd,edf->ecf", xe, p["experts"]["w_in"])   # [E, C, ff]
    if act in ("swiglu", "geglu"):
        hg = jnp.einsum("ecd,edf->ecf", xe, p["experts"]["w_gate"])
        h = (jax.nn.silu(hg) if act == "swiglu" else jax.nn.gelu(hg)) * h
    elif act == "gelu":
        h = jax.nn.gelu(h)
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    oe = jnp.einsum("ecf,efd->ecd", h, p["experts"]["w_out"])  # [E, C, d]

    # scatter back: sorted row i reads oe[expert_i, rank_i] when valid
    out_rows = oe[sorted_expert, jnp.clip(rank, 0, capacity - 1)]
    gate = (weights.reshape(-1)[order] * valid).astype(xt.dtype)   # [T*k] bf16
    contrib = out_rows.astype(xt.dtype) * gate[:, None]
    y = jnp.zeros_like(xt).at[src_token].add(contrib)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], xt, act)

    # Switch-style load-balancing loss: E * sum_e f_e * p_e
    router_probs = jax.nn.softmax(logits, axis=-1)             # [T, E]
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)).sum(1), axis=0)
    frac_probs = jnp.mean(router_probs, axis=0)
    aux = n_experts * jnp.sum(frac_tokens / top_k * frac_probs)
    return y.reshape(b, s, d), aux
