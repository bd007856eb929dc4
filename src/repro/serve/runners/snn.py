"""SNN runner: batched spiking-VGG9 inference behind the `ModelRunner` protocol.

Wraps `models.vgg9.vgg9_infer_hybrid` — the fused dense-core + sparse-core
serving graph — under a `core.hybrid.plan_vgg9_inference` plan sized to the
engine's fixed slot count, so every batch reuses one compiled graph. Image
requests are stacked into the slot batch (zero images fill empty slots; all
layers are row-independent, so real rows are bit-identical to a direct
`vgg9_infer_hybrid` call on the same batch), and the fused pipeline's
occupancy/skip counters are split back out per request:

* spike counts — the per-image input/output sums the fused graph measures
  ([B] vectors; 0/1 spikes make the split exact);
* tile-skip rates — each request's rows of the folded [T*B·H·W, K] matmul
  re-tiled at the layer's block size, i.e. the skip rate the occupancy map
  would deliver if the request were served alone (a tile straddling two
  images never bills the silent one);
* paper-model energy — Eq. 3 workloads built from each request's *measured*
  input-spike counts, priced with the plan's NC allocation and the FPGA
  power model (`core.energy.energy_per_image`).

Data-mesh sharding: under an ambient compute mesh (`dist.context`) whose
``'data'`` axis divides the slot count, `run` switches to
`vgg9_infer_hybrid_sharded` — the folded [T*B·H·W, K] matmuls split across
devices, weights replicated, and the per-shard occupancy counters are
re-assembled so every per-request stat (skip rate, spike counts, energy) is
identical to the single-device run. `EngineCore` needs no changes: sharding
is a runner concern, engaged by wrapping engine stepping in
``compute_mesh(mesh)``.
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...core.energy import analytical_energy_per_image, energy_per_image
from ...core.hybrid import HybridPlan, plan_vgg9_inference
from ...core.workload import (conv_workload, dense_input_workload, fc_workload)
from ...dist.context import current_mesh
from ...models.vgg9 import (VGG9Config, conv_names, vgg9_infer_hybrid,
                            vgg9_infer_hybrid_sharded)
from ...obs.stages import Stages
from ..api import (PAD_REQUEST_ID, Request, Result, SlotProgress, StepBudget,
                   StepReport)


def _per_request_skip(row_occ: np.ndarray, block_m: int, rows: int,
                      rows_per_slice: int, batch: int) -> np.ndarray:
    """Split a folded layer's occupancy back out per request.

    row_occ: [M_pad, K/bk] 0/1 spike occupancy at (row x k-tile) granularity,
    rows ordered (t*batch + b)*rows_per_slice + pixel. For each request we
    gather *its own* rows (in folded order — the order a solo run would fold
    them) and re-tile them at the layer's block_m: the returned skip rate is
    the fraction of (block_m x block_k) tiles the occupancy map would skip if
    the request were served alone with the same kernel plan. This makes the
    per-request number independent of who shares a straddled tile — a silent
    request reports exactly 1.0 next to a dense neighbour — which is the
    intrinsic sparsity signal a co-batching scheduler needs.
    """
    kt = row_occ.shape[1]
    owner = (np.arange(rows) // rows_per_slice) % batch  # folded slice -> request
    skip = np.zeros(batch)
    for b in range(batch):
        rb = row_occ[:rows][owner == b]                  # [T*rows_per_slice, kt]
        pad = (-len(rb)) % block_m
        if pad:
            rb = np.concatenate([rb, np.zeros((pad, kt), rb.dtype)])
        occ = rb.reshape(-1, block_m, kt).any(axis=1)
        skip[b] = 1.0 - occ.sum() / occ.size
    return skip


def _per_timestep_occupancy(row_occ: np.ndarray, rows: int,
                            rows_per_slice: int, batch: int) -> np.ndarray:
    """Per-request per-timestep active-row fraction, [T, B].

    Rows of the folded matmul are ordered (t*batch + b)*rows_per_slice +
    pixel, so slicing the 0/1 row occupancy back out by (t, b) gives each
    request's sparsity *trace over timesteps* — the per-timestep stat the
    engine streams through `poll_partial` while a request is in flight.
    """
    active = row_occ[:rows].any(axis=1).astype(np.float64)
    t = rows // (batch * rows_per_slice)
    return active.reshape(t, batch, rows_per_slice).mean(axis=2)


#: the stat leaves `SNNRunner` reads back per layer of the fused graph
_FETCHED = ("skip_rate", "out_spikes_per_image", "in_spikes_per_image",
            "row_occ", "block_m", "rows")


def _fetched(logits, stats):
    """What the runner reads back of one fused-graph call: the logits and,
    per layer in the graph's order, the stat leaves named in `_FETCHED`
    (``occ_map`` and the tile counts stay on the device)."""
    return logits, {name: {f: st[f] for f in _FETCHED if f in st}
                    for name, st in stats.items()}


class SNNRunner:
    """Fixed-slot spiking-VGG9 serving (`ModelRunner`)."""

    def __init__(self, cfg: VGG9Config, params):
        self.cfg = cfg
        self.params = params
        self._plans: Dict[int, HybridPlan] = {}
        self._replicated: Dict = {}         # mesh -> params on every device
        #: host time per stage of `run` (``snn.*`` spans, once per call)
        self.stages = Stages()

    def plan(self, batch: int) -> HybridPlan:
        """The inference plan for a slot count (cached: plans are static jit
        arguments, so one plan per batch size means one compiled graph)."""
        if batch not in self._plans:
            self._plans[batch] = plan_vgg9_inference(self.cfg, batch)
        return self._plans[batch]

    # -- ModelRunner protocol ------------------------------------------------

    def bucket_key(self, request: Request) -> Hashable:
        return tuple(np.shape(request.payload))

    def filler(self, request: Request) -> Request:
        return Request(PAD_REQUEST_ID, jnp.zeros_like(jnp.asarray(request.payload)))

    def _data_shards(self, n: int) -> int:
        """How many ways to split a slot batch: the ambient mesh's 'data'
        axis size when it divides the batch, else 1 (unsharded)."""
        mesh = current_mesh()
        if mesh is None or "data" not in mesh.axis_names:
            return 1
        ndev = int(mesh.shape["data"])
        return ndev if ndev > 1 and n % ndev == 0 else 1

    def _call(self, fn, *args, **kwargs):
        """Dispatch the fused graph and start the host copies of what the
        runner reads of it (`_fetched`), then wait for the device: two
        spans, so the enqueue (and any recompile) and the wait on the chip
        read apart. The copies queue behind the graph; `_fetch` collects
        them. Returns the fetched tree, still on the device."""
        with self.stages.span("snn.dispatch"):
            logits, _, stats = fn(*args, **kwargs)
            fetched = _fetched(logits, stats)
            for leaf in jax.tree_util.tree_leaves(fetched):
                leaf.copy_to_host_async()
        with self.stages.span("snn.device_wait"):
            return jax.block_until_ready(fetched)

    def _fetch(self, fetched):
        """Bring `_call`'s fetched tree to the host in one `jax.device_get`
        (one blocking wait a step, not one per array), and count the wait,
        the arrays and their bytes in the ``snn.fetch`` stage.

        Returns numpy ``logits`` and, in the graph's layer order, ``skip``
        ({layer: skip_rate}), ``out_spikes`` and ``in_spikes`` ({layer:
        float64 [B]}) and ``occ`` ({layer: (row_occ, block_m, rows)}) of the
        mapped layers; the sharded graph's ``[ndev]`` leaves keep that
        axis."""
        leaves = jax.tree_util.tree_leaves(fetched)
        logits, host = jax.device_get(fetched)
        self.stages.add("snn.fetch", waits=1, arrays=len(leaves),
                        bytes=sum(leaf.nbytes for leaf in leaves))
        layers = [(name, host[name]) for name in fetched[1]]
        skip = {k: v["skip_rate"] for k, v in layers if "skip_rate" in v}
        out_spikes = {k: np.asarray(v["out_spikes_per_image"], np.float64)
                      for k, v in layers}
        in_spikes = {k: np.asarray(v["in_spikes_per_image"], np.float64)
                     for k, v in layers if "in_spikes_per_image" in v}
        occ = {k: (v["row_occ"], v["block_m"], v["rows"])
               for k, v in layers if "row_occ" in v}
        return logits, skip, out_spikes, in_spikes, occ

    def _run_unsharded(self, images, n: int):
        plan = self.plan(n)
        fetched = self._call(
            vgg9_infer_hybrid, self.params, images, self.cfg, plan=plan,
            return_stats=True)
        with self.stages.span("snn.fetch"):
            logits, skip, out_spikes, in_spikes, occ = self._fetch(fetched)
            batch_skip = {k: float(v) for k, v in skip.items()}
            occ = {name: (row_occ, int(block_m), int(rows))
                   for name, (row_occ, block_m, rows) in occ.items()}

        with self.stages.span("snn.split"):
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

    def _run_sharded(self, images, n: int, ndev: int):
        """Split the slot batch over the data mesh (`vgg9_infer_hybrid_sharded`)
        and re-assemble per-request counters from the per-shard stats.

        Per-image spike vectors come back shard-concatenated (already global);
        occupancy maps come back stacked per shard, so per-request skip rates
        are computed shard-by-shard — device ``d`` owns requests
        ``[d*n/ndev, (d+1)*n/ndev)`` — and written into the global vector.
        The numbers match the unsharded run exactly: rows_per_slice and the
        128-row sparse M tile are batch-size-invariant, so re-tiling a
        request's own rows gives the same served-alone skip rate."""
        mesh = current_mesh()
        b_local = n // ndev
        plan = self.plan(b_local)
        if mesh not in self._replicated:
            # place the weights on every device of the mesh once, instead of
            # broadcasting them from the default device on every batch
            self._replicated[mesh] = jax.device_put(
                self.params, NamedSharding(mesh, PartitionSpec()))
        fetched = self._call(
            vgg9_infer_hybrid_sharded, self._replicated[mesh], images,
            self.cfg, mesh=mesh, plan=plan, return_stats=True)
        with self.stages.span("snn.fetch"):
            logits, skip, out_spikes, in_spikes, occ = self._fetch(fetched)
            batch_skip = {k: float(np.mean(v)) for k, v in skip.items()}

        with self.stages.span("snn.split"):
            per_req_skip: Dict[str, np.ndarray] = {}
            ts_occ: Dict[str, np.ndarray] = {}
            t = self.cfg.timesteps
            for name, (row_occ, block_m, rows) in occ.items():
                rps = plan.layer(name).kernel.m // (t * b_local)
                skip = np.zeros(n)
                occ_t = np.zeros((t, n))
                for d in range(ndev):
                    sl = slice(d * b_local, (d + 1) * b_local)
                    skip[sl] = _per_request_skip(
                        row_occ[d], int(block_m[d]), int(rows[d]),
                        rows_per_slice=rps, batch=b_local)
                    occ_t[:, sl] = _per_timestep_occupancy(
                        row_occ[d], int(rows[d]), rows_per_slice=rps,
                        batch=b_local)
                per_req_skip[name] = skip
                ts_occ[name] = occ_t
        return (logits, batch_skip, out_spikes, in_spikes, per_req_skip,
                ts_occ)

    def run(self, batch: Sequence[Request]) -> List[Result]:
        with self.stages.span("snn.input"):
            images = jnp.stack([jnp.asarray(r.payload) for r in batch])
        n = len(batch)
        ndev = self._data_shards(n)
        if ndev > 1:
            logits, batch_skip, out_spikes, in_spikes, per_req_skip, ts_occ = \
                self._run_sharded(images, n, ndev)
        else:
            logits, batch_skip, out_spikes, in_spikes, per_req_skip, ts_occ = \
                self._run_unsharded(images, n)

        # energy is priced with the full-slot-count plan in both modes so a
        # request's Eq. 3 estimate doesn't change with the device count.
        # The batch-context cost is Eq. 3 priced on the batch's *total*
        # measured spikes (pad slots are zero images and contribute
        # nothing). A request's served_energy_j — its share of the batch it
        # actually rode in — is what a sparsity-aware scheduler improves for
        # sparse requests: co-batched with dense stragglers, the batch total
        # (and therefore the share) is dominated by the straggler's spikes.
        n_real = sum(1 for r in batch if not r.is_pad) or 1
        with self.stages.span("snn.energy"):
            plan = self.plan(n)
            energies = [self._energy_estimate(
                            plan, {k: v[i] for k, v in in_spikes.items()})
                        for i in range(n)]
            batch_est = self._energy_estimate(
                plan, {k: float(v.sum()) for k, v in in_spikes.items()})
        batch_stats = {
            "batch_energy_j": batch_est["energy_j"],
            "batch_latency_s": batch_est["latency_s"],
            "batch_real": n_real,
            "served_energy_j": batch_est["energy_j"] / n_real,
            # the analytical (per-op) model's view of the same share, so
            # serving records always carry both cost models side by side
            "served_energy_analytical_j":
                batch_est["energy_analytical_j"] / n_real,
            # active numerics: which weight precision served this request
            "precision": self.precision,
            "wbytes_per": self.wbytes_per,
        }

        results = []
        for i, req in enumerate(batch):
            results.append(Result(req.request_id, logits[i], stats={
                "skip_rate": {k: float(v[i]) for k, v in per_req_skip.items()},
                "batch_skip_rate": batch_skip,
                "out_spikes": {k: float(v[i]) for k, v in out_spikes.items()},
                "in_spikes": {k: float(v[i]) for k, v in in_spikes.items()},
                "spike_total": float(sum(v[i] for v in out_spikes.values())),
                "ts_occupancy": {k: [float(x) for x in v[:, i]]
                                 for k, v in ts_occ.items()},
                **energies[i],
                **batch_stats,
            }))
        return results

    # -- continuous admission ------------------------------------------------

    def session_key(self, request: Request) -> Hashable:
        # one compiled fused graph per image shape: only same-shape images
        # may share a live session's slot batch
        return tuple(np.shape(request.payload))

    def open_session(self, slots: int) -> "_SNNSession":
        return _SNNSession(self, slots)

    # -- paper-model energy --------------------------------------------------

    def _energy_estimate(self, plan: HybridPlan, in_spikes: Dict[str, float]) -> Dict[str, float]:
        """Eq. 3 workloads from one request's measured input spikes, priced
        with the plan's NC allocation and the calibrated FPGA power model."""
        cfg = self.cfg
        convs = cfg.conv_channels
        t = cfg.timesteps
        hw = cfg.img_hw
        n_mp = sum(1 for s in cfg.stages if s == "MP")
        flat = (hw // (2 ** n_mp)) ** 2 * convs[-1]
        wbytes_per = 0.5 if cfg.quant_bits == 4 else 4.0
        precision = "int4" if cfg.quant_bits == 4 else "fp32"

        workloads = [dense_input_workload("conv0", hw, hw, convs[0], t)]
        weight_bytes = [9 * cfg.in_ch * convs[0] * wbytes_per]
        cin = convs[0]
        for i, name in enumerate(conv_names(cfg)[1:], start=1):
            workloads.append(conv_workload(name, convs[i], 9, in_spikes[name]))
            weight_bytes.append(9 * cin * convs[i] * wbytes_per)
            cin = convs[i]
        for name, d_in, d_out in (("fc0", flat, cfg.fc_dim),
                                  ("fc1", cfg.fc_dim, cfg.population)):
            workloads.append(fc_workload(name, d_out, in_spikes[name]))
            weight_bytes.append(d_in * d_out * wbytes_per)

        est = energy_per_image(workloads, plan.cores(), weight_bytes, precision)
        ana = analytical_energy_per_image(workloads, precision)
        return {"energy_j": est["energy_j"], "latency_s": est["latency_s"],
                "energy_analytical_j": ana["energy_j"]}

    @property
    def precision(self) -> str:
        return "int4" if self.cfg.quant_bits == 4 else "fp32"

    @property
    def wbytes_per(self) -> float:
        return 0.5 if self.cfg.quant_bits == 4 else 4.0


class _SNNSession:
    """Slot-refill session: each engine step runs one fused T-timestep batch.

    The spiking VGG9 is feedforward over a fixed timestep window, so a
    request occupies its slot for exactly one step — "continuous admission"
    for this workload means freed (zero-image padding) slots are refilled
    with real queued work at every step boundary instead of only between
    run-to-completion batches. Execution reuses `SNNRunner.run` on the full
    slot width (free slots become zero-image fillers), so row-independence
    keeps mid-stream-admitted requests bit-identical to solo runs.
    """

    def __init__(self, runner: SNNRunner, slots: int):
        self.runner = runner
        self.slots = slots
        self.req: List[Optional[Request]] = [None] * slots

    def admit(self, slot: int, request: Request) -> Optional[Result]:
        assert self.req[slot] is None, f"slot {slot} busy"
        self.req[slot] = request
        return None

    def cancel(self, slot: int) -> Result:
        """An SNN request holds no device state between steps (the fused
        graph runs whole); cancellation just frees the slot."""
        assert self.req[slot] is not None, f"slot {slot} empty"
        req = self.req[slot]
        self.req[slot] = None
        return Result(req.request_id, None, stats={}, status="cancelled")

    def step(self, budget: StepBudget = StepBudget()) -> StepReport:
        """One fused T-timestep batch. The SNN's work unit is the timestep;
        the fused graph always spends all T per occupied slot (a partial-T
        graph would be a different compilation), so the budget is reported
        as cost rather than enforced. Each finished request's per-timestep
        sparsity trace (input-row occupancy per mapped layer) is emitted as
        T partial entries for `EngineCore.poll_partial`."""
        occupied = [i for i in range(self.slots) if self.req[i] is not None]
        if not occupied:
            return StepReport()
        ref = self.req[occupied[0]]
        batch = [self.req[i] if self.req[i] is not None
                 else self.runner.filler(ref) for i in range(self.slots)]
        results = self.runner.run(batch)
        t = self.runner.cfg.timesteps
        finished = {}
        progress = {}
        for i in occupied:
            res = results[i]
            trace = res.stats.get("ts_occupancy", {})
            emitted = tuple({layer: vals[k] for layer, vals in trace.items()}
                            for k in range(t))
            progress[i] = SlotProgress(
                request_id=res.request_id, phase="infer",
                units_done=t, units_total=t, emitted=emitted)
            finished[i] = res
            self.req[i] = None
        return StepReport(finished=finished, progress=progress,
                          cost={"units": t * len(occupied), "timesteps": t})
