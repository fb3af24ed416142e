"""Simulator: predict the per-iteration cost of a planned PCG.

Reference: ``src/runtime/simulator.cc`` — ``Simulator::simulate_runtime``
builds a task graph of per-op measured costs + comm edges and event-simulates
it.  Differences here, on purpose:

* XLA executes one fused program per step, so a serial walk over plan steps
  with an overlap discount models reality better than a Legion-style task
  event sim; compute comes from a roofline over *local* (per-device) shapes.
* **Fusion-aware** (SURVEY §7's named hard part — "per-op measured costs
  don't sum under XLA fusion"; VERDICT r3 #4): only HEAVY ops (GEMMs,
  convs, attention, embedding gathers) pay HBM traffic; elementwise/norm/
  softmax glue fuses into its neighbors and contributes flops only.  Weights
  that fit VMEM stay resident across the training scan and stream nothing;
  there is ONE per-step dispatch overhead, not one per op (the old per-op
  ``kernel_overhead`` × op-count was the dominant error on small graphs).
* Per-op **measured** costs (the ``measure_operator_cost`` analog in
  ``measure.py``) override the roofline for heavy ops when a calibration
  cache is present.
* Training cost = forward × ``train_step_factor`` (measured whole-step /
  forward ratio — backward + optimizer update) + gradient all-reduce for
  replicated params whose op shards the batch.  The factor, MXU efficiency,
  VMEM residency budget, step overhead, and comm overlap all live in the
  machine spec and are overridden by measured calibration
  (``MachineModel.with_calibration``), not hard-coded here.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from ..core.pcg import Plan, Step
from .machine_model import MachineModel


@dataclasses.dataclass
class CostBreakdown:
    compute: float = 0.0
    comm: float = 0.0
    grad_comm: float = 0.0

    @property
    def total(self) -> float:
        return self.compute + self.comm + self.grad_comm

    def __str__(self):
        return (
            f"total={self.total * 1e3:.3f}ms (compute={self.compute * 1e3:.3f} "
            f"comm={self.comm * 1e3:.3f} grad={self.grad_comm * 1e3:.3f})"
        )


def _local_size(spec, sh, mesh) -> int:
    try:
        shape = sh.local_shape(spec.shape, mesh)
    except ValueError:
        shape = spec.shape
    return int(np.prod(shape)) if shape else 1


# Op families that land on the MXU or stay memory-bound as standalone fused
# kernels.  Everything else (elementwise, norms, softmax, cast, dropout,
# shape ops, reductions) is glue XLA fuses into its neighbors: it adds VPU
# flops but no extra HBM round trips.
HEAVY_OPS = frozenset({
    "linear", "shared_expert_linear", "batch_matmul", "conv2d", "embedding",
    "experts",
    "multihead_attention", "inc_multihead_self_attention",
    "spec_inc_multihead_self_attention", "tree_inc_multihead_self_attention",
    "group_by", "aggregate", "aggregate_spec",
})


def serve_component_of(op) -> str:
    """Serve-graph cost-component family of one op: ``attention`` /
    ``lm_head`` / ``mlp`` — THE one classifier both sides of the
    step-level cost attribution share (``serve_search.pp_serve_cost``'s
    decomposed pricing and ``obs.profiler.plan_cost_card``'s
    deterministic counters), so a new op type cannot be priced as one
    family and counted as another.  Attention = any serve attention
    variant (type name ends in ``multihead_self_attention``); lm_head =
    the Linear the InferenceManager marked for LM-head gating
    (``cost_logit_rows``); everything else (MLP linears, embedding,
    norms' weights) folds into ``mlp``."""
    if op.type_name.endswith("multihead_self_attention"):
        return "attention"
    if getattr(op, "cost_logit_rows", None) is not None:
        return "lm_head"
    return "mlp"


def _step_flops(step: Step, mesh) -> float:
    """Local (per-device) flops: global scaled by the output shard fraction
    (+ contracted-dim sharding for partial outputs)."""
    global_flops = step.node.op.flops(step.in_specs)
    shard_frac = 1.0
    if step.out_specs:
        g = int(np.prod(step.out_specs[0].shape)) or 1
        l = _local_size(step.out_specs[0], step.out_shardings[0], mesh)
        shard_frac = l / g
        for a in step.out_shardings[0].partial_axes:
            shard_frac /= mesh.shape[a]
    return global_flops * shard_frac


def _step_compute_time(step: Step, mesh, mm: MachineModel,
                       measured: Optional[Dict] = None,
                       training: bool = True,
                       param_bytes: float = 0.0,
                       fused: bool = True) -> float:
    """One op's contribution to the fused program's time.

    ``param_bytes``: the op's local weight bytes ALREADY scaled by the VMEM
    residency rule (0 when the whole model's weights stay resident).
    """
    spec_hw = mm.spec
    op = step.node.op
    heavy = op.type_name in HEAVY_OPS
    tf = spec_hw.train_step_factor if training else 1.0
    # measured-cost cache lookup (op signature + local shapes); ``measured``
    # is a CostCache (repr-string keys) or any mapping supporting __contains__
    if measured is not None and heavy:
        key = _measure_key(step, mesh)
        if key in measured:
            return measured[key] * tf

    flops = _step_flops(step, mesh)
    if not (fused and not heavy):
        bytes_accessed = param_bytes
        for spec, sh in zip(step.in_specs, step.in_shardings):
            bytes_accessed += (_local_size(spec, sh, mesh)
                               * spec.nbytes() // max(spec.size, 1))
        for spec, sh in zip(step.out_specs, step.out_shardings):
            bytes_accessed += (_local_size(spec, sh, mesh)
                               * spec.nbytes() // max(spec.size, 1))
    else:
        bytes_accessed = 0.0  # fused into a neighbor: flops-only

    if heavy:
        # JAX's default matmul precision on TPU computes f32 GEMMs as a
        # single bf16 pass, so the MXU peak applies regardless of dtype
        peak = spec_hw.peak_flops_bf16 * spec_hw.mxu_efficiency
    else:
        dtype_bits = (8 * (step.out_specs[0].nbytes()
                           // max(step.out_specs[0].size, 1))
                      if step.out_specs else 32)
        peak = (spec_hw.peak_flops_bf16 if dtype_bits <= 16
                else spec_hw.peak_flops_f32)
        peak /= 8.0  # glue runs on the VPU, roughly an order below the MXU
    fwd = max(flops / peak, bytes_accessed / spec_hw.hbm_bandwidth)
    if not fused:
        fwd += spec_hw.kernel_overhead  # legacy per-op mode
    return fwd * tf


def _step_param_bytes(step: Step, plan: Plan, mesh) -> float:
    """Local (per-device) weight bytes the op streams each step."""
    pshs = plan.param_shardings.get(step.node.name, {})
    total = 0.0
    for p in step.node.op.params():
        sh = pshs.get(p.name)
        n = _local_size(p.spec, sh, mesh) if sh is not None else p.spec.size
        total += n * (p.spec.nbytes() // max(p.spec.size, 1))
    return total


def _measure_key(step: Step, mesh):
    local_in = tuple(
        sh.local_shape(spec.shape, mesh)
        for spec, sh in zip(step.in_specs, step.in_shardings)
    )
    return (step.node.op.attr_signature(), local_in)


def step_state_bytes(step: Step, mesh, names=None) -> float:
    """Local bytes of one op's registered serve-state buffers (KV caches +
    spec buffers), sharded by the step's own head-axis config.  ``names``
    optionally restricts to specific buffers (the PP decode cost model
    counts only the committed k/v (+scale) caches it streams per
    macro-step).  0.0 for ops without registered serve capacities."""
    op = step.node.op
    if not (hasattr(op, "state_specs")
            and getattr(op, "cost_max_requests", None)):
        return 0.0
    import jax.numpy as jnp  # np.dtype can't parse "bfloat16"

    head_axes = tuple((step.config or {}).get("head", ()))
    specs = op.state_specs(
        op.cost_max_requests,
        getattr(op, "cost_seq_len", 512),
        getattr(op, "cost_max_spec", 0),
        head_axes,
    )
    total = 0.0
    for name, (shape, dt, sh) in specs.items():
        if names is not None and name not in names:
            continue
        try:
            local = sh.local_shape(shape, mesh)
        except ValueError:
            local = shape
        total += int(np.prod(local)) * jnp.dtype(dt).itemsize
    return total


def plan_memory_bytes(plan: Plan, training: bool = True) -> float:
    """Per-device peak-HBM estimate for a planned PCG.

    Reference: ``src/runtime/memory_optimization.cc`` (Unity's memory-aware
    search).  Counts, per device: local param bytes (×4 when training:
    weight + gradient + two optimizer slots — Adam's m and v; SGD momentum
    uses one slot less, but the estimate must err HIGH), plus stored forward
    activations (training keeps every op output for backward; inference only
    the largest transient), plus **serve state buffers** (KV caches + spec
    buffers) for stateful ops whose serve capacities were registered
    (``InferenceManager`` sets ``cost_max_requests``/``cost_seq_len``/
    ``cost_max_spec`` on the attention ops) — the candidate's own head-axis
    config shards them, so the search correctly sees that TP shrinks the
    per-device cache (VERDICT r3 #5).  An upper bound, deliberately — the
    search uses it to REJECT plans, so erring high only costs optimality,
    never an OOM.
    """
    return plan_memory_parts(plan, training=training)["total"]


def plan_memory_parts(plan: Plan, training: bool = True) -> Dict[str, float]:
    """:func:`plan_memory_bytes` decomposed per component (same arithmetic,
    so the parts always sum to the total the capacity gate uses)::

        {"weights": ..., "kv_state": ..., "transient": ..., "total": ...}

    ``weights`` = local param bytes (×4 training, int8 values+scales when
    annotated); ``kv_state`` = registered serve-state buffers (KV caches +
    spec buffers, sharded by the plan's own head-axis config);
    ``transient`` = stored activations (every output when training, the
    largest single transient for inference).  The decomposition is what
    the memory ledger (obs/memory.py) reconciles component-by-component
    against the REAL allocation, so a weights-model error and a KV-model
    error calibrate independently instead of blurring into one total.
    """
    mesh = plan.mesh
    params = 0.0
    acts = []
    state = 0.0
    # weight matrices replaced by serve int8 quantization (serve/quant.py
    # quantize_int8 / annotate_int8 set ``op.quantization = "int8"``): count
    # 1 byte/element plus the per-out-channel f32 scale instead of the
    # ParamSpec dtype — this is what makes the full-depth 7B-shape serve
    # config (int8 weights + int8 KV) admissible within one chip's HBM.
    _INT8_PARAM_NAMES = ("kernel", "qkv", "o_proj", "q_proj", "kv_a", "kv_b")
    for step in plan.steps:
        if step.is_parallel:
            continue
        pshs = plan.param_shardings.get(step.node.name, {})
        q8 = getattr(step.node.op, "quantization", None) == "int8"
        for p in step.node.op.params():
            sh = pshs.get(p.name)
            n = _local_size(p.spec, sh, mesh) if sh is not None else p.spec.size
            if (q8 and p.name in _INT8_PARAM_NAMES
                    and len(p.spec.shape) >= 2):
                # int8 values + f32 scales (one per output channel; the
                # GLOBAL scale count — errs high under sharding, as this
                # estimator must)
                b = n + (p.spec.size // p.spec.shape[0]) * 4
            else:
                b = n * (p.spec.nbytes() // max(p.spec.size, 1))
            params += b * (4.0 if training and p.trainable else 1.0)
        # NOTE on serve LM-head gating (Linear.cost_logit_rows): the gated
        # prefill program materializes only cost_logit_rows logit rows, but
        # this estimate deliberately does NOT take that discount — the SAME
        # plan also compiles decode/mixed-step programs whose batches carry
        # no ``logit_slots`` and still materialize the full
        # [max_tokens, vocab] logits, and this function's contract is an
        # upper bound over every program the plan can run (err HIGH: a
        # wrong reject costs optimality, a wrong admit OOMs).  The gating
        # discount lives in Linear.flops (a cost-model, not a capacity,
        # concern).
        for spec, sh in zip(step.out_specs, step.out_shardings):
            acts.append(
                _local_size(spec, sh, mesh) * (spec.nbytes() // max(spec.size, 1))
            )
        state += step_state_bytes(step, mesh)
    act = sum(acts) if training else max(acts, default=0)
    return {"weights": params, "kv_state": state, "transient": act,
            "total": params + act + state}


def compose_stage_parts(parts) -> Dict[str, float]:
    """Per-device composition of per-stage :func:`plan_memory_parts`
    dicts (one entry per pipeline stage; a single-plan deployment passes
    a one-element list): each component's max across stages — components
    may bind on different chips — plus ``static`` = weights + kv_state
    composed per stage FIRST, so it is a real binding chip's allocatable
    share.  THE one composition every predicted-side memory-ledger
    emitter shares (``search_serve_plan`` and the managers'
    ``publish_memory``), so the ledger can never receive
    differently-composed values under one plan key.  Bytes in, bytes
    out."""
    return {
        **{c: max(p[c] for p in parts)
           for c in ("weights", "kv_state", "transient", "total")},
        "static": max(p["weights"] + p["kv_state"] for p in parts),
    }


def simulate(
    plan: Plan,
    machine: Optional[MachineModel] = None,
    training: bool = True,
    measured: Optional[Dict] = None,
    overlap: Optional[float] = None,
    fused: bool = True,
) -> CostBreakdown:
    """Predict one iteration's wall time for this plan.

    ``overlap``: fraction of communication hidden behind compute (XLA async
    collectives overlap well when compute is abundant; 0 = fully serial);
    defaults to the machine spec's calibrated value.  ``fused=False``
    restores the legacy per-op roofline (each op pays its own HBM traffic
    and kernel overhead).
    """
    mesh = plan.mesh
    mm = machine or MachineModel.for_mesh(mesh)
    if overlap is None:
        overlap = mm.spec.overlap
    cost = CostBreakdown()

    # VMEM weight residency: a model whose local weights fit the resident
    # budget streams NOTHING per step inside the training scan (XLA pins
    # them); larger models stream the excess fraction of every weight
    param_total = sum(
        _step_param_bytes(s, plan, mesh)
        for s in plan.steps if not s.is_parallel
    )
    stream_frac = 1.0
    if fused and param_total > 0:
        stream_frac = max(
            0.0, 1.0 - mm.spec.vmem_resident_bytes / param_total
        )

    for step in plan.steps:
        if step.is_parallel:
            op = step.node.op
            b = op.comm_bytes(step.in_specs[0], step.in_shardings[0], mesh)
            t = mm.collective_time(b, getattr(op, "axes", ()), mesh)
            if training:
                # the reshard's transpose appears in backward too
                t *= 2.0
            cost.comm += t
        else:
            cost.compute += _step_compute_time(
                step, mesh, mm, measured, training,
                param_bytes=_step_param_bytes(step, plan, mesh) * stream_frac,
                fused=fused,
            )
    if fused:
        # ONE dispatch/loop overhead per compiled step, not one per op
        cost.compute += mm.spec.step_overhead

    if training:
        # gradient all-reduce: params replicated over axes that shard the
        # op's batch get a psum of their gradient (GSPMD inserts it; the
        # reference's NCCL allreduce stage)
        for step in plan.steps:
            if step.is_parallel or not step.config:
                continue
            batch_axes = tuple(step.config.get("sample", ()))
            if not batch_axes:
                continue
            pshs = plan.param_shardings.get(step.node.name, {})
            ps = {p.name: p for p in step.node.op.params()}
            for pname, sh in pshs.items():
                if not ps.get(pname) or not ps[pname].trainable:
                    continue
                axes = tuple(a for a in batch_axes if a not in sh.used_axes())
                if not axes:
                    continue
                spec = ps[pname].spec
                deg = 1
                for a in axes:
                    deg *= mesh.shape[a]
                local_bytes = _local_size(spec, sh, mesh) * (
                    spec.nbytes() // max(spec.size, 1)
                )
                b = 2 * local_bytes * (deg - 1) / deg
                cost.grad_comm += mm.collective_time(b, axes, mesh)

    hidden = min(cost.comm + cost.grad_comm, cost.compute) * overlap
    total_comm = cost.comm + cost.grad_comm - hidden
    # fold the discount proportionally so the breakdown still sums to total
    if cost.comm + cost.grad_comm > 0:
        scale = total_comm / (cost.comm + cost.grad_comm)
        cost.comm *= scale
        cost.grad_comm *= scale
    return cost
