"""Pipeline-parallel serving: stage-split decode with micro-batch interleaving.

SURVEY §4's inference matrix is "model x precision x TP/PP configs"; the serve
stack so far covered TP only.  This module adds the PP column: the serve graph
is split into ``pp`` contiguous STAGES at small live-set boundaries (the same
live-cut machinery the GPipe training executor carves SESE segments with —
``core.graph.live_cuts``), each stage compiles to its own program over its own
device slice (weights + that stage's KV caches resident per slice — the
capacity lever that lets shapes exceeding one chip's HBM serve across the pp
axis), and activations hop stage to stage.  Decode-time micro-batch
interleaving (Orca OSDI'22) keeps every stage busy: ``m`` micro-batches cycle
through the stage chain continuously, shrinking the steady-state pipeline
bubble from ``(pp-1)/pp`` (one batch, ``m=1``) to ``(pp-m)/pp`` — zero once
``m >= pp`` fills the pipeline (``m = pp`` is the decode optimum: beyond it
stage weights re-stream per micro-batch for no bubble win).

Execution model — MULTI-PROGRAM, host-interleaved: one jitted step per stage
per batch-config type, dispatched asynchronously.  Stage programs occupy
disjoint devices, so dispatching micro-batch j+1's stage-0 right after
micro-batch j's (whose stage-1 is still running) overlaps them for real; the
host never blocks inside a macro-step (the one sync is the caller reading
results).  Inter-stage transfer is a ``jax.device_put`` of the boundary
activations onto the next stage's mesh — on TPU this lowers to an ICI
device-to-device copy, the point-to-point analogue of the training pipeline's
``ppermute`` (which needs every stage inside ONE program; serve stages are
deliberately separate programs so each keeps its own donated KV state and its
own TP sharding through the existing GSPMD path).

Bit-identity: each micro-batch runs the exact op ``lower``s of the plan steps
the single-stage InferenceManager would run, in the same order, on the same
values — stage boundaries only name where activations change devices, and
contiguous-range micro-batch splits preserve the flat batch's causal layout
(see ``BatchConfig.split_microbatches``).  Pinned by tests/test_pp_serve.py
for decode, prefill (tiled + gated), and mixed steps, incl. the int8-weights +
int8-KV configuration.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import live_cuts
from ..core.interpreter import build_forward, init_params
from ..core.pcg import PCG
from ..obs.profiler import NULL_PROFILER
from ..obs.telemetry import NULL_TELEMETRY
from .batch_config import BatchConfig, InferenceResult
from .inference_manager import (
    EXIT_BUDGET,
    EXIT_EOS,
    EXIT_NOT_IN_BATCH,
    EXIT_RUNNING,
    mark_gated_lm_head,
    pick_prefill_tile,
    refuse_unsupported_slot_state,
    register_serve_capacities,
    sample_tokens,
    tensor_parallel_strategy,
)
from .kv_allocator import KVAllocator, StageKV, params_nbytes
from .ops import IncMultiHeadSelfAttention


def serve_stage_split(graph, pp: int, out_tid: Optional[int] = None,
                      max_live: int = 2):
    """Split a serve graph's node chain into ``pp`` contiguous stages.

    Cuts are placed at boundaries whose live tensor set is at most
    ``max_live`` wide (llama-family graphs carry ``{residual, hidden}``
    between decoder layers, so 2 covers them; a pure op chain cuts at
    SESE single-tensor boundaries), balanced so each stage owns an equal
    share of the attention layers — the weight- and KV-heavy units.  Ties
    prefer the narrowest cut, then the latest boundary (so norms feeding a
    layer stay with the upstream stage and the next stage starts at its
    attention).

    Returns ``[(nodes, entry_tids, exit_tids)]`` with
    ``exit_tids[s] == entry_tids[s+1]`` (sorted tid order),
    ``entry_tids[0] == graph.input_tids`` and ``exit_tids[-1] == [out_tid]``.
    """
    nodes = graph.nodes
    if not nodes:
        raise ValueError("empty graph")
    if out_tid is None:
        out_tid = nodes[-1].outputs[-1]
    if pp <= 1:
        return [(list(nodes), list(graph.input_tids), [out_tid])]
    lives = live_cuts(graph, [out_tid])
    is_attn = [isinstance(n.op, IncMultiHeadSelfAttention) for n in nodes]
    total = sum(is_attn)
    if pp > total:
        raise ValueError(
            f"pp={pp} stages need at least that many attention layers "
            f"(graph has {total})"
        )
    cum = np.cumsum(is_attn)
    candidates = [i for i in range(len(nodes) - 1)
                  if len(lives[i]) <= max_live]
    cuts: List[int] = []
    lo_attn = 0
    for s in range(1, pp):
        target = total * s / pp
        pool = [i for i in candidates
                if lo_attn < cum[i] < total
                and (not cuts or i > cuts[-1])]
        if not pool:
            raise ValueError(
                f"no admissible cut for stage boundary {s} "
                f"(live sets wider than {max_live}?)"
            )
        best = min(pool, key=lambda i: (abs(cum[i] - target),
                                        len(lives[i]), -i))
        cuts.append(best)
        lo_attn = cum[best]
    bounds = [-1] + cuts + [len(nodes) - 1]
    stages = []
    for s in range(pp):
        seg = nodes[bounds[s] + 1: bounds[s + 1] + 1]
        entry = (list(graph.input_tids) if s == 0
                 else sorted(lives[bounds[s]]))
        exit_ = ([out_tid] if s == pp - 1 else sorted(lives[bounds[s + 1]]))
        stages.append((seg, entry, exit_))
    return stages


class _StageView:
    """Graph-protocol view of a contiguous node range, plannable by PCG.

    Tensor ids (and ``tensor_specs``) are shared with the parent graph, so
    stage entry tids are exactly the parent's boundary tensors; the view
    only narrows ``nodes`` and redeclares the boundary as graph inputs.
    """

    def __init__(self, parent, nodes, input_tids):
        self.nodes = list(nodes)
        self.input_tids = list(input_tids)
        self.tensor_specs = parent.tensor_specs
        self._parent = parent

    def topo_order(self):
        return self.nodes

    def spec(self, tid):
        return self.tensor_specs[tid]

    def unique_name(self, base):
        return self._parent.unique_name(base)


def build_stage_plans(graph, split, strategy, meshes):
    """One PCG plan per stage: the stage's nodes over its own mesh, with the
    (TP) strategy restricted to them and the boundary tensors as plan
    inputs/outputs.  Used by the executor below AND by the serve search's
    TP x PP pricing (``search.serve_search``) — per-stage
    ``plan_memory_bytes`` is what gates pp admissibility under the HBM cap.
    """
    plans = []
    for (nodes, entry, exit_), mesh in zip(split, meshes):
        names = {n.name for n in nodes}
        cfg = {k: v for k, v in (strategy or {}).items() if k in names}
        view = _StageView(graph, nodes, entry)
        plans.append(PCG(view, mesh, cfg, output_tids=list(exit_)).plan())
    return plans


class _Stage:
    """One pipeline stage: plan + params + KV state + jitted step."""

    def __init__(self, nodes, entry_tids, exit_tids, mesh, plan):
        self.nodes = nodes
        self.entry_tids = list(entry_tids)
        self.exit_tids = list(exit_tids)
        self.mesh = mesh
        self.plan = plan
        self.fwd = build_forward(plan, mode="spmd")
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.replicated = NamedSharding(mesh, P())
        self.params: Optional[Dict] = None
        # per-stage KV ownership (serve/kv_allocator.py): the manager binds
        # a StageKV per stage; ``state`` delegates so the async dispatch
        # loop's donate/re-bind cycle is unchanged
        self.kv: Optional[StageKV] = None
        self.step = None  # bound by the manager (closes over its flags)

    @property
    def state(self) -> Optional[Dict]:
        return self.kv.state if self.kv is not None else None

    @state.setter
    def state(self, value) -> None:
        self.kv.state = value


class PipelinedInferenceManager:
    """Stage-split serving over a ``pp`` (x ``tp``) mesh.

    ``model.mesh`` must carry a ``pp`` axis (and optionally ``tp``); each of
    the ``pp`` device slices runs one stage, tensor-parallel over its own
    ``tp`` sub-axis through the unchanged GSPMD serve path (Megatron head
    sharding, Pallas kernels via the per-op shard_map).  API-compatible with
    :class:`InferenceManager` for the RequestManager: ``step`` /
    ``decode_scan`` / ``reset`` / capacity attributes all behave the same,
    so continuous batching, chunked prefill (tiled + LM-head-gated) and the
    serving loops run unmodified.

    ``n_micro``: decode-time micro-batches per macro-step (default = pp).
    Flat BatchConfigs split into ``n_micro`` contiguous token ranges that
    pipeline through the stages; prefill chunks ride whole (successive
    chunks already interleave across stages via async dispatch).

    **Speculative serving composes** (``max_spec_tokens > 0``): each stage
    allocates its layers' spec-tree buffers alongside the committed KV,
    and the host-built ``TreeSearchBatchConfig``/``TreeVerifyBatchConfig``
    batches ride the stage chain WHOLE (like prefill chunks) — the
    tree-verify step is just another batch shape hopping the live-cut
    boundary, so :class:`~.spec_infer.SpecInferManager` drives a
    pipelined target with the draft model co-resident on its own devices
    (the dual-allocator accounting the spec manager already does).  The
    on-device ``SpecDecodeScan`` stays single-program (it calls
    ``_step_impl`` directly); spec × pp serves through the host manager.

    Not yet supported here: the on-device prefill scan — it needs the
    single-program pipelining this multi-program design trades away;
    chunked prefill covers the prompt phase instead.
    """

    # shared with RequestManager like InferenceManager.telemetry; stage
    # dispatches land on per-stage trace tracks ("stage0", "stage1", ...)
    # so a Perfetto export shows the micro-batch interleave per stage
    telemetry = NULL_TELEMETRY
    # seeded chaos hook (serve/resilience.py), synced by the RequestManager.
    # Consulted before every stage dispatch AND every inter-stage hop —
    # faults raise before device work, and retrying a whole macro-step is
    # safe because stage KV writes are positional and value-deterministic
    # (a replayed micro-batch rewrites identical values; see _dispatch).
    fault_injector = None
    # step-level cost attribution (obs/profiler.py), synced by the
    # RequestManager: per-stage dispatch phases (``stage{i}``) time the
    # host-interleaved stage compute, ``hop`` times the inter-stage
    # activation transfer, and every stage program launch counts into the
    # deterministic ``dispatches`` counter.  Host-side only.
    profiler = NULL_PROFILER

    def __init__(
        self,
        model,
        max_requests: int = 8,
        max_tokens_per_batch: int = 64,
        max_seq_len: int = 512,
        n_micro: Optional[int] = None,
        strategy: Optional[Dict[str, Dict]] = None,
        outputs=None,
        use_pallas: str = "auto",
        kv_dtype: Optional[str] = None,
        gate_lm_head: bool = True,
        topk: int = 0,
        kv_page_size: Optional[int] = None,
        max_spec_tokens: int = 0,
    ):
        from ..parallel.mesh import make_mesh

        self.model = model
        self.max_requests = max_requests
        self.max_tokens = max_tokens_per_batch
        self.max_seq_len = max_seq_len
        self.max_spec_tokens = max_spec_tokens
        self.topk = topk
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                             "(expected None or 'int8')")
        self.kv_dtype = kv_dtype
        mesh = model.mesh
        if mesh is None or "pp" not in mesh.shape:
            raise ValueError("PipelinedInferenceManager needs a mesh with a "
                             "'pp' axis (use InferenceManager for pure TP)")
        shape = dict(mesh.shape)
        pp = shape["pp"]
        tp = shape.get("tp", 1)
        for a, n in shape.items():
            if a not in ("pp", "tp") and n > 1:
                raise ValueError(f"unsupported serve mesh axis {a!r}")
        self.pp = pp
        self.tp = tp
        self.n_micro = int(n_micro) if n_micro else pp
        if self.max_tokens % self.n_micro:
            # micro-batches are contiguous EQUAL token ranges of the
            # compiled capacity, so the count must divide it; fall back to
            # the largest divisor and say so rather than silently running
            # the bubble-dominated schedule the caller asked to avoid
            import warnings

            fixed = max(d for d in range(1, self.n_micro + 1)
                        if self.max_tokens % d == 0)
            warnings.warn(
                f"n_micro={self.n_micro} does not divide "
                f"max_tokens_per_batch={self.max_tokens}; using "
                f"n_micro={fixed}", stacklevel=2)
            self.n_micro = fixed

        refuse_unsupported_slot_state(model.graph, pipelined=True)
        register_serve_capacities(model.graph, max_requests, max_seq_len,
                                  max_spec_tokens, kv_dtype)
        if outputs is None:
            out_tids = [model.graph.nodes[-1].outputs[-1]]
        else:
            outputs = outputs if isinstance(outputs, (list, tuple)) \
                else [outputs]
            out_tids = [t.tid for t in outputs]
        self._gate_lm_head = bool(gate_lm_head)
        self._lm_head_marked = (mark_gated_lm_head(
            model.graph, out_tids, max_requests) if gate_lm_head else False)

        # ---- stage meshes: pp-major device slices, tp within a slice ----
        names = list(mesh.axis_names)
        arr = np.asarray(mesh.devices)
        perm = [names.index("pp")] + [i for i, n in enumerate(names)
                                     if n != "pp"]
        arr = arr.transpose(perm).reshape(pp, -1)
        self.stage_meshes = [make_mesh({"tp": tp}, list(arr[s]))
                             for s in range(pp)]
        if strategy is None:
            strategy = tensor_parallel_strategy(
                model.graph, ("tp",), self.stage_meshes[0]) if tp > 1 else {}
        self.strategy = strategy

        split = serve_stage_split(model.graph, pp, out_tids[0])
        plans = build_stage_plans(model.graph, split, strategy,
                                  self.stage_meshes)
        self.stages = [
            _Stage(nodes, entry, exit_, m, plan)
            for (nodes, entry, exit_), m, plan
            in zip(split, self.stage_meshes, plans)
        ]
        self.stage_plans = plans
        self._token_tid = model.graph.input_tids[0]
        # per-stage KVAllocator instances under one deployment-level front:
        # each stage owns ITS caches (always_place — per-stage KV residency
        # is the capacity contract), while admission/preemption/the memory
        # ledger consult the composed allocator exactly like the
        # single-plan manager's.
        stage_kvs = [
            StageKV(stage.nodes, strategy, stage.mesh, max_requests,
                    max_seq_len, max_spec_tokens, always_place=True,
                    label=f"stage{s}")
            for s, stage in enumerate(self.stages)
        ]
        for stage, skv in zip(self.stages, stage_kvs):
            stage.kv = skv
        # paged KV under pp: every stage's buffers share one ROW x SEQ
        # geometry, so ONE logical block table addresses all the per-stage
        # page pools simultaneously — a page id names the same (row,
        # seq-range) in every stage's k/v (+ scale) planes, and a COW copy
        # runs across all of them (kv_paged._copy_page iterates stages).
        self.kv_page_size = kv_page_size
        if kv_page_size:
            from .kv_paged import PagedKVAllocator

            self.kv = PagedKVAllocator(stage_kvs, max_requests, max_seq_len,
                                       page_size=kv_page_size)
        else:
            self.kv = KVAllocator(stage_kvs, max_requests, max_seq_len)

        backend = jax.default_backend()
        self.use_pallas = (backend == "tpu") if use_pallas == "auto" \
            else bool(use_pallas)
        self.pallas_interpret = backend != "tpu"
        self.prefill_tile = pick_prefill_tile(max_tokens_per_batch,
                                              max_seq_len)
        if kv_page_size:
            from .kv_paged import validate_page_tile

            validate_page_tile(kv_page_size, self.prefill_tile)
        self.tree_token_layout = None
        self.prefill_overlap = False  # single-program lever; N/A here

        from ..utils.platform import collective_safe_compiler_options

        n_stages = len(self.stages)
        for s, stage in enumerate(self.stages):
            stage.step = jax.jit(
                self._make_stage_impl(stage, last=(s == n_stages - 1)),
                donate_argnums=(1,),
                compiler_options=collective_safe_compiler_options(stage.mesh),
            )
        last_mesh = self.stages[-1].mesh
        self._advance = jax.jit(
            self._advance_impl, static_argnames=("eos",),
            compiler_options=collective_safe_compiler_options(last_mesh),
        )
        # mid-stretch slot join (on-device continuous batching): a tiny
        # program on the last stage's mesh that activates one batch row
        # between chained scan segments
        self._join = jax.jit(
            self._join_impl, static_argnames=("eos",),
            compiler_options=collective_safe_compiler_options(last_mesh),
        )

    # ------------------------------------------------------------------
    @property
    def gate_lm_head(self) -> bool:
        return self._gate_lm_head and self._lm_head_marked

    @gate_lm_head.setter
    def gate_lm_head(self, value) -> None:
        self._gate_lm_head = bool(value)

    @property
    def params(self):
        """Merged per-node param dict across stages (shared sub-dicts, so
        in-place updates — e.g. ``quantize_int8`` — reach the stages)."""
        if self.stages[0].params is None:
            return None
        merged: Dict[str, Dict] = {}
        for stage in self.stages:
            merged.update(stage.params)
        return merged

    @property
    def state(self):
        """Merged per-node KV state across stages (read-only convenience for
        tests/diagnostics; the live buffers are per stage)."""
        if self.stages[0].state is None:
            return None
        merged: Dict[str, Dict] = {}
        for stage in self.stages:
            merged.update(stage.state)
        return merged

    # ------------------------------------------------------------------
    def _make_stage_impl(self, stage, last: bool):
        fwd = stage.fwd
        entry = tuple(stage.entry_tids)
        token_tid = self._token_tid

        def impl(params, state, bc, xs, sample=None, pages=None):
            base = bc if isinstance(bc, BatchConfig) else bc.base
            if entry == (token_tid,):
                inputs = {token_tid: base.tokens}
            else:
                inputs = dict(zip(entry, xs))
            outs, new_state = fwd(
                params, inputs, state=state,
                extras={
                    "batch_config": bc,
                    "pallas_decode": self.use_pallas,
                    "pallas_interpret": self.pallas_interpret,
                    "tree_layout": None,
                    "qkv0": None,
                    "pages": pages,
                },
            )
            if not last:
                return tuple(outs), new_state
            logits = outs[0].astype(jnp.float32)
            if sample is not None:
                token_ids = sample_tokens(logits, sample)
            else:
                token_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits_max = jnp.max(logits, axis=-1)
            topk_ids = topk_lp = None
            if self.topk:
                lp = jax.nn.log_softmax(logits, axis=-1)
                topk_lp, topk_ids = jax.lax.top_k(lp, self.topk)
                topk_ids = topk_ids.astype(jnp.int32)
            return (
                InferenceResult(token_ids, logits_max, topk_ids, topk_lp),
                new_state,
            )

        return impl

    # ------------------------------------------------------------------
    def init_operators_inference(self, params=None, rng=None, dtype=None):
        graph = self.model.graph
        if params is None:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            for stage in self.stages:
                only = {n.name for n in stage.nodes}
                # same global key indices as the single-plan init: weights
                # are bit-identical to the non-pp manager with this seed
                stage.params = init_params(graph, stage.plan, rng,
                                           dtype=dtype, only=only)
        else:
            for stage in self.stages:
                sub = {}
                for node in stage.nodes:
                    g = params.get(node.name)
                    if g is None:
                        continue
                    shs = stage.plan.param_shardings.get(node.name, {})
                    placed = {}
                    for pname, arr in g.items():
                        sh = shs.get(pname)
                        tgt = (sh.named_sharding(stage.mesh) if sh is not None
                               else stage.replicated)
                        placed[pname] = jax.device_put(arr, tgt)
                    sub[node.name] = placed
                stage.params = sub
        self.allocate_kv_cache()
        return self

    def allocate_kv_cache(self):
        # the allocator owns every stage's buffers (always_place was baked
        # into each StageKV at construction — per-stage KV residency is
        # the capacity contract of pp serving)
        self.kv.allocate()
        self.kv.reset_attribution()
        return self.state

    def reset(self):
        self.allocate_kv_cache()

    @property
    def plan_key(self) -> str:
        """Deployment coordinates in the serve search's convention."""
        return f"tp{self.tp}_pp{self.pp}_m{self.n_micro}"

    def publish_memory(self, telemetry, key=None) -> None:
        """Predicted-vs-allocated HBM per component into the handle's
        memory ledger — per-DEVICE basis, the SAME composition on both
        sides: per-component max across stages (each component's worst
        chip; components may bind on different stages, so the per-pair
        ratios stay meaningful even when no single chip holds every max).
        ``static_gb`` (weights + KV, the allocatable share) is composed
        per STAGE first, so it is a real binding chip's number.  See
        :meth:`InferenceManager.publish_memory` (also for ``key``)."""
        if telemetry is None or not getattr(telemetry, "enabled", False):
            return
        from ..obs.memory import publish_predicted_parts
        from ..search.simulator import compose_stage_parts, plan_memory_parts

        key = key or self.plan_key
        publish_predicted_parts(
            telemetry, key,
            compose_stage_parts([plan_memory_parts(p, training=False)
                                 for p in self.stage_plans]))
        if self.stages[0].state is None:
            return
        per_stage = [
            (params_nbytes(stage.params),
             stage.kv.allocated_bytes(kv_only=False, per_device=True))
            for stage in self.stages
        ]
        telemetry.memory_plan_allocated(
            key,
            weights_gb=max(w for w, _ in per_stage) / 1e9,
            kv_gb=max(kv for _, kv in per_stage) / 1e9,
            static_gb=max(w + kv for w, kv in per_stage) / 1e9,
        )

    # ------------------------------------------------------------------
    def _microbatches(self, bc):
        if isinstance(bc, BatchConfig):
            return bc.split_microbatches(self.n_micro)
        return [bc]  # prefill chunks / tree batches ride whole

    def _page_view(self):
        """Device-side block table (None = slot-contiguous); ONE logical
        table shared by every stage's page pool."""
        return self.kv.page_view()

    def _dispatch(self, bc, sample=None, mb: int = 0, pages=None):
        """One micro-batch through the stage chain; returns the last
        stage's InferenceResult (device arrays, not synced).

        Telemetry spans cover the HOST dispatch of each stage (async — the
        jit calls return without syncing; device occupancy needs XProf) on
        per-stage tracks; the inter-stage ``device_put`` hop is an instant
        on the receiving stage's track.
        """
        tel = self.telemetry
        prof = self.profiler
        fi = self.fault_injector
        xs: Tuple = ()
        res = None
        n = len(self.stages)
        for s, stage in enumerate(self.stages):
            # injected faults fire BEFORE the launch span: a launch that
            # never happened is neither timed nor counted as a dispatch
            if fi is not None:
                fi.maybe_fail(f"stage{s}_dispatch")
                if s > 0:
                    fi.maybe_fail(f"stage{s}_hop")
            with tel.span("stage_dispatch", cat="pp", track=f"stage{s}",
                          prof=prof, phase=f"stage{s}", stage=s, mb=mb):
                if s > 0:
                    tel.instant("stage_hop", cat="pp", track=f"stage{s}",
                                stage=s, mb=mb)
                    if tel.enabled:
                        tel.metrics.counter("pp_hops").inc()
                    with tel.span("hop", cat="pp", track="hop", prof=prof,
                                  stage=s, mb=mb):
                        # the whole hop ships as ONE batched transfer —
                        # batch descriptor, page table and boundary
                        # activations in a single pytree device_put (one
                        # async transfer launch) instead of a host call
                        # per operand
                        bc_s, pg_s, xs = jax.device_put(
                            (bc, pages, xs), stage.replicated)
                else:
                    bc_s, pg_s = jax.device_put((bc, pages),
                                                stage.replicated)
                if s < n - 1:
                    xs, stage.state = stage.step(stage.params, stage.state,
                                                 bc_s, xs, None, pg_s)
                else:
                    smp = (jax.device_put(sample, stage.replicated)
                           if sample is not None else None)
                    res, stage.state = stage.step(stage.params, stage.state,
                                                  bc_s, xs, smp, pg_s)
        return res

    @staticmethod
    def _merge_results(results: Sequence[InferenceResult]) -> InferenceResult:
        if len(results) == 1:
            return results[0]
        cat = lambda xs: (None if xs[0] is None
                          else jnp.concatenate(list(xs), axis=0))
        return InferenceResult(
            cat([r.token_ids for r in results]),
            cat([r.logits_max for r in results]),
            cat([r.topk_ids for r in results]),
            cat([r.topk_logprobs for r in results]),
        )

    def step(self, bc, sample=None, counts=None) -> InferenceResult:
        """Run one serving macro-step: ``n_micro`` interleaved micro-batches
        through the stage chain (async dispatch; stage s runs micro-batch j
        while stage s-1 runs j+1).  Caches update in place per stage."""
        assert self.stages[0].params is not None, \
            "call init_operators_inference() first"
        mbs = self._microbatches(bc)
        tel = self.telemetry
        if tel.enabled:
            # steady-state decode bubble of this macro-step's schedule —
            # the model-side fraction the calibration loop compares against
            # measured stage occupancy (XProf) on device runs
            tel.metrics.gauge("pp_bubble_frac").set(
                max(0, self.pp - len(mbs)) / self.pp)
        pv = self._page_view()
        # ``counts``: the caller's launch bookkeeping (see
        # InferenceManager.step) rides the macro-step span
        with tel.span("pp_macro_step", cat="pp", track="pp",
                      n_micro=len(mbs), kind="step", **(counts or {})):
            results = []
            k = self.max_tokens // max(len(mbs), 1)
            for j, mbc in enumerate(mbs):
                smp = sample
                if sample is not None and len(mbs) > 1:
                    if len(sample) > 3:
                        # per-request (rid, token-index) keys: slice the
                        # fold rows to this micro-batch's contiguous token
                        # range — sampled output is then bit-identical to
                        # the single-program step (rows and keys align)
                        key, t, p, folds = sample
                        smp = (key, t, p, folds[j * k: (j + 1) * k])
                    else:
                        # per-micro-batch key: same sampling distribution
                        # as the single-program step, different bitstream
                        key, t, p = sample
                        smp = (jax.random.fold_in(key, j), t, p)
                results.append(self._dispatch(mbc, smp, mb=j, pages=pv))
        return self._merge_results(results)

    # ------------------------------------------------------------------
    @staticmethod
    def _advance_impl(bc, toks, alive, eos_hit, step_i, allowed, eos):
        """The decode-scan body's advance/lifecycle logic (see
        InferenceManager._decode_scan_impl), jitted on the last stage's
        mesh so multi-step decode never syncs the host.

        ``eos_hit`` carries which rows exited via EOS (vs exhausting
        their ``allowed`` budget) for the per-row exit codes; ``allowed``
        (i32 per flat row, or None) freezes each row after ITS budget —
        rows of unequal remaining budgets ride one chained stretch.
        ``step_i`` is the current step's index within the segment (device
        scalar, so one compiled program serves every step)."""
        live = alive
        if eos is not None:
            hit = alive & (toks == eos)
            eos_hit = eos_hit | hit
            alive = alive & ~hit
        if allowed is not None:
            alive = alive & (step_i + 1 < allowed)
        nxt = bc.advance(toks)
        if eos is not None or allowed is not None:
            nxt = BatchConfig(
                tokens=nxt.tokens,
                request_index=jnp.where(alive, nxt.request_index, -1),
                token_position=nxt.token_position,
                num_tokens=nxt.num_tokens,
                seq_lens=nxt.seq_lens,
            )
        return nxt, alive, eos_hit, live

    @staticmethod
    def _join_impl(bc, tok_src, src_idx, dst, slot, pos, seq_len,
                   num_tokens, eos):
        """Activate one batch row from a staged arrival's held prefill
        result (see InferenceManager._join_impl): the row joins pre-frozen
        when the held token already IS the terminator."""
        tok = tok_src[src_idx]
        active = True if eos is None else tok != eos
        return bc.join_row(dst, tok, slot, pos, seq_len, num_tokens,
                           active=active)

    def join_slot(self, bc, tok_src, src_idx, dst, slot, pos, seq_len,
                  num_tokens, eos=None, counts=None):
        """Splice a mid-stretch arrival into the running (device-resident)
        batch — same contract as InferenceManager.join_slot; the join
        program runs on the last stage's mesh, where the chained scan's
        BatchConfig lives."""
        with self.telemetry.span("join_dispatch", cat="dispatch",
                                 track="dispatch", prof=self.profiler,
                                 phase="dispatch", kind="join", n_steps=1,
                                 **(counts or {})):
            return self._join(
                bc, tok_src, jnp.int32(src_idx), jnp.int32(dst),
                jnp.int32(slot), jnp.int32(pos), jnp.int32(seq_len),
                jnp.int32(num_tokens), eos=eos)

    def decode_scan(self, bc, n_steps: int, eos: Optional[int] = None,
                    sample=None, counts=None):
        """:meth:`decode_scan_async` read back, for a caller with no host
        bookkeeping of ``bc`` (the top position is read off the batch
        here) and no budgets: host ``(tokens, live)`` and the advanced
        BatchConfig.

        A token means something only where ``live`` — the one contract of
        both managers' scans.  This one runs all ``max_tokens`` rows and
        returns the padding rows' argmax; InferenceManager's runs one row
        per slot and returns 0 on the rows it did not run.
        """
        tokens, live, _, bc = self.decode_scan_async(
            bc, n_steps, eos=eos, sample=sample,
            max_position=int(np.max(np.asarray(bc.token_position))),
            counts=counts)
        return np.asarray(tokens), np.asarray(live), bc

    def decode_scan_async(self, bc, n_steps: int, eos: Optional[int] = None,
                          sample=None, allowed=None, max_position=None,
                          counts=None):
        """``n_steps`` pure-decode macro-steps, host-dispatched but never
        host-synced: each micro-batch's next BatchConfig derives on device
        (``_advance_impl``) and flows back to stage 0, and micro-batches
        interleave across stages step by step (i-major dispatch order).
        Returns LAZY device values — ``(tokens [n, max_tokens], live
        masks, per-row exit codes, advanced BatchConfig)`` — so a chained
        stretch dispatches segment after segment (pp hops included,
        device-to-device) and reads everything back once at stretch end.

        ``allowed`` (i32 per flat row, or None) is each row's step budget
        for THIS segment: the advance freezes a row after its budget, and
        the exit codes report EXIT_EOS vs EXIT_BUDGET vs EXIT_RUNNING per
        row (EXIT_NOT_IN_BATCH for pad/frozen-at-entry rows).

        ``max_position`` is REQUIRED: the host-known largest starting
        token position across rows.  ``decode_scan`` reads it from the
        batch with ``np.max`` — a host sync the chained path cannot
        afford on a device-resident mid-stretch BatchConfig.
        """
        assert self.stages[0].params is not None, \
            "call init_operators_inference() first"
        assert max_position is not None, \
            "decode_scan_async needs the host-tracked max_position"
        last = max_position + n_steps
        if last > self.max_seq_len:
            raise ValueError(
                f"decode_scan would reach position {last} > max_seq_len "
                f"{self.max_seq_len}")
        fi = self.fault_injector
        if fi is not None:
            fi.maybe_fail("decode_scan")
        mbs = self._microbatches(bc)
        m = len(mbs)
        rep = self.stages[-1].replicated
        mbs = [jax.device_put(mb, rep) for mb in mbs]
        k = self.max_tokens // m
        alw = [None] * m
        if allowed is not None:
            alw_full = jax.device_put(jnp.asarray(allowed, jnp.int32), rep)
            alw = [alw_full[j * k: (j + 1) * k] for j in range(m)]
        # present BEFORE the entry freeze: a present row whose budget is
        # already 0 exits as EXIT_BUDGET, not EXIT_NOT_IN_BATCH
        present0 = [mb.request_index >= 0 for mb in mbs]
        if allowed is not None:
            # entry freeze: a present row with no budget must not write
            # its step-0 KV (the frozen row's writes land in scratch)
            mbs = [BatchConfig(
                tokens=mb.tokens,
                request_index=jnp.where(a > 0, mb.request_index, -1),
                token_position=mb.token_position,
                num_tokens=mb.num_tokens,
                seq_lens=mb.seq_lens,
            ) for mb, a in zip(mbs, alw)]
        alive = [mb.request_index >= 0 for mb in mbs]
        eos_hit = [jnp.zeros_like(a) for a in alive]
        toks = [[None] * m for _ in range(n_steps)]
        lives = [[None] * m for _ in range(n_steps)]
        tel = self.telemetry
        if tel.enabled:
            tel.metrics.gauge("pp_bubble_frac").set(
                max(0, self.pp - m) / self.pp)
        pv = self._page_view()
        for i in range(n_steps):
            with tel.span("pp_decode_macro_step", cat="pp", track="pp",
                          step=i, n_micro=m, kind="decode_scan",
                          **(counts or {})):
                for j in range(m):
                    smp = None
                    if sample is not None:
                        if len(sample) > 3:
                            key, t, p, folds = sample
                            f = folds[j * k: (j + 1) * k]
                            smp = (key, t, p,
                                   f + jnp.array([0, i], jnp.int32))
                        else:
                            key, t, p = sample
                            smp = (jax.random.fold_in(key, i * m + j), t, p)
                    res = self._dispatch(mbs[j], smp, mb=j, pages=pv)
                    mbs[j], alive[j], eos_hit[j], live = self._advance(
                        mbs[j], res.token_ids, alive[j], eos_hit[j],
                        jnp.int32(i), alw[j], eos=eos)
                    toks[i][j] = res.token_ids
                    lives[i][j] = live
        cat = (lambda xs: xs[0]) if m == 1 else jnp.concatenate
        tokens = jnp.stack([cat(row) for row in toks])
        live_out = jnp.stack([cat(row) for row in lives])
        ecode = cat([
            jnp.where(~present0[j], EXIT_NOT_IN_BATCH,
                      jnp.where(eos_hit[j], EXIT_EOS,
                                jnp.where(alive[j], EXIT_RUNNING,
                                          EXIT_BUDGET))).astype(jnp.int32)
            for j in range(m)])
        return tokens, live_out, ecode, self._merge_bcs(mbs)

    @staticmethod
    def _merge_bcs(mbs: Sequence[BatchConfig]) -> BatchConfig:
        if len(mbs) == 1:
            return mbs[0]
        seq = mbs[0].seq_lens
        for mb in mbs[1:]:
            # each micro-batch advanced only its own slots' depths
            seq = jnp.maximum(seq, mb.seq_lens)
        return BatchConfig(
            tokens=jnp.concatenate([mb.tokens for mb in mbs]),
            request_index=jnp.concatenate([mb.request_index for mb in mbs]),
            token_position=jnp.concatenate(
                [mb.token_position for mb in mbs]),
            num_tokens=sum(mb.num_tokens for mb in mbs),
            seq_lens=seq,
        )

    # ------------------------------------------------------------------
    def stage_memory_bytes(self, training: bool = False) -> List[float]:
        """Per-stage ``plan_memory_bytes`` — the capacity arithmetic the
        serve search gates pp admissibility with (weights + KV + largest
        transient, per device of each stage)."""
        from ..search.simulator import plan_memory_bytes

        return [plan_memory_bytes(p, training=training)
                for p in self.stage_plans]
