"""InferenceManager: compile a serve PCG and run per-step inference.

Reference: ``src/runtime/inference_manager.cc`` —
``compile_model_and_allocate_buffer`` (placement + activation/KV buffers) and
``inference()`` (per-layer dispatch).  Here compilation is: plan the PCG with
a tensor-parallel strategy, allocate the per-attention-op KV caches as sharded
device arrays, and jit ONE step function per batch-config type (incremental /
tree-search / tree-verify — jax caches the compilation per pytree structure,
the analogue of the reference's three task variants).  Caches are donated so
the update is in-place in HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.interpreter import build_forward
from ..core.pcg import PCG
from ..obs.profiler import NULL_PROFILER
from ..obs.telemetry import NULL_TELEMETRY
from ..utils.platform import with_stack_room
from .batch_config import BatchConfig, InferenceResult
from .kv_allocator import (  # noqa: F401 — re-exported for compat
    KVAllocator,
    StageKV,
    allocate_attention_state,
)
from .ops import IncMultiHeadSelfAttention

# Per-slot exit codes a decode scan carries in its state and returns with
# the stretch's single readback (devices decide WHY a row stopped; the
# host only reads the verdict).  Shared by InferenceManager.decode_scan*,
# the pipeline-parallel manager, and SpecDecodeScan.
EXIT_NOT_IN_BATCH = -1  # padding / row frozen before this scan began
EXIT_RUNNING = 0        # budget left and no EOS: resume next segment
EXIT_EOS = 1            # emitted the stop token mid-scan (frozen since)
EXIT_BUDGET = 2         # consumed its max_new_tokens budget in this scan


def tensor_parallel_strategy(
    graph, tp_axes: Tuple[str, ...] = ("tp",), mesh=None
):
    """Megatron-style serve strategy: attention sharded over kv-head groups,
    MLP column→row parallel, LM head vocab-column sharded.

    The analogue of the reference's default TP MachineView assignment for
    serve graphs (``InferenceManager::compile_model_and_allocate_buffer``'s
    tensor-parallel placement).  Unity search can replace this wholesale.
    Dims not divisible by the TP degree are left unsharded (replicated).
    """
    degree = 1
    if mesh is not None:
        for a in tp_axes:
            degree *= dict(mesh.shape)[a]

    strategy: Dict[str, Dict] = {}
    for node in graph.nodes:
        t = node.op.type_name
        op = node.op
        if t in (
            "inc_multihead_self_attention",
            "spec_inc_multihead_self_attention",
            "tree_inc_multihead_self_attention",
        ):
            if op.num_kv_heads % degree == 0:
                strategy[node.name] = {"head": tp_axes}
        elif t == "linear":
            n = node.name
            if "gate_proj" in n or "up_proj" in n or "fc1" in n or "c_fc" in n:
                if op.out_dim % degree == 0:
                    strategy[n] = {"channel_out": tp_axes}
            elif "down_proj" in n or "fc2" in n or "c_proj" in n:
                if op.in_dim and op.in_dim % degree == 0:
                    strategy[n] = {"channel_in": tp_axes}
            elif op.out_dim % degree == 0:
                strategy[n] = {"channel_out": tp_axes}
    return strategy


def _default_calibration(mesh):
    """(machine_model, cost_cache_or_None) from the repo's calibration
    artifacts.

    A search priced by spec-sheet constants alone is uncapped and
    uncalibrated; the serve path must not run on bare spec-sheet
    defaults with no memory cap when the same artifacts are sitting on disk
    (VERDICT r4 #5).  The spec is keyed by the mesh devices'
    ``device_kind`` (an unknown kind is an error, not a default); the
    measured constants and op-cost cache are v5e's and apply to that spec
    only (their absolute times would mis-scale any other, the cpu test
    spec included).  Missing artifacts leave the spec-sheet defaults.
    """
    import os

    from ..search.machine_model import MachineModel
    from ..search.measure import CostCache

    art = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "..", "artifacts",
    )
    mm = MachineModel.for_mesh(mesh)
    costs = None
    if mm.spec.name == "v5e":
        mm = mm.with_calibration(os.path.join(art, "tpu_calib_v5e.json"))
        cpath = os.path.join(art, "tpu_costs_v5e.json")
        if os.path.exists(cpath):
            costs = CostCache(cpath)
    return mm, costs


def searched_serve_strategy(model, budget: int = 300, seed: int = 0,
                            measured=None, memory_limit=None, machine=None):
    """Unity search over a SERVE graph (VERDICT r3 #5).

    The reference searches placements for inference graphs too
    (``InferenceManager::compile_model_and_allocate_buffer`` consults the
    same Unity optimizer as training); here ``graph_optimize`` runs with
    ``training=False`` — no backward factor, no grad all-reduce, inference
    activation accounting — and the memory model counts the KV/spec buffers
    the attention ops registered (``cost_max_requests``/``cost_seq_len``/
    ``cost_max_spec``), sharded by each candidate's own head-axis config.
    Call AFTER the serve capacities are known (InferenceManager does this
    in ``__init__`` via ``strategy="search"``).

    CALIBRATED BY DEFAULT (VERDICT r4 #5): when ``machine``/``measured``/
    ``memory_limit`` are not given, the repo's measured calibration
    artifacts are loaded and the per-chip HBM capacity becomes the memory
    cap (``MachineModel.with_calibration`` + the cost cache, ROADMAP C6).
    """
    from ..search.search import graph_optimize

    if machine is None:
        machine, costs = _default_calibration(model.mesh)
        if measured is None:
            measured = costs
    if memory_limit is None:
        memory_limit = machine.spec.hbm_capacity
    return graph_optimize(
        model.graph, model.mesh, budget=budget, seed=seed,
        training=False, measured=measured, memory_limit=memory_limit,
        machine=machine,
    )


def register_serve_capacities(graph, max_requests, max_seq_len,
                              max_spec_tokens=0, kv_dtype=None,
                              max_tokens=None):
    """Record the serving capacities + KV dtype on a serve graph's attention
    ops so planning (``plan_memory_bytes``), the serve search, and the cache
    allocator all see the deployment's real buffer shapes.  Shared by the
    single-plan :class:`InferenceManager` and the stage-split
    :class:`~flexflow_tpu.serve.pp.PipelinedInferenceManager`."""
    for node in graph.nodes:
        if isinstance(node.op, IncMultiHeadSelfAttention):
            node.op.cost_seq_len = max_seq_len
            node.op.cost_max_requests = max_requests
            node.op.cost_max_spec = max_spec_tokens
            node.op.kv_dtype = kv_dtype
        elif getattr(node.op, "slot_state", False):
            # window rings and recurrent state (serve/hybrid_ops.py): sized
            # by the slots and, for a ring, by the widest step that writes it
            node.op.cost_seq_len = max_seq_len
            node.op.cost_max_requests = max_requests
            node.op.cost_max_tokens = max_tokens or max_seq_len


# what each deployment option lacks for ANY per-slot state
# (:func:`refuse_unsupported_slot_state`); an op's own ``refusals`` add to it
_SLOT_STATE_LACKS = {
    "kv_page_size": (
        "kv_page_size: a page table for a ring that wraps (a differential or "
        "a plain window layer's: a page would hold positions a ring apart) "
        "or a cache that compacts (pages assume one entry a position), pages "
        "for an index of compressed keys beside a cache, and copy-on-write "
        "of recurrent or matrix state (linear attention's, or a state-space "
        "scan's per head) or of an open window at a shared prefix's end"),
    "kv_dtype": (
        "kv_dtype='int8': quantise-on-write of the window ring (the kernels' "
        "ring paths take no scale planes), of the cache the cross-attention "
        "layers read, of a compacting cache's summaries and of a cache whose "
        "compressed keys choose what is read; a graph that keeps plain K/V "
        "planes in a few layers beside float32 matrix state in the rest has "
        "no reading of 'int8' for the state"),
    "max_spec_tokens": (
        "speculation: a recurrent or matrix state, a closed window or an "
        "appended index entry cannot be rolled back over rejected tokens "
        "without a snapshot per tree node"),
    "tp": (
        "tp > 1: a sharding rule for the conv, the scan, the differential "
        "attention's head pairs, a plain ring's K/V groups (its state is "
        "replicated), the per-head summaries, a selection per K/V head on "
        "fewer K/V heads than chips and a matrix state per head"),
    "pipelined": (
        "pp > 1 (the pipelined manager, at any number of stages): the "
        "exported scan output and the shared cache cross stage boundaries, "
        "and its per-stage state hand-over knows full-length K/V planes only"),
}


def refuse_unsupported_slot_state(graph, *, kv_dtype=None, kv_page_size=None,
                                 max_spec_tokens=0, tp=1,
                                 pipelined=False) -> None:
    """A graph that keeps window rings, recurrent or matrix state, a
    compacting cache or an index beside its cache per slot (``slot_state`` ops: serve/hybrid_ops.py) runs
    slot-contiguous, in its compute dtype, one token a step, on one chip.
    Each other deployment option needs something that is not written yet; it
    is refused here, at compile, by what is missing — none silently takes
    another path.  An op whose state lacks more than every such state does
    says so itself (``refusals``: the clause it adds, by option;
    ``refusal_order`` places it among other ops')."""
    kinds = sorted({type(n.op).__name__ for n in graph.nodes
                    if getattr(n.op, "slot_state", False)})
    if not kinds:
        return
    own = sorted({type(n.op) for n in graph.nodes
                  if getattr(n.op, "refusals", None)},
                 key=lambda cls: cls.refusal_order)
    asked = {"kv_page_size": kv_page_size, "kv_dtype": kv_dtype == "int8",
             "max_spec_tokens": max_spec_tokens, "tp": tp > 1,
             "pipelined": pipelined}
    missing = [lacks + "".join(cls.refusals.get(option, "") for cls in own)
               for option, lacks in _SLOT_STATE_LACKS.items() if asked[option]]
    if missing:
        raise ValueError(
            f"this graph keeps per-slot state in {kinds}, which cannot be "
            "combined with " + "; ".join(missing))


def mark_gated_lm_head(graph, out_tids, max_requests) -> bool:
    """Mark the logits-producing Linear for LM-head gating (single-output
    graphs only).  Returns whether a Linear was actually marked — the guard
    the ``gate_lm_head`` property ANDs in (see InferenceManager.__init__)."""
    if len(out_tids) != 1:
        return False
    from ..ops.linear import Linear

    marked = False
    for node in graph.nodes:
        if out_tids[0] in node.outputs and isinstance(node.op, Linear):
            node.op.lm_head_gated = True
            node.op.cost_logit_rows = max_requests
            marked = True
    return marked


def pick_prefill_tile(max_tokens_per_batch: int, max_seq_len: int) -> int:
    """Query-tile width for the Pallas prefill kernel: the largest
    power-of-two divisor of ``max_tokens_per_batch`` capped at 128 that also
    divides ``max_seq_len`` (contract (d) of PrefillBatchConfig — tiled
    segment starts must never clamp against the cache's seq capacity)."""
    tile = 1
    while tile < 128 and max_tokens_per_batch % (tile * 2) == 0:
        tile *= 2
    while tile > 1 and max_seq_len % tile:
        tile //= 2
    return tile


def sample_tokens(logits, sample):
    """Temperature + nucleus (top-p) sampling; exact argmax at T<=0.

    Same math as the ``Sampling`` graph op (ops/reduction.py, reference
    ``src/ops/sampling.cu``) but with DYNAMIC temperature/top_p (traced
    scalars, so one compiled step serves every GenerationConfig) and an
    explicit key threaded from the RequestManager.

    ``sample`` is ``(key, temperature, top_p)`` — one key draws every row,
    so the bits depend on the ROW COUNT of ``logits``: the decode scan,
    which runs on one row per slot, draws with it what a deployment of
    ``max_tokens == max_requests`` draws — or the resilient-serving
    4-tuple ``(key, temperature, top_p, folds)``
    with ``folds`` i32[rows, 2]: row ``i`` draws from
    ``fold_in(fold_in(key, folds[i, 0]), folds[i, 1])``, i.e. a PER-REQUEST
    (rid, token-index) key schedule that is invariant to batch composition
    and preemption-and-recompute (see RequestManager._sample_for).
    """
    key, temperature, top_p = sample[:3]
    folds = sample[3] if len(sample) > 3 else None
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(_):
        lg = logits / jnp.maximum(temperature, 1e-6)
        sorted_lg = jnp.sort(lg, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_lg, cutoff_idx, axis=-1)
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
        if folds is None:
            return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
        keys = jax.vmap(
            lambda f: jax.random.fold_in(jax.random.fold_in(key, f[0]), f[1])
        )(folds)
        return jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)

    return jax.lax.cond(temperature <= 0.0, lambda _: greedy, draw, None)


def _pad_chunks(bcs, sample, n: int):
    """``n`` all-pad chunks shaped as the stacked chunks ``bcs`` (and the
    sample argument that goes with them): no token, every row of no request,
    no logit slot, no sample fold — a stack the prefill scan runs without
    touching a slot, under the program of its length.  Filled on the host
    (a ``jnp.full`` per field and length would be a program each)."""
    def stack(x, fill):
        return None if x is None else jnp.asarray(
            np.full((n,) + x.shape[1:], fill, x.dtype))

    b = bcs.base
    pads = dataclasses.replace(
        bcs,
        base=BatchConfig(tokens=stack(b.tokens, 0),
                         request_index=stack(b.request_index, -1),
                         token_position=stack(b.token_position, 0),
                         num_tokens=stack(b.num_tokens, 0),
                         seq_lens=stack(b.seq_lens, 0)),
        logit_slots=stack(bcs.logit_slots, -1))
    if sample is not None and len(sample) > 3:
        sample = (*sample[:3], stack(sample[3], 0))
    return pads, sample


def _summed_load(load):
    """A scan's stacked per-step expert load summed over its steps (None for
    a graph without routed layers: the program then returns nothing more)."""
    return None if load is None else jnp.sum(load, axis=0)


def decode_scan_width(bc) -> int:
    """Rows the decode scan's body runs on for the batch ``bc``: one per
    request slot.  A pure-decode batch holds at most one live row per
    request, so the scan never needs the flat step's ``max_tokens``
    capacity.  From shapes alone (no sync, usable at trace time), and the
    ONE place the width is worked out: the program, its guard and its
    dispatch span all ask here, about the batch they were handed."""
    return min(bc.max_requests, bc.max_tokens)


class InferenceManager:
    # serving telemetry handle (obs/): host-side dispatch spans only — it
    # is NEVER passed into a jitted program, so attaching a live handle
    # cannot change compiled executables or their outputs.  RequestManager
    # shares its handle here; the class default is the no-op singleton.
    telemetry = NULL_TELEMETRY
    # seeded chaos hook (serve/resilience.py), synced by the RequestManager
    # like the telemetry handle.  Consulted at each dispatch site BEFORE
    # any work reaches the device, so an injected fault leaves no partial
    # device state and a retried dispatch replays identical compute.
    fault_injector = None
    # step-level cost attribution (obs/profiler.py), synced by the
    # RequestManager like the telemetry handle: dispatch-phase timing +
    # the dispatch counter live HERE (the program-launch sites); the
    # deterministic flops/byte accounting lives in the RequestManager
    # (host bookkeeping).  Host-side only — never traced into a program.
    profiler = NULL_PROFILER
    # the scheduler's tick journal (obs/journal.py), synced by the
    # RequestManager like the two handles above: the launch spans' self
    # time and arguments go into the open tick's record.  None without a
    # manager (direct ``step`` calls)
    journal = None

    def __init__(
        self,
        model,
        max_requests: int = 8,
        max_tokens_per_batch: int = 64,
        max_seq_len: int = 512,
        max_spec_tokens: int = 0,
        strategy: Optional[Dict[str, Dict]] = None,
        tp_axes: Optional[Tuple[str, ...]] = None,
        topk: int = 0,
        outputs=None,
        use_pallas: str = "auto",
        kv_dtype: Optional[str] = None,
        gate_lm_head: bool = True,
        prefill_overlap: bool = True,
        kv_page_size: Optional[int] = None,
    ):
        """``model`` is an FFModel whose graph was built by a serve builder.

        ``outputs``: the logits Tensor(s); defaults to the last node's last
        output (the LM head) — serve graphs can have dangling intermediate
        tensors (e.g. the unused residual sum of the final fused norm).

        ``kv_dtype``: KV-cache storage dtype.  ``"int8"`` stores the
        committed k/v caches as int8 with per-(row, head, position) f32
        scales (quantize-on-write, dequant fused into the Pallas attention
        kernels) — halving decode KV bandwidth vs bf16 and the capacity
        term that gates full-depth models; None (default) keeps the model's
        compute dtype.  Registered on the attention ops BEFORE planning, so
        ``plan_memory_bytes`` / the serve search see the quantized cache
        footprint.

        ``gate_lm_head``: mark the logits-producing Linear for LM-head
        gating — prefill chunks built by the RequestManager then compute
        logits only at each request's last prompt token (gather-then-GEMM
        over <= max_requests rows) instead of all chunk positions.  The
        flag is read at BATCH-BUILD time (it decides whether
        PrefillBatchConfigs carry ``logit_slots``), so it can be toggled
        between calls for ablation; decode/mixed/hand-built batches are
        never gated.

        ``kv_page_size``: enable the PAGED KV cache (serve/kv_paged.py):
        the same physical buffers are carved into fixed pages of this many
        tokens, managed through a per-request block table with refcounted
        copy-on-write prefix sharing — no fragmentation at high occupancy,
        shared system prompts prefilled once.  Must divide ``max_seq_len``
        AND its 128-lane pad (asserted at allocator construction) and be a
        multiple of the prefill tile (asserted here).  None (default)
        keeps the slot-contiguous allocator; both paths are bit-identical
        (tests/test_kv_paged.py).  Writes require mapped pages: the
        RequestManager prepares them before every dispatch
        (``_kv_prepare``); callers driving ``step``/``decode_scan``
        directly must call ``kv.bind(rid, slot=...)`` +
        ``kv.prepare_write(rid, lo, hi)`` themselves — an unprepared
        write lands in the scratch page (pad-token semantics), not an
        error.

        ``prefill_overlap``: software-pipeline the prefill scan — chunk
        i+1's embedding→norm→layer-0 QKV projection is issued inside chunk
        i's scan step (carried across the ``lax.scan`` boundary), giving
        XLA's scheduler a cross-iteration target to overlap with chunk i's
        attention/MLP tail.  Auto-disabled when the graph's prologue isn't
        the recognized embedding→rms_norm→attention chain (OPT's position
        embedding, falcon's parallel blocks ride the plain scan).  Read
        per prefill_scan call (static jit arg), so it too ablates without
        rebuilding.
        """
        self.model = model
        self.max_requests = max_requests
        self.max_tokens = max_tokens_per_batch
        self.max_seq_len = max_seq_len
        self.max_spec_tokens = max_spec_tokens
        self.topk = topk
        if kv_dtype not in (None, "int8"):
            # no silent fp coercion: the caches follow the model's compute
            # dtype unless quantized, so honoring e.g. a float32 request on
            # a bf16 model would need a real mixed-precision cache path —
            # refuse rather than hand back a dtype the caller didn't ask for
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                             "(expected None or 'int8'; fp caches always "
                             "use the model's compute dtype)")
        self.kv_dtype = kv_dtype
        mesh = model.mesh
        if tp_axes is None:
            tp_axes = ("tp",) if mesh is not None and "tp" in mesh.shape else ()
        self.tp_axes = tuple(tp_axes)
        tp = 1
        for a in self.tp_axes:
            tp *= dict(mesh.shape)[a]
        refuse_unsupported_slot_state(
            model.graph, kv_dtype=kv_dtype, kv_page_size=kv_page_size,
            max_spec_tokens=max_spec_tokens, tp=tp)
        # register serve capacities on the attention ops so the search's
        # cost/memory models see the KV + spec buffers (plan_memory_bytes)
        register_serve_capacities(model.graph, max_requests, max_seq_len,
                                  max_spec_tokens, kv_dtype,
                                  max_tokens=max_tokens_per_batch)
        # which path each kind of attention layer took in each program
        # (Pallas kernel or XLA), written by the ops as they are traced:
        # {(layer kind, batch type): path} — a fallback is never unseen; and
        # {("decode_block", (layer kind, batch type)): the seq block
        # ``decode_attention`` planned there} (``ops.note_decode_block``)
        self.attention_paths: Dict[Tuple[str, Any], str] = {}
        self._paths_counted = 0
        # routed-expert layers (ops that leave a load count: ``counts_load``),
        # and per launch dispatched and not yet collected ``(kind, steps,
        # int32[3] on the device: experts visited, pairs, the fullest
        # expert's pairs — summed over steps and layers)``, ``kind`` one of
        # ``EXPERT_LOAD_KEYS`` (a decode scan's steps; a prefill scan's
        # chunks or a flat step that fed prompt rows); the scheduler takes
        # them with its next readback (``take_expert_load``)
        self.expert_layers = sum(
            bool(getattr(n.op, "counts_load", False))
            for n in model.graph.nodes)
        self.expert_load_pending: List[Tuple[str, int, Any]] = []
        if outputs is None:
            out_tids = [model.graph.nodes[-1].outputs[-1]]
        else:
            outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
            out_tids = [t.tid for t in outputs]
        # LM-head gating: mark the logits producer (the final Linear) so
        # prefill chunks carrying ``logit_slots`` compute logits only at
        # sample points.  cost_logit_rows makes the search's cost model
        # price the gated program (Linear.flops) — marked BEFORE the serve
        # search runs, like the KV capacities above.  ``_lm_head_marked``
        # records whether a Linear was actually marked: the public
        # ``gate_lm_head`` property ANDs it in, so flipping the flag True
        # on a graph whose logits producer was never marked (no single
        # Linear output) cannot make the RequestManager build gated
        # batches an unmarked LM head would ignore — slot-indexed sample
        # points against flat-indexed results would corrupt every request.
        self._lm_head_marked = False
        self._gate_lm_head = bool(gate_lm_head)
        if gate_lm_head:
            self._lm_head_marked = mark_gated_lm_head(
                model.graph, out_tids, max_requests)
        if strategy == "search":
            strategy = searched_serve_strategy(model)
        elif strategy is None:
            strategy = tensor_parallel_strategy(model.graph, self.tp_axes, mesh) \
                if self.tp_axes else {}
        self.strategy = strategy
        self.pcg = PCG(model.graph, mesh, strategy, output_tids=out_tids)
        self.plan = self.pcg.plan()
        self._fwd = build_forward(self.plan, mode="spmd")
        self._token_tid = model.graph.input_tids[0]
        self.params = None
        # KV-cache ownership lives in the allocator (serve/kv_allocator.py)
        # — admission control, preemption pricing, and the memory ledger
        # all consult THIS object; ``self.state`` is a delegating property,
        # so the jitted step's donate/re-bind cycle is unchanged.
        # ``kv_page_size`` swaps in the paged allocator behind the same
        # interface (serve/kv_paged.py).
        stage_kv = [StageKV(model.graph.nodes, strategy, self.plan.mesh,
                            max_requests, max_seq_len, max_spec_tokens)]
        self.kv_page_size = kv_page_size
        if kv_page_size:
            from .kv_paged import PagedKVAllocator

            self.kv = PagedKVAllocator(stage_kv, max_requests, max_seq_len,
                                       page_size=kv_page_size)
        else:
            self.kv = KVAllocator(stage_kv, max_requests, max_seq_len)
        # Pallas decode/tree kernels: replace the cache-row-gather attention.
        # "auto" = on for TPU backends; under TP the attention op wraps the
        # kernel in shard_map over the kv-head axis (IncMultiHeadSelfAttention
        # ._head_shard_map) — a sharding it can't express (non-head mesh
        # axes > 1) raises on a TPU backend and takes the gather path off
        # it (CPU tests).  True forces the flag on (interpret mode off-TPU,
        # for tests); False = pure-JAX path.
        # INIT-ONLY: the flags are baked into the jitted step at first trace;
        # mutating the attributes afterwards has no effect.
        backend = jax.default_backend()
        if use_pallas == "auto":
            self.use_pallas = backend == "tpu"
        else:
            self.use_pallas = bool(use_pallas)
        self.pallas_interpret = backend != "tpu"
        # query-tile width for the Pallas prefill kernel: the largest
        # power-of-two divisor of max_tokens, capped at 128.  At the 7B
        # shape (KV=32, D=128) the unchunked tile-128 working set is 17.4 MB
        # against the compiler's 16 MB scoped VMEM; the KV-HEAD-CHUNKED
        # grid axis in ops/pallas/attention.py (_prefill_plan) shrinks the
        # per-grid-step working set until it fits (kv_chunk 16, 256-position
        # blocks there), so the wide tile is admissible: half the grid rows
        # per chunk, half the per-row DMA-wait boundaries.
        # RequestManager builds PrefillBatchConfigs with this tile size for
        # pure-prefill steps.  The tile must also divide max_seq_len
        # (ADVICE r5 medium): the tiled-prefill block write assumes
        # tile-aligned starts never clamp against the cache's seq capacity.
        self.prefill_tile = pick_prefill_tile(max_tokens_per_batch,
                                              max_seq_len)
        if kv_page_size:
            from .kv_paged import validate_page_tile

            validate_page_tile(kv_page_size, self.prefill_tile)
        # fixed tree-token layout (rows, slots) registered by SpecDecodeScan
        # (one per InferenceManager); the layout is PASSED per step by the
        # scan, never applied to host-built tree batches
        self.tree_token_layout: Optional[Tuple[int, int]] = None
        # prefill software pipelining: recognize the embedding -> rms_norm
        # -> attention prologue (llama-family serve graphs) whose layer-0
        # QKV projection can be issued one scan step early.  Graphs with a
        # different prologue (OPT's position embedding, falcon's parallel
        # blocks) keep the plain scan.
        self._overlap_steps = None
        steps = self.plan.steps
        if (prefill_overlap and len(steps) >= 3
                and steps[0].node.op.type_name == "embedding"
                and steps[1].node.op.type_name == "rms_norm"
                and steps[2].node.op.type_name
                == "inc_multihead_self_attention"
                and list(steps[1].in_vids) == list(steps[0].out_vids[:1])
                and list(steps[2].in_vids) == list(steps[1].out_vids[:1])):
            self._overlap_steps = tuple(steps[:3])
            steps[2].node.op.qkv0_consumer = True
        self.prefill_overlap = self._overlap_steps is not None
        # CPU virtual-device meshes get a sequential HLO schedule PER
        # PROGRAM (collective rendezvous deadlock class, VERDICT r4 weak
        # #1 / r5 weak #5) instead of the old process-wide XLA_FLAGS
        # override — single-device programs keep the default scheduler.
        from ..utils.platform import collective_safe_compiler_options

        opts = collective_safe_compiler_options(mesh)
        self._step = jax.jit(self._step_impl, donate_argnums=(1,),
                             compiler_options=opts)
        self._scan = jax.jit(
            self._decode_scan_impl,
            donate_argnums=(1,),
            static_argnames=("n_steps", "eos"),
            compiler_options=opts,
        )
        self._pscan = jax.jit(self._prefill_scan_impl, donate_argnums=(1,),
                              static_argnames=("overlap",),
                              compiler_options=opts)
        # static signature -> the scan lengths run (see prefill_scan)
        self._pscan_lengths: Dict[Tuple, set] = {}
        # mid-stretch slot join (on-device continuous batching): a tiny
        # program that activates one batch row between scan segments
        self._join = jax.jit(self._join_impl, static_argnames=("eos",),
                             compiler_options=opts)

    @property
    def gate_lm_head(self) -> bool:
        """Whether RequestManager-built prefill chunks gate the LM head.

        True only when the flag is on AND a Linear was actually marked at
        construction — the two cannot disagree (see __init__)."""
        return self._gate_lm_head and self._lm_head_marked

    @gate_lm_head.setter
    def gate_lm_head(self, value) -> None:
        self._gate_lm_head = bool(value)

    @property
    def state(self):
        """The KV-cache buffers, owned by the allocator.  The property
        keeps the historical API: the jitted step takes ``self.state``
        (donated) and the result re-binds it, with the allocator as the
        one place the buffers live."""
        return self.kv.state

    @state.setter
    def state(self, value) -> None:
        self.kv.state = value

    @property
    def plan_key(self) -> str:
        """This deployment's coordinates in the serve search's
        ``tp{t}_pp{p}_m{m}`` convention (single-plan: pp=1, m=1)."""
        tp = 1
        mesh = self.plan.mesh
        if mesh is not None:
            shape = dict(mesh.shape)
            for a in self.tp_axes:
                tp *= shape.get(a, 1)
        return f"tp{tp}_pp1_m1"

    # ------------------------------------------------------------------
    def init_operators_inference(self, params=None, rng=None, dtype=None):
        """Initialize params (random if none given) and allocate KV caches.

        Reference: ``InferenceManager::init_operators_inference`` +
        the cache allocation inside each attention op's ``init_task``.
        """
        from ..core.interpreter import init_params

        if params is None:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            params = init_params(self.model.graph, self.plan, rng, dtype=dtype)
        self.params = params
        self.allocate_kv_cache()
        return self

    def allocate_kv_cache(self):
        state = self.kv.allocate()
        self.kv.reset_attribution()
        return state

    def publish_memory(self, telemetry, key: Optional[str] = None) -> None:
        """Record this deployment's predicted-vs-allocated HBM into the
        handle's memory ledger (obs/memory.py): predicted =
        ``plan_memory_parts`` over the compiled plan (the same arithmetic
        the serve search gates with), allocated = the REAL parameter and
        KV-buffer bytes (int8 values+scales and lane padding included).
        ``key`` overrides the ledger plan key — co-resident deployments
        (the spec draft model) must not collide with the target's record
        when both run the same tp/pp shape.  Host-side accounting only;
        no-op for a disabled handle or before the caches are allocated."""
        if telemetry is None or not getattr(telemetry, "enabled", False):
            return
        from ..obs.memory import publish_predicted_parts
        from ..search.simulator import compose_stage_parts, plan_memory_parts

        key = key or self.plan_key
        # static_gb = the statically-allocatable share (weights + KV) —
        # the component the allocated side can actually be compared to;
        # total_gb keeps the transient and stays one-sided (nothing ever
        # "allocates" a transient, so reconciling it would book the
        # activation share as model error)
        publish_predicted_parts(
            telemetry, key,
            compose_stage_parts([plan_memory_parts(self.plan,
                                                   training=False)]))
        if self.state is None:
            return
        from .kv_allocator import params_nbytes

        w = params_nbytes(self.params)
        kv = self.kv.allocated_bytes(kv_only=False, per_device=True)
        # the three kinds of per-slot state apart (bytes ONE slot holds)
        by_kind = {f"slot_{kind}_bytes": b
                   for kind, b in self.kv.bytes_per_slot().items()}
        telemetry.memory_plan_allocated(
            key, weights_gb=w / 1e9, kv_gb=kv / 1e9,
            static_gb=(w + kv) / 1e9, **by_kind,
        )

    # ------------------------------------------------------------------
    def _sample_tokens(self, logits, sample):
        """See module-level :func:`sample_tokens` (shared with the
        pipeline-parallel manager)."""
        return sample_tokens(logits, sample)

    def _step_impl(self, params, state, bc, sample=None, tree_layout=None,
                   qkv0=None, pages=None, one_row_per_request=False):
        # ``tree_layout`` is passed ONLY by SpecDecodeScan, whose verify
        # batches are guaranteed slot-major [R, P]; host-built tree batches
        # (SpecInferManager) have variable layouts and must not take the
        # batched-kernel path.  ``qkv0`` (prefill software pipelining) is
        # this chunk's precomputed layer-0 q/k/v from the scan carry; only
        # the marked qkv0_consumer attention op reads it.  ``pages`` is the
        # paged-KV block table (kv_paged.PageTable) every attention op
        # translates its cache coordinates through; None = slot-contiguous.
        # ``one_row_per_request`` (static; the decode scan passes it): every
        # live row is a request of its own, so an op with recurrent state
        # updates all rows at once instead of scanning them in order.
        # A graph with routed layers collects what they count on the device
        # in this step (``extras["counters"]``: ``{node: int32[3]}``) and
        # returns the sum as the result's ``expert_load``.
        base = bc if isinstance(bc, BatchConfig) else bc.base
        counters = {} if self.expert_layers else None
        outs, new_state = self._fwd(
            params,
            {self._token_tid: base.tokens},
            state=state,
            extras={
                "batch_config": bc,
                "pallas_decode": self.use_pallas,
                "pallas_interpret": self.pallas_interpret,
                "tree_layout": tree_layout
                if not isinstance(bc, BatchConfig) else None,
                "qkv0": qkv0,
                "pages": pages,
                "one_row_per_request": one_row_per_request,
                "attention_paths": self.attention_paths,
                "counters": counters,
            },
        )
        with jax.named_scope("sample"):
            logits = outs[0].astype(jnp.float32)  # [T, vocab]
            if sample is not None:
                token_ids = self._sample_tokens(logits, sample)
            else:
                token_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits_max = jnp.max(logits, axis=-1)
            topk_ids = topk_lp = None
            if self.topk:
                lp = jax.nn.log_softmax(logits, axis=-1)
                topk_lp, topk_ids = jax.lax.top_k(lp, self.topk)
                topk_ids = topk_ids.astype(jnp.int32)
        load = sum(counters.values()) if counters else None
        return (
            InferenceResult(token_ids, logits_max, topk_ids, topk_lp, load),
            new_state,
        )

    def _count_attention_paths(self) -> None:
        """One telemetry counter per (attention layer kind, path) the traced
        programs took — ``attention_path.window_attention.xla`` on a chip is
        a fallback someone should see.  Counts programs, not launches: an
        entry is counted once, when the dispatch that traced it returns."""
        if len(self.attention_paths) == self._paths_counted \
                or not self.telemetry.enabled:
            return
        for (kind, batch), path in list(self.attention_paths.items())[
                self._paths_counted:]:
            self.telemetry.metrics.counter(
                f"attention_path.{kind}.{path}").inc()
        self._paths_counted = len(self.attention_paths)

    def _page_view(self):
        """Current device-side block table (None = slot-contiguous).  Read
        per dispatch — the RequestManager's pre-dispatch ``prepare_write``
        calls may have remapped pages (allocation, COW) since last step."""
        return self.kv.page_view()

    def step(self, bc, sample=None, counts=None) -> InferenceResult:
        """Run one serving step; caches update in place (donated).

        ``sample``: optional ``(key, temperature, top_p)`` — argmax if None.
        ``counts``: what the caller's host bookkeeping knows about this
        launch (``rows``, ``prompt_tokens``, ``ctx_sum``, ...) — ints that
        become arguments of the dispatch span, nothing else.
        """
        assert self.params is not None, "call init_operators_inference() first"
        if self.fault_injector is not None:
            self.fault_injector.maybe_fail("step")
        # span = host dispatch time (the jit call returns without syncing);
        # device time shows up at the result readback, not here.  Dispatch
        # spans live on their own track: they nest inside the serve loop's
        # spans, and per-track totals assume non-overlapping spans per track
        with self.telemetry.span("step_dispatch", cat="dispatch",
                                 track="dispatch", prof=self.profiler,
                                 phase="dispatch", jr=self.journal,
                                 kind="step", n_steps=1,
                                 **(counts or {})):
            result, self.state = with_stack_room(
                self._step, self.params, self.state, bc, sample, None, None,
                self._page_view())
        if result.expert_load is not None and (counts or {}).get(
                "prompt_tokens"):
            # a flat step that fed prompt rows (decode rows ride along)
            self.expert_load_pending.append(
                ("prefill", 1, result.expert_load))
        self._count_attention_paths()
        return result

    # ------------------------------------------------------------------
    def _decode_scan_impl(self, params, state, bc, sample, pages, allowed,
                          n_steps: int, eos: Optional[int]):
        """n_steps pure-decode steps as ONE on-device ``lax.scan``.

        TPU-first redesign of the reference's serving loop (§3.3): instead of
        a host round trip per token (``prepare_next_batch`` → dispatch →
        sync), the next step's BatchConfig is derived on device from the
        step's output (``BatchConfig.advance``) and the host only syncs once
        per scan.  With dispatch latency L and device step time t, TPOT drops
        from ``max(L, t)`` to ``t + L/n_steps``.

        The callers' contract is WIDE — ``bc``, ``allowed``, the sample
        folds and every result are per flat row of a ``max_tokens`` batch,
        the layout ``step`` and ``join_slot`` share — but the scan's body
        runs on ``min(max_requests, max_tokens)`` rows, one per slot: the
        rows with ``request_index >= 0`` (at most one per request, wherever
        they sit) are compacted on device once per call, outside the scan,
        and the results are expanded back to their flat rows at the end.
        So the KV write (``ops.DUS_MAX_TOKENS``), the attention kernel's
        grid, the GEMMs and the LM head see that many rows.  Rows the
        scan did not run read token 0, ``live`` False and
        ``EXIT_NOT_IN_BATCH``; the returned ``bc`` leaves them as they came.

        ``eos`` (static): slots that emit it are FROZEN for the rest of the
        scan — their request_index flips to -1, so later steps write their
        KV to the scratch row and their emissions are masked out of ``live``.

        ``allowed`` (i32[max_tokens] or None): per-flat-row remaining token
        budgets — the device-side ``max_new_tokens`` exit.  A row is frozen
        the same way once it has emitted ``allowed[row]`` tokens, so a
        chained stretch can run rows of UNEQUAL remaining budgets in one
        scan without overshooting any of them.  Per-row exit codes
        (``EXIT_*``) come back with the results: what ended each row —
        still running, EOS, or budget — readable in the stretch's single
        readback, so the host reaps lifecycle outcomes without re-deriving
        them from the token stream.
        """
        wide = bc
        width = decode_scan_width(bc)
        narrowed = width < bc.max_tokens
        if narrowed:
            with jax.named_scope("advance"):
                # stable: the present rows keep their flat order, absent
                # rows fill what is left of the width — no host read of bc
                taken = jnp.argsort(bc.request_index < 0, stable=True)[:width]
                bc = BatchConfig(
                    tokens=bc.tokens[taken],
                    request_index=bc.request_index[taken],
                    token_position=bc.token_position[taken],
                    num_tokens=bc.num_tokens,
                    seq_lens=bc.seq_lens,
                )
                if allowed is not None:
                    allowed = allowed[taken]
                if sample is not None and len(sample) > 3:
                    sample = (*sample[:3], sample[3][taken])
        present = bc.request_index >= 0
        alive0 = present
        if allowed is not None:
            alive0 = alive0 & (allowed > 0)
            # entry freeze: a row that arrives with no budget must not
            # write KV even on step 0 (its writes go to the scratch row)
            bc = BatchConfig(
                tokens=bc.tokens,
                request_index=jnp.where(alive0, bc.request_index, -1),
                token_position=bc.token_position,
                num_tokens=bc.num_tokens,
                seq_lens=bc.seq_lens,
            )

        def body(carry, i):
            state, bc, alive, eos_hit = carry
            stp = None
            if sample is not None:
                if len(sample) > 3:
                    # per-request key schedule: each row's token index
                    # advances one per scan step
                    key, temperature, top_p, folds = sample
                    stp = (key, temperature, top_p, folds.at[:, 1].add(i))
                else:
                    key, temperature, top_p = sample
                    stp = (jax.random.fold_in(key, i), temperature, top_p)
            # the block table is CONSTANT across the scan: the manager's
            # prepare_write pre-mapped (and COW-resolved) every page the
            # n_steps positions can reach before dispatch
            result, state = self._step_impl(params, state, bc, stp,
                                            pages=pages,
                                            one_row_per_request=True)
            toks = result.token_ids
            live = alive  # emission validity for THIS step
            with jax.named_scope("advance"):
                if eos is not None:
                    hit = live & (toks == eos)
                    eos_hit = eos_hit | hit
                    alive = alive & ~hit
                if allowed is not None:
                    alive = alive & (i + 1 < allowed)
                nxt = bc.advance(toks)
                if eos is not None or allowed is not None:
                    nxt = BatchConfig(
                        tokens=nxt.tokens,
                        request_index=jnp.where(alive, nxt.request_index,
                                                -1),
                        token_position=nxt.token_position,
                        num_tokens=nxt.num_tokens,
                        seq_lens=nxt.seq_lens,
                    )
            # with the routed layers' load this step (None, and nothing in
            # the program, for a graph without such layers)
            return (state, nxt, alive, eos_hit), (toks, live,
                                                  result.expert_load)

        eos_hit0 = jnp.zeros_like(alive0)
        (state, bc, alive_end, eos_hit), (tokens, live, load) = jax.lax.scan(
            body, (state, bc, alive0, eos_hit0), jnp.arange(n_steps)
        )
        load = _summed_load(load)
        with jax.named_scope("advance"):
            ecode = jnp.where(
                ~present, EXIT_NOT_IN_BATCH,
                jnp.where(eos_hit, EXIT_EOS,
                          jnp.where(alive_end, EXIT_RUNNING, EXIT_BUDGET)),
            ).astype(jnp.int32)
            if narrowed:
                shape = (n_steps, wide.max_tokens)
                tokens = jnp.zeros(shape, tokens.dtype).at[:, taken].set(
                    jnp.where(present, tokens, 0))
                live = jnp.zeros(shape, live.dtype).at[:, taken].set(live)
                ecode = jnp.full(shape[1:], EXIT_NOT_IN_BATCH,
                                 ecode.dtype).at[taken].set(ecode)
                bc = BatchConfig(
                    tokens=wide.tokens.at[taken].set(bc.tokens),
                    request_index=wide.request_index.at[taken].set(
                        bc.request_index),
                    token_position=wide.token_position.at[taken].set(
                        bc.token_position),
                    num_tokens=bc.num_tokens,
                    seq_lens=bc.seq_lens,
                )
        return tokens, live, ecode, state, bc, load

    def _decode_scan_guards(self, bc, n_steps: int, max_position: int,
                            rows=None) -> int:
        """Shared pre-dispatch validation for the scan paths; returns the
        width the scan of ``bc`` runs at (``decode_scan_width``), for the
        dispatch span.

        ``max_position``: the highest ``token_position`` in the batch as
        HOST bookkeeping (the chained path always knows it — reading it
        off a device-resident ``bc`` would force the mid-stretch sync the
        whole design removes).

        ``rows``: the caller's host count of rows that hold a request (the
        dispatch span's ``rows``), where it has one.  More than the scan's
        width would be cut by the compaction and come back
        ``EXIT_NOT_IN_BATCH`` without a word, so they are refused here."""
        # no bound on the width here: the scan's K/V rows go in by ONE
        # call a layer however many they are (ops.put_rows); where a plane
        # stays on the update-slice chain, put_rows warns past the chain's
        # widest (ops.SCAN_DUS_MAX_ROWS) as the program is traced
        width = decode_scan_width(bc)
        if rows is not None and rows > width:
            raise ValueError(
                f"decode_scan got {rows} rows with a request; a "
                "pure-decode batch holds one row per request, at most "
                f"{width} (BatchConfig.advance)")
        last = int(max_position) + n_steps
        if last > self.max_seq_len:
            raise ValueError(
                f"decode_scan would reach position {last} > max_seq_len "
                f"{self.max_seq_len}; cache writes past the end clamp to the "
                "last slot and silently corrupt it"
            )
        return width

    def decode_scan(self, bc, n_steps: int, eos: Optional[int] = None,
                    sample=None, counts=None):
        """Run ``n_steps`` decode steps on device: :meth:`decode_scan_async`
        for a caller that has no host bookkeeping of ``bc`` — the top
        position and the count of rows with a request are read off the
        batch here — and wants no budgets or exit codes.

        Returns ``(tokens, live, bc)``: i32[n_steps, T] token ids,
        bool[n_steps, T] emission validity (False once a slot passed its
        ``eos``), and the advanced BatchConfig to resume from — all per
        flat row of the ``T = max_tokens`` batch that came in, though the
        scan itself ran on one row per slot (``_decode_scan_impl``); a
        token means something only where ``live``.
        """
        import numpy as np

        rows = int(np.count_nonzero(np.asarray(bc.request_index) >= 0))
        tokens, live, _, bc = self.decode_scan_async(
            bc, n_steps, eos=eos, sample=sample,
            max_position=int(np.max(np.asarray(bc.token_position))),
            counts={**(counts or {}), "rows": rows})
        return tokens, live, bc

    def decode_scan_async(self, bc, n_steps: int, eos: Optional[int] = None,
                          sample=None, allowed=None,
                          max_position: Optional[int] = None, counts=None):
        """One chained-stretch segment: ``n_steps`` decode steps with NO
        readback and NO host-side read of ``bc``.

        The on-device continuous-batching path (request_manager's chained
        ``_decode_stretch``): segments dispatch back-to-back, joins splice
        arrivals in between them (``join_slot``), and the host materializes
        everything in ONE sync at stretch end.  ``allowed`` is the
        per-flat-row remaining-token budget (i32[max_tokens]); rows freeze
        on device when it runs out, so heterogeneous budgets share one
        scan.  It, ``bc``, the sample folds and the results are all in the
        flat step's ``max_tokens`` layout, whatever width the scan runs at
        (``decode_scan_width``).  ``max_position`` is the caller's host
        bookkeeping of the batch's highest token position (required: this
        path must not sync to validate).  Returns LAZY device values
        ``(tokens, live, exit_codes, bc)``.
        """
        assert self.params is not None, "call init_operators_inference() first"
        assert max_position is not None, \
            "decode_scan_async requires host-tracked max_position"
        width = self._decode_scan_guards(
            bc, n_steps, max_position=max_position,
            rows=(counts or {}).get("rows"))
        if self.fault_injector is not None:
            self.fault_injector.maybe_fail("decode_scan")
        with self.telemetry.span("decode_scan_dispatch", cat="dispatch",
                                 track="dispatch", prof=self.profiler,
                                 phase="dispatch", jr=self.journal,
                                 kind="decode_scan",
                                 n_steps=n_steps, width=width,
                                 **(counts or {})):
            tokens, live, ecode, self.state, bc, load = with_stack_room(
                self._scan, self.params, self.state, bc, sample,
                self._page_view(), allowed, n_steps=n_steps, eos=eos)
        if load is not None:
            self.expert_load_pending.append(("scan", n_steps, load))
        if self.telemetry.enabled:
            self.telemetry.metrics.counter("decode_scan_steps").inc(n_steps)
        self._count_attention_paths()
        return tokens, live, ecode, bc

    # ``take_expert_load``'s keys by kind of launch: experts visited, pairs,
    # the fullest expert's pairs, and steps (chunks) x routed layers
    EXPERT_LOAD_KEYS = {
        "scan": ("experts_visited", "expert_pairs", "expert_pairs_max",
                 "expert_steps"),
        "prefill": ("prefill_experts_visited", "prefill_expert_pairs",
                    "prefill_expert_pairs_max", "prefill_expert_chunks"),
    }

    def take_expert_load(self):
        """The routed layers' load of the launches dispatched since the last
        call, read back — every one was dispatched before the result the
        caller has just waited for, so nothing here waits: the decode scans'
        ``{"experts_visited", "expert_pairs", "expert_pairs_max",
        "expert_steps"}`` (``expert_steps`` = scan steps x routed layers) and
        the prompt-feeding launches' ``prefill_*`` four
        (``prefill_expert_chunks`` = prefill-scan chunks and flat steps that
        fed prompt rows, x routed layers) — each kind's keys only where such
        a launch ran.  None for a graph without routed layers or when none
        ran."""
        taken, self.expert_load_pending = self.expert_load_pending, []
        if not taken:
            return None
        import numpy as np

        out = {}
        for kind, keys in self.EXPERT_LOAD_KEYS.items():
            loads = [(n, np.asarray(load)) for k, n, load in taken
                     if k == kind]
            if loads:
                total = np.sum([load for _, load in loads], axis=0)
                out.update(zip(keys, (
                    *(int(v) for v in total),
                    self.expert_layers * sum(n for n, _ in loads))))
        return out

    def _join_impl(self, bc, tok_src, src_idx, dst, slot, pos, seq_len,
                   num_tokens, eos: Optional[int]):
        with jax.named_scope("join"):
            tok = tok_src[src_idx]
            active = True if eos is None else tok != eos
            return bc.join_row(dst, tok, slot, pos, seq_len, num_tokens,
                               active=active)

    def join_slot(self, bc, tok_src, src_idx, dst, slot, pos, seq_len,
                  num_tokens, eos: Optional[int] = None, counts=None):
        """Splice one staged arrival into a running stretch's batch.

        ``tok_src``: the arrival's final prefill-chunk result tokens (a
        DEVICE array — reading it would sync); ``src_idx``: where its next
        token sits in that array; ``dst``: the flat batch row the request
        occupies from now on; ``slot``/``pos``/``seq_len``/``num_tokens``:
        host bookkeeping of the joined batch.  One tiny jitted program
        (fixed avals — compiles once, polled by the recompile guard); the
        dispatched chain stays fully async.
        """
        with self.telemetry.span("join_dispatch", cat="dispatch",
                                 track="dispatch", prof=self.profiler,
                                 phase="dispatch", jr=self.journal,
                                 kind="join", n_steps=1,
                                 **(counts or {})):
            return with_stack_room(
                self._join, bc, tok_src, jnp.int32(src_idx), jnp.int32(dst),
                jnp.int32(slot), jnp.int32(pos), jnp.int32(seq_len),
                jnp.int32(num_tokens), eos=eos)

    # ------------------------------------------------------------------
    def _project_chunk0(self, params, bc):
        """Embedding → layer-0 norm → layer-0 QKV projection for one chunk.

        The prologue the prefill pipelining issues one scan step EARLY
        (``_prefill_scan_impl``).  Runs the exact op ``lower``s of the
        recognized plan steps (with the interpreter's sharding constraints
        and the same extras the in-graph lowering would see), so the
        carried q/k/v are bit-identical to what the in-graph path would
        compute — an invariant pinned end-to-end by
        tests/test_prefill_gating.py::test_prefill_overlap_scan_bit_identical,
        which is the guard if a future op lower or interpreter convention
        change makes the two paths diverge.
        """
        from ..core.interpreter import (_constrain_spmd, _mesh_is_trivial,
                                        node_scope)
        from ..core.op import OpContext

        e_step, n_step, a_step = self._overlap_steps
        mesh = self.plan.mesh
        trivial = _mesh_is_trivial(mesh)
        x = bc.base.tokens
        for step in (e_step, n_step):
            ctx = OpContext(
                mode="spmd", mesh=None if trivial else mesh,
                training=False, rng=None, config=step.config,
                extras={
                    # mirror _step_impl's extras so an embedding/norm lower
                    # that consults any of them behaves identically here
                    # (pages stays None: the prologue never touches caches)
                    "batch_config": bc,
                    "pallas_decode": self.use_pallas,
                    "pallas_interpret": self.pallas_interpret,
                    "tree_layout": None,
                    "qkv0": None,
                    "pages": None,
                },
            )
            with jax.named_scope(node_scope(step.node)):
                [x] = step.node.op.lower(ctx, [x],
                                         params.get(step.node.name, {}))
            if not trivial:
                x = _constrain_spmd(x, step.out_shardings[0], mesh)
        return a_step.node.op.project_qkv(
            x, params.get(a_step.node.name, {}), bc)

    def _prefill_scan_impl(self, params, state, bcs, sample=None,
                           pages=None, overlap=False):
        """A stack of prefill chunks as ONE on-device ``lax.scan``.

        The decode loop already scans (``decode_scan``); prefill was the one
        serve phase still paying a host dispatch (+ a host sync at request
        boundaries) per chunk.  ``bcs`` is a PrefillBatchConfig whose
        leaves carry a leading chunk axis; each scan step runs the normal
        step program (Q-tiled Pallas prefill kernel included) and emits its
        token ids — the host reads only the sample points it needs, once,
        after the whole scan.  With LM-head gating (``bcs.logit_slots``)
        the emitted ids are [n_chunks, max_requests], indexed by slot.
        The LAST chunk's ids come back a second time in the flat
        ``[max_tokens]`` layout ``join_slot`` takes its token source in
        (gated ids zero-padded: index = slot), so a prompt fed here splices
        into a running stretch with no program of its own in between.

        ``overlap`` (static): software-pipeline the scan — step i ALSO
        computes chunk i+1's embedding→norm→layer-0 QKV (``_project_chunk0``)
        and carries it, so the projection (and its weight fetch) is visible
        to XLA's scheduler alongside chunk i's attention/MLP tail instead
        of sitting behind the while-loop iteration boundary.  Costs one
        redundant prologue per scan segment (the last step precomputes a
        dummy); measured on device via the bench's overlap ablation — if
        XLA's scheduler refuses the overlap the ablation delta is ~0 and
        the artifact records it as scheduler-bound.
        """
        # per-request (rid, token-index) sample keys ride the scan xs with
        # a leading chunk axis (the 4-tuple schedule — see sample_tokens);
        # the legacy 3-tuple folds the shared key by chunk index instead
        per_row = sample is not None and len(sample) > 3
        folds_all = sample[3] if per_row else None

        def run_step(state, bc, i, fold=None, qkv0=None):
            stp = None
            if per_row:
                stp = (sample[0], sample[1], sample[2], fold)
            elif sample is not None:
                key, temperature, top_p = sample
                stp = (jax.random.fold_in(key, i), temperature, top_p)
            return self._step_impl(params, state, bc, stp, qkv0=qkv0,
                                   pages=pages)

        n = bcs.base.tokens.shape[0]
        idx = jnp.arange(n)
        if not overlap:
            def body(state, xs):
                bc, i = xs[0], xs[1]
                result, state = run_step(state, bc, i,
                                         xs[2] if per_row else None)
                return state, (result.token_ids, result.expert_load)

            state, (tokens, load) = jax.lax.scan(
                body, state,
                (bcs, idx, folds_all) if per_row else (bcs, idx))
            # tokens: i32[n_chunks, T or R]
            return tokens, self._flat_last(tokens), state, _summed_load(load)

        # chunk i+1's batch config rides step i's xs; the final step
        # re-projects its own chunk (uniform program; output unused)
        bcs_next = jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x[1:], x[-1:]], axis=0), bcs)
        pre0 = self._project_chunk0(
            params, jax.tree_util.tree_map(lambda x: x[0], bcs))

        def body(carry, xs):
            state, pre = carry
            bc, bc_next, i = xs[0], xs[1], xs[2]
            result, state = run_step(state, bc, i,
                                     xs[3] if per_row else None, qkv0=pre)
            pre_next = self._project_chunk0(params, bc_next)
            return (state, pre_next), (result.token_ids, result.expert_load)

        (state, _), (tokens, load) = jax.lax.scan(
            body, (state, pre0),
            (bcs, bcs_next, idx, folds_all) if per_row
            else (bcs, bcs_next, idx))
        return tokens, self._flat_last(tokens), state, _summed_load(load)

    def _flat_last(self, tokens):
        """The last chunk's token ids as ``join_slot``'s ``tok_src``:
        i32[max_tokens] (slot-indexed gated ids are zero-padded to it)."""
        last = tokens[-1]
        short = self.max_tokens - last.shape[0]
        return jnp.pad(last, (0, short)) if short > 0 else last

    @property
    def _pscan_overlap(self) -> bool:
        return bool(self.prefill_overlap and self._overlap_steps is not None)

    def _pscan_ran(self, gated: bool, sample) -> set:
        """The prefill-scan lengths run so far under one static signature
        of ``_pscan``: LM head ``gated`` or not, the structure of the
        ``sample`` argument (None: greedy), ``overlap``."""
        return self._pscan_lengths.setdefault(
            (gated, len(sample or ()), self._pscan_overlap), set())

    def prefill_scan_longest(self, gated: bool, sample=None) -> int:
        """The longest power of two such that it and every smaller one
        have run as a prefill scan of chunks ``gated`` or not with a
        ``sample`` argument of this structure (0: not even a scan of one
        chunk): the lengths a feed can be cut into and compile nothing."""
        ran = self._pscan_ran(gated, sample)
        longest = 1
        while longest in ran:
            longest *= 2
        return longest // 2

    def prefill_scan(self, bcs, sample=None, counts=None,
                     flat_last: bool = False, coming=()):
        """Run a stacked PrefillBatchConfig (leading chunk axis) on device.

        ``sample``: optional ``(key, temperature, top_p)`` so the chunks
        carrying a prompt's final position emit a SAMPLED first token.
        ``flat_last``: return ``(tokens, last)`` — ``last`` is the final
        chunk's ids in ``join_slot``'s flat layout (the same program either
        way: the caller that splices a prompt in just keeps it).

        The set of scan lengths stays CLOSED under what has run: the scan
        length is a static shape, one program a length, and a caller that
        cuts its feeds into powers of two no longer than
        :meth:`prefill_scan_longest` (``RequestManager._prefill_feed``)
        must find each of them built.  So a length asked for the first time
        is preceded, once, by every smaller power of two not run yet, each
        on all-pad chunks — no token, every row of no request (the scratch
        row), no logit slot: what a chunk's pad tiles are, so no slot's
        cache or state moves, live decoders' included — under the same
        span with ``pad=1`` (the journal counts the launch and no prompt
        chunk).  ``coming``: the lengths the caller launches next, as the
        rest of the same feed; they build themselves, so no pad scan is
        spent on them.  After a feed has run, every later one of its
        longest length or shorter compiles nothing.
        """
        assert self.params is not None, "call init_operators_inference() first"
        if self.fault_injector is not None:
            self.fault_injector.maybe_fail("prefill_scan")
        n_chunks = int(bcs.base.tokens.shape[0])
        ran = self._pscan_ran(bcs.logit_slots is not None, sample)
        if n_chunks not in ran:
            for k in range((n_chunks - 1).bit_length()):
                if (1 << k) not in ran and (1 << k) not in coming:
                    self._launch_prefill_scan(
                        *_pad_chunks(bcs, sample, 1 << k), {"pad": 1})
                    ran.add(1 << k)
        out = self._launch_prefill_scan(bcs, sample, counts)
        ran.add(n_chunks)
        return out if flat_last else out[0]

    def _launch_prefill_scan(self, bcs, sample, counts):
        n_chunks = int(bcs.base.tokens.shape[0])
        with self.telemetry.span("prefill_scan_dispatch", cat="dispatch",
                                 track="dispatch", prof=self.profiler,
                                 phase="dispatch", jr=self.journal,
                                 kind="prefill_scan",
                                 n_steps=n_chunks, n_chunks=n_chunks,
                                 **(counts or {})):
            tokens, last, self.state, load = with_stack_room(
                self._pscan, self.params, self.state, bcs, sample,
                self._page_view(), overlap=self._pscan_overlap)
        if load is not None and not (counts or {}).get("pad"):
            self.expert_load_pending.append(("prefill", n_chunks, load))
        self._count_attention_paths()
        return tokens, last

    def reset(self):
        """Clear all cache contents (new serving session)."""
        self.state = self.allocate_kv_cache()
