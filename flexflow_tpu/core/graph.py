"""Frontend computation graph: the TPU-native analogue of FlexFlow's Layer graph.

FlexFlow keeps two graphs (reference: ``src/runtime/layer.cc``,
``src/runtime/model.cc``): a user-built *Layer* graph that only knows tensor
shapes, and a lowered *Parallel Computation Graph* whose tensors carry
partitioning.  We keep the same split: :class:`Graph` here is the Layer graph
(shapes + dtypes only); :mod:`flexflow_tpu.core.pcg` wraps it with a mesh and
per-tensor :class:`~flexflow_tpu.core.sharding.TensorSharding` annotations and
reifies resharding as parallel-op nodes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """Static shape + dtype of one logical (global) tensor."""

    shape: Tuple[int, ...]
    dtype: Any = jnp.float32

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def nbytes(self) -> int:
        return self.size * jnp.dtype(self.dtype).itemsize

    def __str__(self) -> str:
        return f"{jnp.dtype(self.dtype).name}{list(self.shape)}"


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """A weight owned by a node: spec + initializer name."""

    name: str
    spec: TensorSpec
    initializer: Any = None  # Initializer instance or None -> op default
    trainable: bool = True
    # hold the spec's dtype whatever dtype the model's params are cast to at
    # initialization (float32 constants of a recurrence in a bf16 model)
    pin_dtype: bool = False


class Tensor:
    """Handle to a tensor in a Graph (what FFModel builder methods return)."""

    __slots__ = ("graph", "tid")

    def __init__(self, graph: "Graph", tid: int):
        self.graph = graph
        self.tid = tid

    @property
    def spec(self) -> TensorSpec:
        return self.graph.tensor_specs[self.tid]

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.spec.shape

    @property
    def dtype(self):
        return self.spec.dtype

    def __repr__(self) -> str:
        return f"Tensor(t{self.tid}: {self.spec})"


@dataclasses.dataclass
class Node:
    """One operator instance in the graph."""

    nid: int
    name: str  # unique, e.g. "dense_3"
    op: Any  # flexflow_tpu.core.op.Op
    inputs: List[int]  # tensor ids
    outputs: List[int]  # tensor ids

    def __repr__(self) -> str:
        ins = ",".join(f"t{t}" for t in self.inputs)
        outs = ",".join(f"t{t}" for t in self.outputs)
        return f"{self.name}({ins})->({outs})"


class Graph:
    """A DAG of Nodes over tensor ids, built incrementally (append-only)."""

    def __init__(self):
        self.nodes: List[Node] = []
        self.tensor_specs: List[TensorSpec] = []
        self.producer: Dict[int, Tuple[int, int]] = {}  # tid -> (nid, out_idx)
        self.input_tids: List[int] = []  # graph inputs (placeholders)
        self._name_counts: Dict[str, int] = {}

    # ---- construction -------------------------------------------------
    def add_input(self, spec: TensorSpec) -> Tensor:
        tid = self._new_tensor(spec)
        self.input_tids.append(tid)
        return Tensor(self, tid)

    def _new_tensor(self, spec: TensorSpec) -> int:
        self.tensor_specs.append(spec)
        return len(self.tensor_specs) - 1

    def unique_name(self, base: str) -> str:
        n = self._name_counts.get(base, 0)
        self._name_counts[base] = n + 1
        return f"{base}_{n}" if n else base

    def add_node(
        self,
        op: Any,
        inputs: Sequence[Tensor],
        name: Optional[str] = None,
    ) -> List[Tensor]:
        for t in inputs:
            if t.graph is not self:
                raise ValueError("input tensor from a different graph")
        name = self.unique_name(name or op.type_name)
        in_specs = [t.spec for t in inputs]
        out_specs = op.infer_shapes(in_specs)
        nid = len(self.nodes)
        out_tids = [self._new_tensor(s) for s in out_specs]
        node = Node(nid, name, op, [t.tid for t in inputs], out_tids)
        self.nodes.append(node)
        for i, tid in enumerate(out_tids):
            self.producer[tid] = (nid, i)
        return [Tensor(self, tid) for tid in out_tids]

    # ---- queries ------------------------------------------------------
    def topo_order(self) -> List[Node]:
        # append-only construction => node list is already topologically sorted
        return self.nodes

    def consumers(self, tid: int) -> List[Tuple[Node, int]]:
        out = []
        for node in self.nodes:
            for slot, t in enumerate(node.inputs):
                if t == tid:
                    out.append((node, slot))
        return out

    def spec(self, tid: int) -> TensorSpec:
        return self.tensor_specs[tid]

    def param_specs(self) -> Dict[str, Dict[str, ParamSpec]]:
        """{node_name: {param_name: ParamSpec}} for all weighted nodes."""
        out: Dict[str, Dict[str, ParamSpec]] = {}
        for node in self.nodes:
            ps = node.op.params()
            if ps:
                out[node.name] = {p.name: p for p in ps}
        return out

    def __str__(self) -> str:
        lines = []
        for tid in self.input_tids:
            lines.append(f"  input t{tid}: {self.tensor_specs[tid]}")
        for node in self.nodes:
            outs = ", ".join(
                f"t{t}:{self.tensor_specs[t]}" for t in node.outputs
            )
            ins = ", ".join(f"t{t}" for t in node.inputs)
            lines.append(f"  {node.name}: ({ins}) -> {outs}")
        return "Graph(\n" + "\n".join(lines) + "\n)"


def live_cuts(graph: "Graph", final_tids: Sequence[int]) -> List[frozenset]:
    """Per-boundary live tensor sets: the cut-tracking core of the SESE
    segment machinery (``FFModel._pipeline_segments`` uses it for the GPipe
    training executor; the serve stage split uses it for pipeline-parallel
    serving).

    ``live_cuts(g, finals)[i]`` is the set of tensor ids produced at or
    before node ``i`` (graph inputs included) that are still needed strictly
    after it — consumed by a later node, or listed in ``final_tids`` (the
    protected outputs).  A boundary whose live set is small is a cheap
    pipeline cut: only those tensors cross between stages.  A single-tensor
    live set is exactly the SESE (single-entry/single-exit) segment boundary
    the training pipeline carves at; serve graphs with fused residual
    norms carry ``{residual, hidden}`` between decoder layers, so their
    natural cuts are two tensors wide.
    """
    nodes = graph.nodes
    keep = set(final_tids)
    last_use: Dict[int, int] = {}
    for i, node in enumerate(nodes):
        for t in node.inputs:
            last_use[t] = i
    live = {t for t in graph.input_tids if last_use.get(t) is not None}
    out: List[frozenset] = []
    for i, node in enumerate(nodes):
        for t in node.inputs:
            if last_use.get(t) == i and t not in keep:
                live.discard(t)
        for t in node.outputs:
            if last_use.get(t, -1) > i or t in keep:
                live.add(t)
        out.append(frozenset(live))
    return out
