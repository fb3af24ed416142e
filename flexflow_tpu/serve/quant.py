"""Weight-only int8 quantization for serve graphs (VERDICT r4 #8).

Reference parity: the serve fork's Linear carries quantization hooks
(SURVEY.md §2.2 — "quantization hooks in serve fork"); FlexFlow dequantizes
in its CUDA GEMM prologue.  The TPU analogue: weights are stored int8 with
per-out-channel f32 scales and dequantized on chip — XLA fuses the
``convert * scale`` into the dot's operand pipeline, so HBM traffic for the
quantized weights halves (bf16 -> int8).  Decode is weight-bandwidth-bound,
making this a direct TPOT lever.

Applies AFTER ``init_operators_inference`` / HF weight load: arrays are
replaced in-place in ``im.params`` (sharded like the originals), and the
attention op's fused QKV / output projections ride the same scheme via a
dtype check in ``serve/ops.py``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.linear import Linear


def _quantize_array(w):
    """int8-quantize ``w`` with per-out-channel scales.

    Every weight here contracts over its FIRST dim (Linear ``[in, out]``,
    fused QKV ``[E, KV, G, D]``, o_proj ``[QH*D, E]``; the latent operator's
    ``q_proj [E, H, D]``, ``kv_a [E, r + rope]`` and ``kv_b [r, H, D]``,
    which is dequantised whole before either of its uses), so the scale spans
    ``w.shape[1:]`` — one scale per output channel.  Returns ``(q int8,
    scale f32)`` with ``q * scale ~= w`` and per-element error bounded by
    ``scale / 2``.
    """
    wf = np.asarray(w, np.float32)
    scale = np.abs(wf).max(axis=0) / 127.0
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    q = np.clip(np.round(wf / scale), -127, 127).astype(np.int8)
    return q, scale


def _like_sharded(arr, ref):
    """Device-put ``arr`` with ``ref``'s sharding when it has one."""
    sh = getattr(ref, "sharding", None)
    if sh is not None and getattr(sh, "mesh", None) is not None:
        try:
            return jax.device_put(arr, sh)
        except (ValueError, TypeError):
            pass
    return jnp.asarray(arr)


def _scale_sharding(kernel_ref, mesh=None):
    """NamedSharding for a per-out-channel scale: the kernel sharding's
    spec with the contracted (first) dim dropped.  The mesh comes from the
    kernel's OWN sharding — under pipeline-parallel serving each stage's
    kernels live on that stage's sub-mesh, not the model's full mesh."""
    sh = getattr(kernel_ref, "sharding", None)
    if sh is None or getattr(sh, "spec", None) is None:
        return None
    mesh = getattr(sh, "mesh", None) or mesh
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(*sh.spec[1:]))


def quantize_int8(im, include: Optional[Sequence[str]] = None,
                  attention: bool = True) -> int:
    """Quantize the serve model's weight matrices to int8 in place.

    ``include``: optional name substrings restricting which nodes quantize
    (default: every Linear with a 2-D kernel + every attention op's fused
    projections).  The filter applies to BOTH branches — ``attention=True``
    only opts the attention ops in, it does not override ``include``
    (ADVICE r5 low).  ``attention``: also quantize the attention op's fused
    ``qkv`` and ``o_proj``.  Returns the number of quantized weight arrays.
    Call after ``init_operators_inference`` (and any HF weight load);
    re-quantizing is a no-op (int8 arrays are skipped).
    """
    assert im.params is not None, "call init_operators_inference() first"
    mesh = im.model.mesh
    n = 0
    for node in im.model.graph.nodes:
        op = node.op
        g = im.params.get(node.name)
        if g is None:
            continue
        if include and not any(s in node.name for s in include):
            continue
        if isinstance(op, Linear):
            k = g.get("kernel")
            if k is None or k.dtype == jnp.int8:
                continue
            q, scale = _quantize_array(k)
            g["kernel"] = _like_sharded(q, k)
            ssh = _scale_sharding(k, mesh)
            g["kernel_scale"] = (jax.device_put(jnp.asarray(scale), ssh)
                                 if ssh is not None else jnp.asarray(scale))
            op.quantization = "int8"
            n += 1
        elif attention and (hasattr(op, "num_kv_heads")
                            or hasattr(op, "int8_params")):
            # an attention op names its projections (``int8_params``: the
            # latent operator's four); the others have the fused pair
            for pname in getattr(op, "int8_params", ("qkv", "o_proj")):
                w = g.get(pname)
                if w is None or w.dtype == jnp.int8:
                    continue
                q, scale = _quantize_array(w)
                g[pname] = _like_sharded(q, w)
                ssh = _scale_sharding(w, mesh)
                g[f"{pname}_scale"] = (
                    jax.device_put(jnp.asarray(scale), ssh)
                    if ssh is not None else jnp.asarray(scale))
                op.quantization = "int8"  # capacity planning (see below)
                n += 1
    return n


def annotate_int8(graph, include: Optional[Sequence[str]] = None,
                  attention: bool = True) -> int:
    """Mark a serve graph's weight matrices as int8 FOR CAPACITY PLANNING,
    without touching any arrays.

    ``plan_memory_bytes`` (search/simulator.py) counts params marked
    ``op.quantization == "int8"`` at 1 byte/element + per-out-channel f32
    scales — the planning-time counterpart of :func:`quantize_int8`, usable
    on a purely symbolic graph (no ``init_operators_inference`` needed).
    This is how the full-depth 32-layer 7B-shape config is budgeted BEFORE
    allocating anything: build the graph, ``annotate_int8`` it, register
    the serve capacities (+ ``kv_dtype="int8"``), and check
    ``plan_memory_bytes(plan, training=False)`` against the chip's HBM.
    Same ``include``/``attention`` selection rules as :func:`quantize_int8`.
    Returns the number of ops marked.
    """
    n = 0
    for node in graph.nodes:
        op = node.op
        if include and not any(s in node.name for s in include):
            continue
        if isinstance(op, Linear):
            if any(p.name == "kernel" and len(p.spec.shape) == 2
                   for p in op.params()):
                op.quantization = "int8"
                n += 1
        elif attention and (hasattr(op, "num_kv_heads")
                            or hasattr(op, "int8_params")):
            op.quantization = "int8"
            n += 1
    return n


def dequant(w, scale, dtype):
    """On-chip dequantize: fused by XLA into the consuming dot."""
    if w.dtype != jnp.int8:
        return w
    return (w.astype(jnp.float32) * scale).astype(dtype)
