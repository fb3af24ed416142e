"""Normalization ops: LayerNorm, RMSNorm and the fused residual variants.

Reference: ``src/ops/layer_norm.cc/.cu``, ``rms_norm.cc/.cu``,
``residual_layer_norm.cu``, ``add_bias_residual_layer_norm.cu``,
``residual_rms_norm.cu``, ``sigmoid_silu_multi.cu`` — the fused variants exist
in the reference because separate CUDA kernels would round-trip HBM; under XLA
the fusion happens automatically, but we keep them as distinct graph ops so
serve-graph shapes (and the search space) match the reference one-to-one.

Sharding: normalization reduces over the last (feature) dim, so that dim must
be local; all leading dims propagate.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from ..core.graph import ParamSpec, TensorSpec
from ..core.op import Op, ShardingSolution, register_op
from ..core.sharding import TensorSharding
from .elementwise import propagate


def _norm_sharding(spec: TensorSpec, in_sh) -> TensorSharding:
    sh = propagate(in_sh, spec)
    sh = TensorSharding(sh.dims, frozenset())  # no partial inputs
    return sh.with_dim(spec.ndim - 1, ())  # feature dim must be local


def _layer_norm(x, gamma, beta, eps):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if gamma is not None:
        y = y * gamma
    if beta is not None:
        y = y + beta
    return y.astype(dtype)


def _rms_norm(x, gamma, eps, unit_offset=False, out_dtype=None):
    """``unit_offset``: the gain is stored as its distance from one (scale
    by ``1 + gamma``).  ``out_dtype``: what the normed rows come out as
    where that is not the input's type (a float32 residual stream feeding
    bf16 projections)."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps)
    if gamma is not None:
        y = y * (1.0 + gamma.astype(jnp.float32) if unit_offset else gamma)
    return y.astype(out_dtype or x.dtype)


@register_op
class LayerNorm(Op):
    type_name = "layer_norm"

    def __init__(self, dim: int, elementwise_affine: bool = True, eps: float = 1e-5,
                 use_bias: bool = True, dtype=jnp.float32):
        self.dim = int(dim)
        self.elementwise_affine = elementwise_affine
        self.eps = float(eps)
        self.use_bias = use_bias
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [in_specs[0]]

    def params(self):
        if not self.elementwise_affine:
            return []
        ps = [ParamSpec("gamma", TensorSpec((self.dim,), jnp.dtype(self.dtype)))]
        if self.use_bias:
            ps.append(ParamSpec("beta", TensorSpec((self.dim,), jnp.dtype(self.dtype))))
        return ps

    def lower(self, ctx, inputs, params):
        gamma = params.get("gamma") if self.elementwise_affine else None
        beta = params.get("beta") if self.elementwise_affine and self.use_bias else None
        return [_layer_norm(inputs[0], gamma, beta, self.eps)]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        sh = _norm_sharding(in_specs[0], in_shardings[0] if in_shardings else None)
        return ShardingSolution(inputs=[sh], outputs=[sh])

    def flops(self, in_specs):
        return 8 * in_specs[0].size


@register_op
class RMSNorm(Op):
    type_name = "rms_norm"

    def __init__(self, dim: int, eps: float = 1e-6, dtype=jnp.float32,
                 unit_offset: bool = False, out_dtype=None):
        self.dim = int(dim)
        self.eps = float(eps)
        self.dtype = jnp.dtype(dtype).name
        self.unit_offset = bool(unit_offset)
        self.out_dtype = jnp.dtype(out_dtype).name if out_dtype else None

    def infer_shapes(self, in_specs):
        x = in_specs[0]
        return [TensorSpec(x.shape, jnp.dtype(self.out_dtype or x.dtype))]

    def params(self):
        return [ParamSpec("gamma", TensorSpec((self.dim,), jnp.dtype(self.dtype)))]

    def lower(self, ctx, inputs, params):
        return [_rms_norm(inputs[0], params.get("gamma"), self.eps,
                          self.unit_offset, self.out_dtype)]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        sh = _norm_sharding(in_specs[0], in_shardings[0] if in_shardings else None)
        return ShardingSolution(inputs=[sh], outputs=[sh])

    def flops(self, in_specs):
        return 5 * in_specs[0].size


@register_op
class ResidualLayerNorm(Op):
    """out_residual = x + r1 (+ r2); out = layer_norm(out_residual).

    Reference: ``src/ops/residual_layer_norm.cu`` (two outputs).
    """

    type_name = "residual_layer_norm"

    def __init__(self, dim: int, use_two_residuals: bool = False,
                 elementwise_affine: bool = True, eps: float = 1e-5,
                 use_bias: bool = True, dtype=jnp.float32):
        self.dim = int(dim)
        self.use_two_residuals = use_two_residuals
        self.elementwise_affine = elementwise_affine
        self.eps = float(eps)
        self.use_bias = use_bias
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [in_specs[0], in_specs[0]]  # (residual_sum, normed)

    def params(self):
        if not self.elementwise_affine:
            return []
        ps = [ParamSpec("gamma", TensorSpec((self.dim,), jnp.dtype(self.dtype)))]
        if self.use_bias:
            ps.append(ParamSpec("beta", TensorSpec((self.dim,), jnp.dtype(self.dtype))))
        return ps

    def lower(self, ctx, inputs, params):
        s = inputs[0] + inputs[1]
        if self.use_two_residuals:
            s = s + inputs[2]
        gamma = params.get("gamma") if self.elementwise_affine else None
        beta = params.get("beta") if self.elementwise_affine and self.use_bias else None
        return [s, _layer_norm(s, gamma, beta, self.eps)]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        sh = _norm_sharding(in_specs[0], in_shardings[0] if in_shardings else None)
        n = len(in_specs)
        return ShardingSolution(inputs=[sh] * n, outputs=[sh, sh])


@register_op
class AddBiasResidualLayerNorm(Op):
    """out_residual = x + attn_bias + residual; out = LN(out_residual).

    Reference: ``src/ops/add_bias_residual_layer_norm.cu`` (OPT graph shape).
    """

    type_name = "add_bias_residual_layer_norm"

    def __init__(self, dim: int, elementwise_affine: bool = True,
                 eps: float = 1e-5, use_bias: bool = True, dtype=jnp.float32):
        self.dim = int(dim)
        self.elementwise_affine = elementwise_affine
        self.eps = float(eps)
        self.use_bias = use_bias
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [in_specs[0], in_specs[0]]

    def params(self):
        ps = [ParamSpec("attn_bias", TensorSpec((self.dim,), jnp.dtype(self.dtype)))]
        if self.elementwise_affine:
            ps.append(ParamSpec("gamma", TensorSpec((self.dim,), jnp.dtype(self.dtype))))
            if self.use_bias:
                ps.append(ParamSpec("beta", TensorSpec((self.dim,), jnp.dtype(self.dtype))))
        return ps

    def lower(self, ctx, inputs, params):
        s = inputs[0] + params["attn_bias"] + inputs[1]
        gamma = params.get("gamma") if self.elementwise_affine else None
        beta = params.get("beta") if self.elementwise_affine and self.use_bias else None
        return [s, _layer_norm(s, gamma, beta, self.eps)]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        sh = _norm_sharding(in_specs[0], in_shardings[0] if in_shardings else None)
        return ShardingSolution(inputs=[sh, sh], outputs=[sh, sh])


@register_op
class ResidualRMSNorm(Op):
    """out_residual = x + r; out = rms_norm(out_residual).

    Reference: ``src/ops/residual_rms_norm.cu`` (LLaMA serve graph shape).
    """

    type_name = "residual_rms_norm"

    def __init__(self, dim: int, eps: float = 1e-6, dtype=jnp.float32,
                 unit_offset: bool = False, out_dtype=None):
        self.dim = int(dim)
        self.eps = float(eps)
        self.dtype = jnp.dtype(dtype).name
        self.unit_offset = bool(unit_offset)
        self.out_dtype = jnp.dtype(out_dtype).name if out_dtype else None

    def infer_shapes(self, in_specs):
        # the sum takes the wider of the two types: a float32 residual
        # stream stays float32 whatever the block's output came in
        x = in_specs[0]
        s = jnp.promote_types(x.dtype, in_specs[1].dtype)
        return [TensorSpec(x.shape, s),
                TensorSpec(x.shape, jnp.dtype(self.out_dtype or s))]

    def params(self):
        return [ParamSpec("gamma", TensorSpec((self.dim,), jnp.dtype(self.dtype)))]

    def lower(self, ctx, inputs, params):
        s = inputs[0] + inputs[1]
        return [s, _rms_norm(s, params.get("gamma"), self.eps,
                             self.unit_offset, self.out_dtype)]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        sh = _norm_sharding(in_specs[0], in_shardings[0] if in_shardings else None)
        return ShardingSolution(inputs=[sh, sh], outputs=[sh, sh])


@register_op
class SigmoidSiluMulti(Op):
    """silu(x1) * x2 — the SwiGLU gate junction.

    Reference: ``src/ops/sigmoid_silu_multi.cu``.
    """

    type_name = "sigmoid_silu_multi"

    def infer_shapes(self, in_specs):
        return [in_specs[0]]

    def lower(self, ctx, inputs, params):
        return [jax.nn.silu(inputs[0]) * inputs[1]]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        # fully elementwise: propagate (both inputs must match; prefer in0's)
        sh = propagate(in_shardings[0] if in_shardings else None, in_specs[0])
        sh = TensorSharding(sh.dims, frozenset())
        return ShardingSolution(inputs=[sh, sh], outputs=[sh])

    def flops(self, in_specs):
        return 5 * in_specs[0].size


@register_op
class BatchNorm(Op):
    """Batch normalization (training uses batch stats; running stats carried as
    non-trainable params updated outside the graph for simplicity).

    Reference: ``src/ops/batch_norm.cc/.cu`` (cuDNN).
    """

    type_name = "batch_norm"

    def __init__(self, dim: int, relu: bool = False, eps: float = 1e-5,
                 momentum: float = 0.9, dtype=jnp.float32):
        self.dim = int(dim)  # channel count (NCHW dim 1)
        self.relu = relu
        self.eps = float(eps)
        self.momentum = float(momentum)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [in_specs[0]]

    def params(self):
        d = jnp.dtype(self.dtype)
        return [
            ParamSpec("gamma", TensorSpec((self.dim,), d)),
            ParamSpec("beta", TensorSpec((self.dim,), d)),
            ParamSpec("running_mean", TensorSpec((self.dim,), d), trainable=False),
            ParamSpec("running_var", TensorSpec((self.dim,), d), trainable=False),
        ]

    def lower(self, ctx, inputs, params):
        x = inputs[0]  # NCHW
        axes = (0,) + tuple(range(2, x.ndim))
        if ctx.training:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            if ctx.mode == "local" and ctx.mesh is not None and ctx.config:
                sample = ctx.config.get("sample", ())
                if sample:
                    mean = jax.lax.pmean(mean, sample)
                    var = jax.lax.pmean(var, sample)  # approx (ignores E[m^2] term)
        else:
            mean = params["running_mean"]
            var = params["running_var"]
        shape = (1, self.dim) + (1,) * (x.ndim - 2)
        y = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + self.eps)
        y = y * params["gamma"].reshape(shape) + params["beta"].reshape(shape)
        if self.relu:
            y = jnp.maximum(y, 0)
        return [y.astype(x.dtype)]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        x = in_specs[0]
        sample = tuple(config.get("sample", ()))
        sh = TensorSharding.replicated(x.ndim)
        if sample:
            sh = sh.with_dim(0, sample)
        return ShardingSolution(inputs=[sh], outputs=[sh])
