"""Fused batch-norm statistics + scale/shift + activation (channel-last).

"Operator Fusion in XLA" (PAPERS.md) names cross-op reductions as a
fusion class XLA will not form by itself: the BN statistics pass reads
the whole activation tensor, and XLA schedules it as its own reduction
fusion separate from the normalize+relu elementwise fusion — three
passes over HBM for what is arithmetically two.  These kernels do it in
two passes with one read each:

  * ``_bn_stats_kernel`` — ONE sweep computing per-channel sum and
    sum-of-squares together (the reference's BatchNormWithReLU kernel
    fuses the same pair, src/operator/contrib/batch_norm_relu.cc);
  * ``_bn_apply_kernel`` — normalize folded to per-channel scale/shift
    (the round-2 dtype discipline from ops/nn.py: f32 statistics, the
    big tensor touched only in its own dtype) + the activation, fused.

Channel-last (NHWC) only — the TPU zoo path; channel-first callers fall
back to the reference composition (an observable fallback, see
ops/nn.py batch_norm_act_train).

Variance is E[x^2] - mean^2 (one-pass), vs the reference's two-pass
E[(x-mean)^2]; both are f32 accumulations and agree to ~1e-6 relative on
O(1) activations — the documented tolerance (docs/kernels.md).  The
backward is the standard analytic BN+act gradient in jnp: it is a plain
matmul-free elementwise+reduction pipeline XLA already fuses well, so a
hand kernel buys nothing there (measured round-2: the win is the forward
statistics read).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import registry as _registry

__all__ = ["bn_act_train", "pick_row_block", "supported_act"]

_ACTS = ("relu", "identity")


def supported_act(act_type: str) -> bool:
    return act_type in _ACTS


def pick_row_block(rows: int) -> int:
    """Largest preferred block dividing ``rows`` (0 = not tile-able);
    the shared picker in :mod:`.registry`."""
    return _registry.pick_block(rows)


def _bn_stats_kernel(x_ref, s_ref, ss_ref):
    import jax.experimental.pallas as pl

    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    xb = x_ref[...].astype(jnp.float32)
    s_ref[...] += xb.sum(axis=0, keepdims=True)
    ss_ref[...] += (xb * xb).sum(axis=0, keepdims=True)


def _bn_apply_kernel(scale_ref, shift_ref, x_ref, y_ref, *, act: str):
    y = x_ref[...] * scale_ref[...] + shift_ref[...]
    if act == "relu":
        y = jnp.maximum(y, 0)
    y_ref[...] = y.astype(y_ref.dtype)


def _stats_pallas(x2d, br: int, interpret: bool):
    import jax.experimental.pallas as pl

    rows, c = x2d.shape
    out = pl.pallas_call(
        _bn_stats_kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, c), lambda r: (r, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda r: (0, 0)),
                   pl.BlockSpec((1, c), lambda r: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        compiler_params=_registry.tpu_compiler_params(("arbitrary",)),
        interpret=interpret,
        name="bn_act_stats",
    )(x2d)
    return out[0][0], out[1][0]


def _apply_pallas(x2d, scale, shift, act: str, br: int, interpret: bool):
    import jax.experimental.pallas as pl

    rows, c = x2d.shape
    return pl.pallas_call(
        functools.partial(_bn_apply_kernel, act=act),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((1, c), lambda r: (0, 0)),
                  pl.BlockSpec((1, c), lambda r: (0, 0)),
                  pl.BlockSpec((br, c), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((br, c), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, c), x2d.dtype),
        compiler_params=_registry.tpu_compiler_params(("parallel",)),
        interpret=interpret,
        name="bn_act_apply",
    )(scale.reshape(1, c), shift.reshape(1, c), x2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def bn_act_train(x, gamma, beta, eps: float, act: str, interpret: bool):
    """Fused training-mode BN + activation on channel-LAST ``x``.

    Returns ``(y, mean, var)`` — batch statistics in f32, ``y`` in
    ``x.dtype`` (moving-average blending stays with the caller, matching
    ``ops.nn.batch_norm_train``).  The caller guarantees tile-ability
    (``pick_row_block`` > 0) and a supported ``act``."""
    y, mean, var = _bn_act_fwd_impl(x, gamma, beta, eps, act, interpret)
    return y, mean, var


def _bn_act_fwd_impl(x, gamma, beta, eps, act, interpret):
    c = x.shape[-1]
    rows = x.size // c
    x2d = x.reshape(rows, c)
    br = pick_row_block(rows)
    s, ss = _stats_pallas(x2d, br, interpret)
    n = jnp.float32(rows)
    mean = s / n
    var = jnp.maximum(ss / n - mean * mean, 0.0)  # one-pass; clamp -0 ulps
    inv = lax.rsqrt(var + eps)
    # round-2 dtype discipline: fold stats into per-channel f32 vectors,
    # cast the C-sized vectors, touch the big tensor only in its own dtype
    gf = gamma.astype(jnp.float32)
    scale = (gf * inv).astype(x.dtype)
    shift = (beta.astype(jnp.float32) - mean * gf * inv).astype(x.dtype)
    y2d = _apply_pallas(x2d, scale, shift, act, br, interpret)
    return y2d.reshape(x.shape), mean, var


def _bn_act_fwd(x, gamma, beta, eps, act, interpret):
    y, mean, var = _bn_act_fwd_impl(x, gamma, beta, eps, act, interpret)
    return (y, mean, var), (x, gamma, mean, var, y)


def _bn_act_bwd(eps, act, interpret, res, cts):
    """Analytic BN(+act) backward (jnp; XLA fuses this pipeline fine).

    Includes the exact mean/var cotangent contributions so consumers that
    differentiate through the returned statistics stay correct (the npx
    layer stop-gradients them, making those terms zero)."""
    x, gamma, mean, var, y = res
    gy, gmean, gvar = cts
    axes = tuple(range(x.ndim - 1))
    n = jnp.float32(x.size // x.shape[-1])
    inv = lax.rsqrt(var + eps)
    gy = gy.astype(jnp.float32)
    if act == "relu":
        gy = gy * (y > 0)
    xc = x.astype(jnp.float32) - mean
    xhat = xc * inv
    dgamma = (gy * xhat).sum(axes)
    dbeta = gy.sum(axes)
    dx = (gamma.astype(jnp.float32) * inv) * (
        gy - dbeta / n - xhat * dgamma / n)
    if gmean is not None:
        dx = dx + gmean / n
    if gvar is not None:
        dx = dx + gvar * 2.0 * xc / n
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


bn_act_train.defvjp(_bn_act_fwd, _bn_act_bwd)
