"""``mx.npx`` — NumPy-extension namespace: the NN operator surface.

Ref: python/mxnet/numpy_extension/ + the ``_npx_*`` op shims (src/api/operator).
Each function lifts a pure kernel from ops.nn into NDArray land with autograd
via ops.dispatch. Stateful semantics handled here, not in kernels:
  * batch_norm mutates moving_mean/var in-place like the reference kernel
    (src/operator/nn/batch_norm.cc) — via NDArray._set_data so jit traces
    capture the update;
  * dropout / rrelu draw from the global RNG (mxnet_tpu.random) and are
    identity under predict mode (autograd.is_training gates, matching
    mode-dependent ops in the reference).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import autograd
from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from ..ops import nn as _nn
from ..ops.dispatch import call, invoke, wrap_op
from ..random import next_key
from ..util import is_np_array, set_np, reset_np  # noqa: F401

__all__ = [
    "activation", "leaky_relu", "relu", "sigmoid", "fully_connected",
    "convolution", "deconvolution", "pooling", "batch_norm", "layer_norm",
    "group_norm", "instance_norm", "lrn", "dropout", "softmax", "log_softmax",
    "masked_softmax", "masked_log_softmax", "softmax_cross_entropy",
    "embedding", "one_hot", "pick", "topk", "sequence_mask", "sequence_last",
    "sequence_reverse", "space_to_depth", "depth_to_space", "rnn",
    "div_sqrt_dim", "interleaved_matmul_selfatt_qk",
    "interleaved_matmul_selfatt_valatt", "interleaved_matmul_encdec_qk",
    "interleaved_matmul_encdec_valatt", "sldwin_atten_score",
    "sldwin_atten_mask_like", "sldwin_atten_context", "box_encode",
    "box_decode", "bipartite_matching", "quadratic", "index_copy",
    "index_array", "edge_id", "getnnz", "batch_norm_with_relu",
    "dynamic_reshape", "col2im", "hawkesll", "rroi_align", "roi_pooling",
    "upsampling", "khatri_rao", "sample_unique_zipfian",
    "gamma", "gammaln", "erf", "erfinv", "digamma",
    "reshape_like", "slice_like", "broadcast_like", "shape_array", "batch_dot",
    "arange_like", "gather_nd", "scatter_nd", "index_update", "index_add",
    "smooth_l1", "l2_normalization", "ctc_loss", "all_finite",
    "multi_sum_sq",
    "clip_by_global_norm",
    "multi_head_attention", "flash_attention",
    "foreach", "while_loop", "cond",
    "box_iou", "box_nms", "roi_align",
    "waitall", "load", "save", "set_np", "reset_np", "is_np_array",
    "cpu", "gpu", "tpu", "num_gpus", "num_tpus", "current_context",
]

from ..context import cpu, gpu, tpu, num_gpus, num_tpus, current_context  # noqa: E402
from ..ndarray import waitall  # noqa: E402
from ..ndarray.utils import load, save  # noqa: E402


# -- activations -------------------------------------------------------------

def activation(data, act_type: str = "relu", **kw):
    # op name must stay the registry name "activation" (act_type is an
    # attr) so exported symbol-json reloads via resolve_op
    return call(lambda x: _nn.activation(x, act_type), (data,), {},
                name="activation", attrs={"act_type": act_type})


def leaky_relu(data, gamma=None, act_type: str = "leaky", slope: float = 0.25,
               lower_bound: float = 0.125, upper_bound: float = 0.334, **kw):
    key = None
    if act_type == "rrelu" and autograd.is_training():
        key = next_key()
    args = (data, gamma) if gamma is not None else (data,)

    def f(x, g=None):
        return _nn.leaky_relu(x, g, act_type=act_type, slope=slope,
                              lower_bound=lower_bound, upper_bound=upper_bound,
                              rng_key=key)

    return call(f, args, {}, name="leaky_relu",
                attrs={"act_type": act_type, "slope": slope})


relu = wrap_op(jax.nn.relu, "relu")
sigmoid = wrap_op(jax.nn.sigmoid, "sigmoid")
erf = wrap_op(jax.scipy.special.erf, "erf")
erfinv = wrap_op(jax.scipy.special.erfinv, "erfinv")
gamma = wrap_op(lambda x: jnp.exp(jax.scipy.special.gammaln(x)), "gamma")
gammaln = wrap_op(jax.scipy.special.gammaln, "gammaln")
digamma = wrap_op(jax.scipy.special.digamma, "digamma")


# -- layers ------------------------------------------------------------------

def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True, **kw):
    args = (x, weight) if bias is None or no_bias else (x, weight, bias)

    def f(xx, ww, bb=None):
        return _nn.fully_connected(xx, ww, bb, no_bias=no_bias, flatten=flatten)

    return call(f, args, {}, name="fully_connected",
                attrs={"num_hidden": num_hidden, "no_bias": no_bias,
                       "flatten": flatten})


def convolution(data, weight, bias=None, kernel=None, stride=1, dilate=1,
                pad=0, num_filter=None, num_group=1, no_bias=False,
                layout=None, **kw):
    args = (data, weight) if bias is None or no_bias else (data, weight, bias)

    def f(x, w, b=None):
        return _nn.convolution(x, w, b, stride=stride, dilate=dilate, pad=pad,
                               num_group=num_group, no_bias=no_bias,
                               layout=layout)

    return call(f, args, {}, name="convolution",
                attrs={"kernel": kernel, "stride": stride, "dilate": dilate,
                       "pad": pad, "num_filter": num_filter,
                       "num_group": num_group, "no_bias": no_bias,
                       "layout": layout})


def deconvolution(data, weight, bias=None, kernel=None, stride=1, dilate=1,
                  pad=0, adj=0, num_filter=None, num_group=1, no_bias=False,
                  target_shape=None, layout=None, **kw):
    args = (data, weight) if bias is None or no_bias else (data, weight, bias)

    def f(x, w, b=None):
        return _nn.deconvolution(x, w, b, stride=stride, dilate=dilate, pad=pad,
                                 adj=adj, num_group=num_group, no_bias=no_bias,
                                 target_shape=target_shape, layout=layout)

    return call(f, args, {}, name="deconvolution",
                attrs={"kernel": kernel, "stride": stride, "dilate": dilate,
                       "pad": pad, "adj": adj, "num_filter": num_filter,
                       "num_group": num_group, "no_bias": no_bias,
                       "target_shape": target_shape, "layout": layout})


def pooling(data, kernel=1, pool_type="max", stride=None, pad=0,
            global_pool=False, count_include_pad=True,
            pooling_convention="valid", layout=None, **kw):
    return call(lambda x: _nn.pooling(x, kernel=kernel, pool_type=pool_type,
                                      stride=stride, pad=pad, global_pool=global_pool,
                                      count_include_pad=count_include_pad,
                                      pooling_convention=pooling_convention,
                                      layout=layout),
                (data,), {}, name="pooling",
                attrs={"kernel": kernel, "pool_type": pool_type,
                       "stride": stride, "pad": pad,
                       "global_pool": global_pool,
                       "pooling_convention": pooling_convention,
                       "layout": layout})


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               output_mean_var=False, axis=1, **kw):
    """Training mode updates running stats in place (see module docstring)."""
    training = autograd.is_training()
    if training and not use_global_stats:
        res = call(lambda xx, g, b, m, v: _nn.batch_norm_train(
            xx, g, b, m, v, eps=eps, momentum=momentum, axis=axis,
            fix_gamma=fix_gamma),
            (x, gamma, beta, running_mean, running_var), {},
            name="batch_norm",
            attrs={"eps": eps, "momentum": momentum, "axis": axis,
                   "fix_gamma": fix_gamma})
        out, new_mean, new_var = res
        running_mean._set_data(jax.lax.stop_gradient(new_mean._data))
        running_var._set_data(jax.lax.stop_gradient(new_var._data))
        if output_mean_var:
            return out, new_mean, new_var
        return out
    out = call(lambda xx, g, b, m, v: _nn.batch_norm_infer(
        xx, g, b, m, v, eps=eps, axis=axis, fix_gamma=fix_gamma),
        (x, gamma, beta, running_mean, running_var), {}, name="batch_norm",
        attrs={"eps": eps, "momentum": momentum, "axis": axis,
               "fix_gamma": fix_gamma})
    if output_mean_var:
        return out, running_mean, running_var
    return out


def layer_norm(x, gamma, beta, axis=-1, eps=1e-5, **kw):
    return call(lambda xx, g, b: _nn.layer_norm(xx, g, b, axis=axis, eps=eps),
                (x, gamma, beta), {}, name="layer_norm",
                attrs={"axis": axis, "eps": eps})


def group_norm(x, gamma, beta, num_groups=1, eps=1e-5, **kw):
    return call(lambda xx, g, b: _nn.group_norm(xx, g, b, num_groups=num_groups, eps=eps),
                (x, gamma, beta), {}, name="group_norm")


def instance_norm(x, gamma, beta, eps=1e-5, **kw):
    return call(lambda xx, g, b: _nn.instance_norm(xx, g, b, eps=eps),
                (x, gamma, beta), {}, name="instance_norm")


def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    return call(lambda x: _nn.lrn(x, alpha, beta, knorm, nsize), (data,), {}, name="lrn")


def dropout(data, p=0.5, mode="training", axes=(), **kw):
    if not autograd.is_training() and mode != "always":
        return data
    if p <= 0.0:
        return data
    key = next_key()
    return call(lambda x: _nn.dropout(x, key, p=p, axes=axes), (data,), {},
                name="dropout", attrs={"p": p})


# -- softmax -----------------------------------------------------------------

def softmax(data, axis=-1, length=None, temperature=None, use_length=False, **kw):
    if length is not None:
        return call(lambda x, l: _nn.softmax(x, axis=axis, temperature=temperature,
                                             length=l, use_length=True),
                    (data, length), {}, name="softmax")
    return call(lambda x: _nn.softmax(x, axis=axis, temperature=temperature),
                (data,), {}, name="softmax", attrs={"axis": axis})


def log_softmax(data, axis=-1, temperature=None, **kw):
    return call(lambda x: _nn.log_softmax(x, axis=axis, temperature=temperature),
                (data,), {}, name="log_softmax", attrs={"axis": axis})


def masked_softmax(data, mask, axis=-1, temperature=1.0, **kw):
    return call(lambda x, m: _nn.masked_softmax(x, m, axis=axis, temperature=temperature),
                (data, mask), {}, name="masked_softmax")


def masked_log_softmax(data, mask, axis=-1, temperature=1.0, **kw):
    return call(lambda x, m: _nn.masked_log_softmax(x, m, axis=axis, temperature=temperature),
                (data, mask), {}, name="masked_log_softmax")


def softmax_cross_entropy(logits, labels, sparse_label=True, axis=-1, **kw):
    return call(lambda lg, lb: _nn.softmax_cross_entropy(lg, lb, sparse_label=sparse_label,
                                                         axis=axis),
                (logits, labels), {}, name="softmax_cross_entropy")


# -- indexing / misc ---------------------------------------------------------

def embedding(data, weight, input_dim=None, output_dim=None, sparse_grad=False, **kw):
    return call(lambda i, w: _nn.embedding(i, w), (data, weight), {},
                name="embedding",
                attrs={"input_dim": input_dim, "output_dim": output_dim})


def one_hot(data, depth, on_value=1.0, off_value=0.0, dtype="float32", **kw):
    return call(lambda i: _nn.one_hot(i, depth, on_value, off_value, jnp.dtype(dtype)),
                (data,), {}, name="one_hot")


def pick(data, index, axis=-1, keepdims=False, mode="clip", **kw):
    return call(lambda x, i: _nn.pick(x, i, axis=axis, keepdims=keepdims, mode=mode),
                (data, index), {}, name="pick")


def topk(data, k=1, axis=-1, ret_typ="indices", is_ascend=False, dtype="float32", **kw):
    return call(lambda x: _nn.topk(x, k=k, axis=axis, ret_typ=ret_typ,
                                   is_ascend=is_ascend, dtype=jnp.dtype(dtype)),
                (data,), {}, name="topk")


def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0, **kw):
    if sequence_length is None:
        return call(lambda x: _nn.sequence_mask(x, None, False, value, axis),
                    (data,), {}, name="sequence_mask")
    return call(lambda x, l: _nn.sequence_mask(x, l, True, value, axis),
                (data, sequence_length), {}, name="sequence_mask")


def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0, **kw):
    if sequence_length is None:
        return call(lambda x: _nn.sequence_last(x, None, False, axis), (data,), {},
                    name="sequence_last")
    return call(lambda x, l: _nn.sequence_last(x, l, True, axis),
                (data, sequence_length), {}, name="sequence_last")


def space_to_depth(data, block_size, layout="NCHW", **kw):
    """Ref src/operator/tensor/matrix_op.cc:1042."""
    return call(lambda x: _nn.space_to_depth(x, block_size, layout),
                (data,), {}, name="space_to_depth",
                attrs={"block_size": block_size, "layout": layout})


def depth_to_space(data, block_size, layout="NCHW", **kw):
    """Ref src/operator/tensor/matrix_op.cc:985."""
    return call(lambda x: _nn.depth_to_space(x, block_size, layout),
                (data,), {}, name="depth_to_space",
                attrs={"block_size": block_size, "layout": layout})


def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0, **kw):
    if sequence_length is None:
        return call(lambda x: _nn.sequence_reverse(x, None, False, axis), (data,), {},
                    name="sequence_reverse")
    return call(lambda x, l: _nn.sequence_reverse(x, l, True, axis),
                (data, sequence_length), {}, name="sequence_reverse")


# -- shape helpers -----------------------------------------------------------

def reshape_like(lhs, rhs, **kw):
    return call(lambda a, b: a.reshape(b.shape), (lhs, rhs), {}, name="reshape_like")


def slice_like(data, shape_like, axes=None, **kw):
    def f(a, b):
        slices = [slice(None)] * a.ndim
        ax = axes if axes is not None else range(a.ndim)
        for i in ax:
            slices[i] = slice(0, b.shape[i])
        return a[tuple(slices)]

    return call(f, (data, shape_like), {}, name="slice_like")


def broadcast_like(lhs, rhs, **kw):
    return call(lambda a, b: jnp.broadcast_to(a, b.shape), (lhs, rhs), {},
                name="broadcast_like")


def shape_array(data, **kw):
    return NDArray(jnp.asarray(data.shape, dtype=jnp.int64))


def arange_like(data, start=0.0, step=1.0, axis=None, **kw):
    n = data.size if axis is None else data.shape[axis]
    return NDArray(jnp.arange(n, dtype=jnp.float32) * step + start)


def batch_dot(a, b, transpose_a=False, transpose_b=False, **kw):
    from ..ndarray import batch_dot as _bd

    return _bd(a, b, transpose_a=transpose_a, transpose_b=transpose_b)


def gather_nd(data, indices, **kw):
    def f(x, idx):
        idx = idx.astype(jnp.int32)
        return x[tuple(idx[i] for i in range(idx.shape[0]))]

    return call(f, (data, indices), {}, name="gather_nd")


def scatter_nd(data, indices, shape, **kw):
    def f(v, idx):
        idx = idx.astype(jnp.int32)
        out = jnp.zeros(shape, v.dtype)
        return out.at[tuple(idx[i] for i in range(idx.shape[0]))].set(v)

    return call(f, (data, indices), {}, name="scatter_nd")


def index_update(data, indices, val, **kw):
    return call(lambda x, i, v: x.at[tuple(i.astype(jnp.int32)[k] for k in range(i.shape[0]))].set(v),
                (data, indices, val), {}, name="index_update")


def index_add(data, indices, val, **kw):
    return call(lambda x, i, v: x.at[tuple(i.astype(jnp.int32)[k] for k in range(i.shape[0]))].add(v),
                (data, indices, val), {}, name="index_add")


def ctc_loss(pred, labels, pred_lengths=None, label_lengths=None, out=None):
    """Connectionist temporal classification loss (ref CTCLoss,
    src/operator/nn/ctc_loss.cc -> ops.ctc lax.scan forward-algorithm).
    pred: (N, T, C) logits; labels: (N, L) ints, 0 = blank/padding."""
    from ..ops import ctc as _ctc

    args = [pred, labels] + [x for x in (pred_lengths, label_lengths)
                             if x is not None]

    def f(p, lab, *rest):
        pl = rest[0] if pred_lengths is not None else None
        ll = rest[-1] if label_lengths is not None else None
        return _ctc.ctc_loss(p, lab, pred_lengths=pl, label_lengths=ll)

    return call(f, tuple(args), {}, name="ctc_loss", out=out, attrs={})


def l2_normalization(data, eps=1e-10, mode="instance", out=None):
    """L2-normalize (ref src/operator/l2_normalization.cc): 'instance'
    divides by the norm over all non-batch axes, 'channel' over axis 1,
    'spatial' over axes >= 2."""
    def f(x):
        if mode == "instance":
            axes = tuple(range(1, x.ndim))
        elif mode == "channel":
            axes = (1,)
        elif mode == "spatial":
            axes = tuple(range(2, x.ndim))
        else:
            raise MXNetError(f"unknown l2_normalization mode {mode!r}")
        return x / jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + eps)

    return call(f, (data,), {}, name="l2_normalization", out=out,
                attrs={"eps": eps, "mode": mode})


def smooth_l1(data, scalar=1.0, **kw):
    def f(x):
        s2 = scalar * scalar
        return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x,
                         jnp.abs(x) - 0.5 / s2)

    return call(f, (data,), {}, name="smooth_l1")


# -- AMP helpers (ref: src/operator/all_finite.cc) ---------------------------

def all_finite(data, init_output=True, **kw):
    """1.0 if every element finite else 0.0 — grad-scan for the loss scaler."""
    return call(lambda x: jnp.isfinite(x).all().astype(jnp.float32), (data,), {},
                name="all_finite")


def multi_all_finite(*arrays, num_arrays=None, init_output=True, **kw):
    return invoke(lambda *xs: jnp.stack([jnp.isfinite(x).all() for x in xs]).all()
                  .astype(jnp.float32), list(arrays), name="multi_all_finite")


def multi_sum_sq(*arrays, num_arrays=None, **kw):
    return invoke(lambda *xs: tuple(jnp.sum(jnp.square(x)) for x in xs),
                  list(arrays), name="multi_sum_sq")


def clip_by_global_norm(arrays, max_norm: float):
    """Utility used by trainers (gluon Trainer has clip_gradient per-array;
    global-norm clip is the transformer-era extra)."""
    total = jnp.sqrt(sum(jnp.sum(jnp.square(a._data)) for a in arrays))
    scale = jnp.minimum(1.0, max_norm / (total + 1e-12))
    for a in arrays:
        a._set_data(a._data * scale)
    return float(total)


# -- fused RNN (ref: src/operator/rnn.cc) ------------------------------------

def rnn(data, parameters, state, state_cell=None, mode="lstm",
        state_size=None, num_layers=1, bidirectional=False, p=0.0,
        state_outputs=True, projection_size=None, sequence_length=None,
        use_sequence_length=False, **kw):
    """Fused multi-layer RNN (ref src/operator/rnn.cc:297-421 → ops.rnn
    lax.scan kernel). Inter-layer dropout draws from the global RNG and is
    active only under autograd training mode, like the reference's mode-
    dependent dropout."""
    from ..ops import rnn as _rnn
    from ..random import next_key

    drop = p if (p > 0.0 and autograd.is_training() and num_layers > 1) else 0.0
    key = jax.random.key_data(next_key()) if drop > 0.0 else None

    inputs = [data, parameters, state]
    if mode == "lstm":
        if state_cell is None:
            raise MXNetError("lstm mode requires state_cell")
        inputs.append(state_cell)
    if use_sequence_length:
        if sequence_length is None:
            raise MXNetError("use_sequence_length=True requires sequence_length")
        inputs.append(sequence_length)

    def f(*raw):
        x, params, h0 = raw[0], raw[1], raw[2]
        i = 3
        c0 = None
        if mode == "lstm":
            c0 = raw[i]
            i += 1
        seq = raw[i] if use_sequence_length else None
        return _rnn.rnn_fused(x, params, h0, c0, mode=mode,
                              state_size=state_size, num_layers=num_layers,
                              bidirectional=bidirectional, p=drop,
                              projection_size=projection_size,
                              sequence_length=seq,
                              use_sequence_length=use_sequence_length,
                              dropout_key=key)

    res = call(f, tuple(inputs), {}, name="rnn",
               attrs={"mode": mode, "state_size": state_size,
                      "num_layers": num_layers,
                      "bidirectional": bidirectional, "p": p,
                      "projection_size": projection_size,
                      "use_sequence_length": use_sequence_length,
                      "state_outputs": True})
    if not state_outputs:
        return res[0]
    return res


# -- fused attention ---------------------------------------------------------
def flash_attention(query, key, value, mask=None, valid_length=None,
                    causal=False, scale=None, out=None):
    """Fused flash attention on (B, H, T, D) NDArrays (pallas on TPU).

    ``valid_length``: (B,) key lengths — stays on the pallas kernel
    (boolean ``mask`` falls back to the reference path).
    Ref counterpart: src/operator/contrib/transformer.cc interleaved-matmul
    attention kernels; redesigned as a blockwise online-softmax TPU kernel
    (ops/attention.py)."""
    from ..ops import attention as _att

    extras = [x for x in (mask, valid_length) if x is not None]
    has_mask = mask is not None

    def f(*raw):
        m = raw[3] if has_mask else None
        vl = raw[3 + has_mask] if valid_length is not None else None
        return _att.flash_attention(raw[0], raw[1], raw[2], mask=m,
                                    kv_valid_length=vl, causal=causal,
                                    scale=scale)

    return call(f, (query, key, value) + tuple(extras), {},
                name="flash_attention", out=out)


def cache_append(cache, new, lengths, out=None):
    """Append (B, H, T, D) rows into a (B, H, C, D) cache leaf at per-row
    ``lengths`` offsets (ops/attention.cache_append) — the decode path's
    prefill-write/step-append primitive (docs/serving.md)."""
    from ..ops import attention as _att

    return call(lambda c, n, l: _att.cache_append(c, n, l),
                (cache, new, lengths), {}, name="cache_append", out=out)


def cache_page_copy(dst, src, n_pages, src_start=0, dst_start=0, dst_row=0,
                    out=None):
    """Copy ``n_pages`` capacity-axis pages of a (B, H, C_s, D) KV cache
    into row ``dst_row`` of a (B_d, H, C_d, D) cache
    (ops/attention.cache_page_copy) — the device half of the
    prefill→decode cache shipment; ``n_pages`` static, offsets traced."""
    from ..ops import attention as _att

    return call(lambda d, s, r: _att.cache_page_copy(
        d, s, int(n_pages), src_start=int(src_start),
        dst_start=int(dst_start), dst_row=r),
        (dst, src, dst_row), {}, name="cache_page_copy", out=out)


def flash_attention_decode(query, kv, cache_len, scale=None,
                           k_scale=None, v_scale=None, out=None):
    """Decode-mode attention of (B, H, Tq, D) queries against a packed
    (B, H, C, 2*D) KV cache leaf (K‖V on the last axis) with per-row
    PRE-append ``cache_len`` (B,) — local query ``i`` attends cache
    positions ``<= cache_len + i``
    (ops/attention.flash_attention_decode; pallas on TPU).  With
    ``k_scale``/``v_scale`` (B, H, C, 1) the leaf is int8 per
    :func:`quantize_kv` and dequant happens inside the kernel."""
    from ..ops import attention as _att

    if k_scale is not None:
        return call(lambda q, c, l, ks, vs: _att.flash_attention_decode(
            q, c, l, scale=scale, k_scale=ks, v_scale=vs),
            (query, kv, cache_len, k_scale, v_scale), {},
            name="flash_attention_decode", out=out)
    return call(lambda q, c, l: _att.flash_attention_decode(
        q, c, l, scale=scale),
        (query, kv, cache_len), {},
        name="flash_attention_decode", out=out)


def quantize_kv(x, out=None):
    """Symmetric per-position int8 quantization of (..., D) K/V rows
    -> ``(q int8 (..., D), scale f32 (..., 1))`` — run BEFORE
    :func:`cache_append` into an int8 cache (ops/attention.quantize_kv;
    docs/precision.md)."""
    from ..ops import attention as _att

    return call(lambda a: _att.quantize_kv(a), (x,), {},
                name="quantize_kv", out=out)


def dequantize_kv(q, scale, dtype=None, out=None):
    """Inverse of :func:`quantize_kv` (ops/attention.dequantize_kv)."""
    import jax.numpy as _jnp

    from ..ops import attention as _att

    return call(lambda a, s: _att.dequantize_kv(
        a, s, dtype=_jnp.float32 if dtype is None else dtype),
        (q, scale), {}, name="dequantize_kv", out=out)


def multi_head_attention(query, key, value, num_heads, mask=None,
                         valid_length=None, causal=False, scale=None,
                         out=None):
    """(B, T, H*D) -> (B, T, H*D) fused multi-head attention.
    ``valid_length``: (B,) key lengths (pallas-friendly padding mask)."""
    from ..ops import attention as _att

    if query.shape[-1] % num_heads:
        raise MXNetError(f"embedding dim {query.shape[-1]} not divisible by "
                         f"num_heads {num_heads}")
    extras = [x for x in (mask, valid_length) if x is not None]
    has_mask = mask is not None

    def f(*raw):
        q, k, v = raw[0], raw[1], raw[2]
        m = raw[3] if has_mask else None
        vl = raw[3 + has_mask] if valid_length is not None else None
        b, tq, emb = q.shape
        tk = k.shape[1]
        d = emb // num_heads
        qh = q.reshape(b, tq, num_heads, d).transpose(0, 2, 1, 3)
        kh = k.reshape(b, tk, num_heads, d).transpose(0, 2, 1, 3)
        vh = v.reshape(b, tk, num_heads, d).transpose(0, 2, 1, 3)
        o = _att.flash_attention(qh, kh, vh, mask=m, kv_valid_length=vl,
                                 causal=causal, scale=scale)
        return o.transpose(0, 2, 1, 3).reshape(b, tq, emb)

    return call(f, (query, key, value) + tuple(extras), {},
                name="multi_head_attention", out=out,
                attrs={"num_heads": num_heads, "causal": causal,
                       "scale": scale, "has_mask": has_mask,
                       "has_valid_length": valid_length is not None})


# -- control flow ------------------------------------------------------------
from ..ops.control_flow import foreach, while_loop, cond  # noqa: E402


# -- bounding boxes / detection (ref src/operator/contrib/bounding_box.cc,
# multibox_*.cc, roi_align.cc) ----------------------------------------------
def box_iou(lhs, rhs, format="corner", out=None):
    from ..ops import boxes as _bx

    return call(lambda a, b: _bx.box_iou(a, b, fmt=format), (lhs, rhs), {},
                name="box_iou", out=out)


def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
            coord_start=2, score_index=1, id_index=-1,
            force_suppress=False, out=None):
    from ..ops import boxes as _bx

    return call(lambda d: _bx.box_nms(
        d, overlap_thresh=overlap_thresh, valid_thresh=valid_thresh,
        topk=topk, coord_start=coord_start, score_index=score_index,
        id_index=id_index, force_suppress=force_suppress), (data,), {},
        name="box_nms", out=out)


def roi_align(data, rois, pooled_size, spatial_scale=1.0, sample_ratio=2,
              out=None):
    from ..ops import boxes as _bx

    ps = pooled_size if isinstance(pooled_size, (tuple, list)) \
        else (pooled_size, pooled_size)
    return call(lambda d, r: _bx.roi_align(
        d, r, tuple(ps), spatial_scale=spatial_scale,
        sample_ratio=sample_ratio), (data, rois), {}, name="roi_align",
        out=out)


# -- spatial / contrib ops (ref src/operator/contrib/, bilinear_sampler.cc,
# spatial_transformer.cc, grid_generator.cc, count_sketch.cc) ----------------
def bilinear_sampler(data, grid, out=None):
    from ..ops import spatial as _sp

    return call(_sp.bilinear_sampler, (data, grid), {},
                name="bilinear_sampler", out=out)


def grid_generator(data, transform_type="affine", target_shape=None,
                   out=None):
    from ..ops import spatial as _sp

    return call(lambda d: _sp.grid_generator(
        d, transform_type=transform_type,
        target_shape=tuple(target_shape) if target_shape else None),
        (data,), {}, name="grid_generator", out=out)


def spatial_transformer(data, loc, target_shape, transform_type="affine",
                        sampler_type="bilinear", out=None):
    from ..ops import spatial as _sp

    return call(lambda d, l: _sp.spatial_transformer(
        d, l, tuple(target_shape), transform_type=transform_type,
        sampler_type=sampler_type), (data, loc), {},
        name="spatial_transformer", out=out)


def deformable_convolution(data, offset, weight, bias=None, kernel=(3, 3),
                           stride=(1, 1), pad=(0, 0), dilate=(1, 1),
                           num_filter=None, num_group=1,
                           num_deformable_group=1, no_bias=False,
                           mask=None, out=None):
    """v1 (ref contrib deformable_convolution) and, with ``mask``, the v2
    modulated variant — one wrapper so the gluon layers and npx agree."""
    from ..ops import spatial as _sp

    has_bias = bias is not None and not no_bias
    args = [data, offset, weight]
    if has_bias:
        args.append(bias)
    if mask is not None:
        args.append(mask)

    def f(d, o, w, *rest):
        rest = list(rest)
        b = rest.pop(0) if has_bias else None
        m = rest.pop(0) if mask is not None else None
        return _sp.deformable_convolution(
            d, o, w, b, kernel=kernel, stride=stride, pad=pad,
            dilate=dilate, num_filter=num_filter, num_group=num_group,
            num_deformable_group=num_deformable_group, mask=m)

    return call(f, tuple(args), {},
                name="deformable_convolution" if mask is None
                else "modulated_deformable_convolution", out=out)


def roi_pooling(data, rois, pooled_size, spatial_scale=1.0, out=None):
    """Max-pool ROI pooling (ref src/operator/roi_pooling.cc ROIPooling —
    not ROIAlign: rounded bounds, hard max bins)."""
    from ..ops import spatial as _sp

    ps = (pooled_size if isinstance(pooled_size, (tuple, list))
          else (pooled_size, pooled_size))
    return call(lambda d, r: _sp.roi_pooling(
        d, r, tuple(ps), spatial_scale=spatial_scale), (data, rois), {},
        name="roi_pooling", out=out)


def upsampling(*data, scale, sample_type="nearest", num_filter=0,
               multi_input_mode="concat", num_args=1, out=None):
    """UpSampling (ref src/operator/nn/upsampling.cc): nearest repeat or
    bilinear-deconvolution."""
    from ..ops import spatial as _sp

    return call(lambda *ds: _sp.upsampling(
        *ds, scale=int(scale), sample_type=sample_type,
        num_filter=num_filter, multi_input_mode=multi_input_mode,
        num_args=num_args), data, {}, name="upsampling", out=out)


def khatri_rao(*args, out=None):
    """Column-wise Khatri-Rao product (ref src/operator/contrib/krprod.cc
    khatri_rao): inputs (M_i, N) -> (prod M_i, N), column k is the
    Kronecker product of the k-th columns. One einsum per factor — XLA
    fuses the chain."""
    import jax.numpy as _jnp

    def f(*ms):
        acc = ms[0]
        for m in ms[1:]:
            acc = _jnp.einsum("ik,jk->ijk", acc, m).reshape(
                acc.shape[0] * m.shape[0], acc.shape[1])
        return acc

    return call(f, args, {}, name="khatri_rao", out=out)


def sample_unique_zipfian(range_max, shape=None, out=None):
    """Sample WITHOUT replacement from an approximate Zipfian (log-uniform)
    distribution over [0, range_max) (ref src/operator/random/
    unique_sample_op.cc _sample_unique_zipfian; the sampled-softmax
    helper). Returns (samples int64 (batch, n), num_tries int64 (batch,)).
    Host-side eager op — rejection counts are data-dependent."""
    import numpy as _onp
    from ..ndarray import NDArray as _ND
    from ..random import next_key

    if shape is None:
        raise MXNetError("sample_unique_zipfian requires shape=(batch, n)")
    batch, n = (shape if isinstance(shape, (tuple, list)) else (1, shape))
    if n > range_max:
        raise MXNetError(
            f"cannot draw {n} unique values from range_max={range_max}")
    # fold the global generator state into a host seed (stateful draw)
    import jax.random as _jr

    rs = _onp.random.RandomState(
        int(_jr.randint(next_key(), (), 0, 2 ** 31 - 1)))
    log_range = _onp.log(range_max + 1)
    samples = _onp.zeros((batch, n), _onp.int64)
    tries = _onp.zeros((batch,), _onp.int64)
    for b in range(batch):
        seen = set()
        cnt = 0
        while len(seen) < n:
            v = int(_onp.exp(rs.rand() * log_range)) - 1
            cnt += 1
            if 0 <= v < range_max and v not in seen:
                seen.add(v)
        samples[b] = _onp.fromiter(seen, _onp.int64, len(seen))
        tries[b] = cnt
    import jax.numpy as _jnp

    return _ND(_jnp.asarray(samples)), _ND(_jnp.asarray(tries))


def count_sketch(data, h, s, out_dim, out=None):
    from ..ops import spatial as _sp

    return call(lambda d, hh, ss: _sp.count_sketch(d, hh, ss, int(out_dim)),
                (data, h, s), {}, name="count_sketch", out=out)


def adaptive_max_pool2d(data, output_size, out=None):
    from ..ops import spatial as _sp

    return call(lambda x: _sp.adaptive_max_pool2d(x, output_size), (data,),
                {}, name="adaptive_max_pool2d", out=out)


def adaptive_avg_pool1d(data, output_size, out=None):
    from ..ops import spatial as _sp

    return call(lambda x: _sp.adaptive_avg_pool1d(x, output_size), (data,),
                {}, name="adaptive_avg_pool1d", out=out)


def adaptive_avg_pool3d(data, output_size, out=None):
    from ..ops import spatial as _sp

    return call(lambda x: _sp.adaptive_avg_pool3d(x, output_size), (data,),
                {}, name="adaptive_avg_pool3d", out=out)


# -- dynamic-shape recipes (SURVEY §7 hard part 3) ---------------------------
# XLA needs static shapes; the reference's data-dependent ops (BooleanMask,
# np.unique) map onto pad-to-static recipes: fix the output size up front,
# results are compacted to the front and padded with fill, and the true
# count comes back alongside. Eager callers can keep plain np.unique /
# fancy indexing; these are the jit-safe forms.

def boolean_mask(data, mask, axis=0, size=None, fill_value=0, out=None):
    """Ref: src/operator/contrib/boolean_mask.cc. Rows of ``data`` where
    ``mask`` is nonzero, compacted to the front. Under jit pass ``size``
    (static output length, default len(mask)); returns (selected, count)
    where rows past count hold fill_value."""
    import jax.numpy as _jnp

    def f(d, m):
        mb = m.astype(bool).reshape(-1)
        n = mb.shape[0]
        k = n if size is None else int(size)
        d2 = _jnp.moveaxis(d, axis, 0)
        # stable compaction: position of each selected row in the output
        pos = _jnp.cumsum(mb) - 1
        src = _jnp.where(mb, pos, n)  # non-selected scatter to a dump row
        gathered = _jnp.full((k + 1,) + d2.shape[1:], fill_value, d2.dtype)
        gathered = gathered.at[_jnp.clip(src, 0, k)].set(
            _jnp.where(mb.reshape((-1,) + (1,) * (d2.ndim - 1)), d2,
                       gathered[-1]), mode="drop")
        outv = _jnp.moveaxis(gathered[:k], 0, axis)
        return outv, _jnp.sum(mb).astype(_jnp.int32)

    return call(f, (data, mask), {}, name="boolean_mask", out=out)


def unique_padded(data, size=None, fill_value=0, out=None):
    """jit-safe np.unique: sorted unique values padded with fill_value to a
    static ``size`` (default data.size); returns (values, count). Uses the
    jnp.unique size= recipe (the reference's np.unique is host-side and
    dynamically shaped — src/operator/numpy/np_unique_op.cc)."""
    import jax.numpy as _jnp

    def f(d):
        k = d.size if size is None else int(size)
        vals = _jnp.unique(d.reshape(-1), size=k, fill_value=fill_value)
        # count = number of distinct values actually present
        flat = _jnp.sort(d.reshape(-1))
        distinct = _jnp.concatenate([_jnp.ones((1,), bool),
                                     flat[1:] != flat[:-1]])
        return vals, _jnp.sum(distinct).astype(_jnp.int32)

    return call(f, (data,), {}, name="unique_padded", out=out)


# -- transformer helpers (ref src/operator/contrib/transformer.cc) -----------

def div_sqrt_dim(data, **kw):
    from ..ops import transformer as _tr

    return call(_tr.div_sqrt_dim, (data,), {}, name="div_sqrt_dim")


def interleaved_matmul_selfatt_qk(queries_keys_values, heads, **kw):
    from ..ops import transformer as _tr

    return call(lambda x: _tr.interleaved_matmul_selfatt_qk(x, heads),
                (queries_keys_values,), {},
                name="interleaved_matmul_selfatt_qk",
                attrs={"heads": heads})


def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads,
                                      **kw):
    from ..ops import transformer as _tr

    return call(lambda x, a: _tr.interleaved_matmul_selfatt_valatt(
        x, a, heads), (queries_keys_values, attention), {},
        name="interleaved_matmul_selfatt_valatt", attrs={"heads": heads})


def interleaved_matmul_encdec_qk(queries, keys_values, heads, **kw):
    from ..ops import transformer as _tr

    return call(lambda q, kv: _tr.interleaved_matmul_encdec_qk(q, kv, heads),
                (queries, keys_values), {},
                name="interleaved_matmul_encdec_qk", attrs={"heads": heads})


def interleaved_matmul_encdec_valatt(keys_values, attention, heads, **kw):
    from ..ops import transformer as _tr

    return call(lambda kv, a: _tr.interleaved_matmul_encdec_valatt(
        kv, a, heads), (keys_values, attention), {},
        name="interleaved_matmul_encdec_valatt", attrs={"heads": heads})


def sldwin_atten_score(query, key, dilation, w, symmetric=True, **kw):
    from ..ops import transformer as _tr

    return call(lambda q, k, d: _tr.sldwin_atten_score(q, k, d, w, symmetric),
                (query, key, dilation), {}, name="sldwin_atten_score",
                attrs={"w": w, "symmetric": symmetric})


def sldwin_atten_mask_like(score, dilation, valid_length, w, symmetric=True,
                           **kw):
    from ..ops import transformer as _tr

    return call(lambda s, d, v: _tr.sldwin_atten_mask_like(
        s, d, v, w, symmetric), (score, dilation, valid_length), {},
        name="sldwin_atten_mask_like", attrs={"w": w, "symmetric": symmetric})


def sldwin_atten_context(score, value, dilation, w, symmetric=True, **kw):
    from ..ops import transformer as _tr

    return call(lambda s, v, d: _tr.sldwin_atten_context(
        s, v, d, w, symmetric), (score, value, dilation), {},
        name="sldwin_atten_context", attrs={"w": w, "symmetric": symmetric})


# -- contrib tail (ref src/operator/contrib/) --------------------------------

def box_encode(samples, matches, anchors, refs, means=None, stds=None, **kw):
    from ..ops import boxes as _bx

    return call(lambda s, m, a, r: _bx.box_encode(s, m, a, r, means, stds),
                (samples, matches, anchors, refs), {}, name="box_encode")


def box_decode(data, anchors, std0=0.1, std1=0.1, std2=0.2, std3=0.2,
               clip=-1.0, format="corner", **kw):  # noqa: A002
    from ..ops import boxes as _bx

    return call(lambda d, a: _bx.box_decode(d, a, std0, std1, std2, std3,
                                            clip, format),
                (data, anchors), {}, name="box_decode")


def bipartite_matching(score, threshold=1e-12, is_ascend=False, topk=-1,
                       **kw):
    from ..ops import boxes as _bx

    return call(lambda s: _bx.bipartite_matching(s, threshold, is_ascend,
                                                 topk),
                (score,), {}, name="bipartite_matching",
                attrs={"threshold": threshold, "is_ascend": is_ascend,
                       "topk": topk})


def quadratic(data, a=0.0, b=0.0, c=0.0, **kw):
    """f(x) = a x^2 + b x + c (ref contrib/quadratic_op.cc — the tutorial
    custom-op example, kept for parity)."""
    return call(lambda x: a * x * x + b * x + c, (data,), {},
                name="quadratic", attrs={"a": a, "b": b, "c": c})


def index_copy(old_tensor, index_vector, new_tensor, **kw):
    """Copy new_tensor rows into old_tensor at index positions
    (ref contrib/index_copy.cc:166)."""
    return call(lambda o, i, n: o.at[i.astype(jnp.int32)].set(n),
                (old_tensor, index_vector, new_tensor), {},
                name="index_copy")


def index_array(data, axes=None, **kw):
    """Per-element N-D index tensor (ref contrib/index_array.cc): output
    (\\*data.shape, len(axes) or ndim) of int64 coordinates."""
    def f(x):
        idx = jnp.stack(jnp.meshgrid(
            *[jnp.arange(d) for d in x.shape], indexing="ij"), axis=-1)
        if axes is not None:
            idx = idx[..., tuple(axes)]
        return idx.astype(jnp.int32)
    return call(f, (data,), {}, name="index_array")


def edge_id(data, u, v, **kw):
    """CSR edge-id lookup (ref contrib/dgl_graph.cc _contrib_edge_id
    semantics): data is a CSRNDArray adjacency; returns data[u[i], v[i]]
    per pair, -1 where absent."""
    from ..ndarray.sparse import CSRNDArray

    if not isinstance(data, CSRNDArray):
        raise MXNetError("edge_id expects a CSRNDArray adjacency")
    dense = data.todense()
    def f(dd, uu, vv):
        vals = dd[uu.astype(jnp.int32), vv.astype(jnp.int32)]
        return jnp.where(vals != 0, vals, -1.0)
    return call(f, (dense, u, v), {}, name="edge_id")


def getnnz(data, axis=None, **kw):
    """Number of stored values in a sparse matrix (ref
    contrib/nnz.cc _contrib_getnnz)."""
    from ..ndarray.sparse import CSRNDArray

    if isinstance(data, CSRNDArray):
        if axis is None:
            return int(data.data.shape[0])
        dense = data.todense()
    else:
        dense = data
    def f(x):
        nz = (x != 0)
        return jnp.sum(nz, axis=axis).astype(jnp.int32) if axis is not None \
            else jnp.sum(nz).astype(jnp.int32)
    return call(f, (dense,), {}, name="getnnz")


def batch_norm_with_relu(x, gamma, beta, running_mean, running_var,
                         eps=1e-5, momentum=0.9, fix_gamma=False,
                         use_global_stats=False, axis=1, **kw):
    """BatchNorm fused with ReLU (ref contrib/batch_norm_relu.cc).

    Training mode dispatches to the single-pass Pallas statistics +
    normalize+relu kernels (``mx.kernels.bn_act``, docs/kernels.md) when
    the kernels layer is active; otherwise — and always in inference
    mode, where XLA fuses the folded affine + relu on its own — the
    composed reference path runs.  Moving stats update in place like
    ``batch_norm``."""
    training = autograd.is_training()
    if training and not use_global_stats:
        res = call(lambda xx, g, b, m, v: _nn.batch_norm_act_train(
            xx, g, b, m, v, eps=eps, momentum=momentum, axis=axis,
            fix_gamma=fix_gamma, act_type="relu"),
            (x, gamma, beta, running_mean, running_var), {},
            name="batch_norm_with_relu",
            attrs={"eps": eps, "momentum": momentum, "axis": axis,
                   "fix_gamma": fix_gamma})
        out, new_mean, new_var = res
        running_mean._set_data(jax.lax.stop_gradient(new_mean._data))
        running_var._set_data(jax.lax.stop_gradient(new_var._data))
        return out
    return relu(batch_norm(x, gamma, beta, running_mean, running_var,
                           eps=eps, momentum=momentum, fix_gamma=fix_gamma,
                           use_global_stats=use_global_stats, axis=axis,
                           **kw))


def dynamic_reshape(data, shape_like, **kw):
    """Reshape data to shape_like's shape (ref contrib/dynamic_reshape).
    Under jit, shapes are static at trace time, so this is reshape_like."""
    return reshape_like(data, shape_like)


def col2im(data, output_size, kernel, stride=1, dilate=1, pad=0, **kw):
    """Fold im2col columns back to an image, summing overlaps
    (ref src/operator/nn/im2col.cc col2im)."""
    import itertools

    def f(x):
        n_sp = len(kernel) if isinstance(kernel, (tuple, list)) else 2
        k = kernel if isinstance(kernel, (tuple, list)) else (kernel,) * n_sp
        st = stride if isinstance(stride, (tuple, list)) else (stride,) * n_sp
        d = dilate if isinstance(dilate, (tuple, list)) else (dilate,) * n_sp
        p = pad if isinstance(pad, (tuple, list)) else (pad,) * n_sp
        out_size = (output_size if isinstance(output_size, (tuple, list))
                    else (output_size,) * n_sp)
        N = x.shape[0]
        import numpy as _np

        kprod = 1
        for kk in k:
            kprod *= kk
        C = x.shape[1] // kprod
        padded = [out_size[i] + 2 * p[i] for i in range(n_sp)]
        col_sp = [(padded[i] - (d[i] * (k[i] - 1) + 1)) // st[i] + 1
                  for i in range(n_sp)]
        img = jnp.zeros((N, C) + tuple(padded), x.dtype)
        cols = x.reshape((N, C, kprod) + tuple(col_sp))
        for ki, off in enumerate(itertools.product(*[range(kk) for kk in k])):
            sl = [slice(None), slice(None)]
            for i in range(n_sp):
                start = off[i] * d[i]
                stop = start + st[i] * (col_sp[i] - 1) + 1
                sl.append(slice(start, stop, st[i]))
            img = img.at[tuple(sl)].add(cols[:, :, ki])
        unpad = [slice(None), slice(None)] + \
            [slice(p[i], p[i] + out_size[i]) for i in range(n_sp)]
        return img[tuple(unpad)]
    return call(f, (data,), {}, name="col2im")


def hawkesll(mu, alpha, beta, state, lags, marks, valid_length, max_time,
             **kw):
    """Marked Hawkes process log-likelihood
    (ref contrib/hawkes_ll-inl.h _contrib_hawkesll); lax.scan over events."""
    from ..ops import hawkes as _hk

    return call(_hk.hawkesll,
                (mu, alpha, beta, state, lags, marks, valid_length,
                 max_time), {}, name="hawkesll")


def rroi_align(data, rois, pooled_size, spatial_scale=1.0,
               sampling_ratio=-1, **kw):
    """Rotated ROI align (ref contrib/rroi_align.cc _contrib_RROIAlign)."""
    import builtins as _bi
    import math as _math

    import numpy as _np_host

    from ..ops import spatial as _sp

    grid_sizes = None
    if sampling_ratio <= 0:
        # reference grids depend on concrete roi sizes: read them eagerly
        # HERE (outside any trace) so the traced fn stays differentiable
        ph_, pw_ = (pooled_size if isinstance(pooled_size, (tuple, list))
                    else (pooled_size, pooled_size))
        rois_h = _np_host.asarray(
            rois.asnumpy() if isinstance(rois, NDArray) else rois)
        grid_sizes = [
            (_bi.max(int(_math.ceil(_bi.max(r[4] * spatial_scale, 1.0)
                                    / ph_)), 1),
             _bi.max(int(_math.ceil(_bi.max(r[3] * spatial_scale, 1.0)
                                    / pw_)), 1))
            for r in rois_h]

    return call(lambda d, r: _sp.rroi_align(d, r, pooled_size,
                                            spatial_scale, sampling_ratio,
                                            _grid_sizes=grid_sizes),
                (data, rois), {}, name="rroi_align",
                attrs={"pooled_size": list(pooled_size)
                       if isinstance(pooled_size, (tuple, list))
                       else pooled_size,
                       "spatial_scale": spatial_scale,
                       "sampling_ratio": sampling_ratio})


# ---------------------------------------------------------------------------
# npx utility surface (ref python/mxnet/numpy_extension/utils.py + random.py
# + __init__.py re-exports)
# ---------------------------------------------------------------------------

def seed(seed_value):
    """Seed the global PRNG (ref numpy_extension/random.py seed)."""
    from .. import random as _random

    _random.seed(seed_value)


def from_numpy(ndarray, zero_copy=True):
    """Wrap a host numpy array as an NDArray (ref utils.py from_numpy;
    the device copy makes zero_copy advisory here)."""
    return NDArray(jnp.asarray(ndarray))


def from_dlpack(ext):
    """Ref utils.py from_dlpack."""
    from ..dlpack import from_dlpack as _impl

    return _impl(ext)


def to_dlpack_for_read(data):
    """Ref utils.py to_dlpack_for_read."""
    from ..dlpack import to_dlpack_for_read as _impl

    return _impl(data)


def to_dlpack_for_write(data):
    """Ref utils.py to_dlpack_for_write."""
    from ..dlpack import to_dlpack_for_write as _impl

    return _impl(data)


def savez(file, *args, **kwds):
    """Save arrays into an .npz (ref utils.py savez/save compat): NDArray
    values are converted to host numpy first."""
    import numpy as _onp

    def host(v):
        return v.asnumpy() if isinstance(v, NDArray) else _onp.asarray(v)

    _onp.savez(file, *[host(a) for a in args],
               **{k: host(v) for k, v in kwds.items()})


def _batch_tuple(batch_shape):
    """int-or-tuple batch_shape normalizer (same contract as
    numpy/random.py _shape)."""
    if batch_shape is None:
        return ()
    if isinstance(batch_shape, int):
        return (batch_shape,)
    return tuple(batch_shape)


def bernoulli(prob=None, logit=None, size=None, dtype=None, device=None,
              out=None):
    """Ref numpy_extension/random.py bernoulli (prob XOR logit)."""
    from ..numpy import random as _nprandom

    if (prob is None) == (logit is None):
        raise MXNetError("bernoulli: exactly one of prob/logit required")
    res = _nprandom.bernoulli(prob, size=size, dtype=dtype, logit=logit,
                              device=device)
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def normal_n(loc=0.0, scale=1.0, batch_shape=None, dtype=None, device=None):
    """Ref numpy_extension/random.py normal_n: batch_shape PREPENDS the
    broadcast parameter shape."""
    from ..numpy import random as _nprandom

    shape = _batch_tuple(batch_shape) + jnp.broadcast_shapes(
        jnp.shape(loc), jnp.shape(scale))
    return _nprandom.normal(loc, scale, size=shape, dtype=dtype,
                            device=device)


def uniform_n(low=0.0, high=1.0, batch_shape=None, dtype=None, device=None):
    """Ref numpy_extension/random.py uniform_n."""
    from ..numpy import random as _nprandom

    shape = _batch_tuple(batch_shape) + jnp.broadcast_shapes(
        jnp.shape(low), jnp.shape(high))
    return _nprandom.uniform(low, high, size=shape, dtype=dtype,
                             device=device)


__all__ += ["seed", "from_numpy", "from_dlpack", "to_dlpack_for_read",
            "to_dlpack_for_write", "savez", "bernoulli", "normal_n",
            "uniform_n"]

from . import random  # noqa: E402  (npx.random namespace, ref npx/random.py)
from . import image  # noqa: E402  (npx.image namespace, ref npx/image.py)

__all__ += ["random", "image"]
