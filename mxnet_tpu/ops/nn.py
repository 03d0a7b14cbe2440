"""Dense NN primitives as pure jax functions — the kernel corpus.

TPU-native replacement for src/operator/nn/ (32.2k LoC of CUDA/cuDNN/MKL-DNN
kernels, SURVEY.md §2.2): convolution/deconvolution → lax.conv_general_dilated
(lowers onto the MXU), pooling → lax.reduce_window, norms/softmax →
jnp reductions that XLA fuses, fully_connected → dot_general.

Layouts: the reference exposes a ``layout`` parameter on conv/pool
(src/operator/nn/convolution-inl.h, mshadow layout enums); default is
channel-first NCHW/OIHW, with NHWC/NWC/NDHWC as the channel-last variants
(weights then OHWI-style, matching the reference's mshadow mapping).
Channel-last is the TPU-preferred layout: the channel dim maps onto the
128-lane minor tile, so bf16 convs feed the MXU without the layout-transpose
pairs XLA otherwise inserts around NCHW convs.

All functions here take/return raw jax arrays; NDArray lifting happens in
numpy_extension (npx).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

IntOrTuple = Union[int, Tuple[int, ...]]


def _tuple(v: IntOrTuple, n: int) -> Tuple[int, ...]:
    import numbers

    if isinstance(v, numbers.Integral):  # incl. numpy integer scalars
        return (int(v),) * n
    t = tuple(v)
    if len(t) == 1:
        return t * n
    if len(t) != n:
        raise MXNetError(f"expected length-{n} tuple, got {t}")
    return t


# -- linear ------------------------------------------------------------------

def fully_connected(x, weight, bias=None, num_hidden: Optional[int] = None,
                    no_bias: bool = False, flatten: bool = True):
    """Ref: src/operator/nn/fully_connected.cc:251-335. y = x·Wᵀ + b.

    flatten=True collapses all but the batch dim (reference semantics)."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        y = y + bias
    return y


# -- convolution -------------------------------------------------------------

_CHANNEL_FIRST = {3: "NCW", 4: "NCHW", 5: "NCDHW"}
_CHANNEL_LAST = {3: "NWC", 4: "NHWC", 5: "NDHWC"}


def _norm_layout(layout: Optional[str], ndim: int) -> str:
    """Validate/default a conv layout string for an ndim-d input."""
    if ndim not in _CHANNEL_FIRST:
        raise MXNetError(f"convolution expects 3-5d input, got {ndim}d")
    if layout is None:
        return _CHANNEL_FIRST[ndim]
    layout = str(layout)
    if layout not in (_CHANNEL_FIRST[ndim], _CHANNEL_LAST[ndim]):
        raise MXNetError(
            f"unsupported layout {layout!r} for {ndim}d convolution; "
            f"expected {_CHANNEL_FIRST[ndim]} or {_CHANNEL_LAST[ndim]}")
    return layout


def _conv_dn(layout: str):
    """lhs/rhs/out dimension-number specs for a layout string.

    Channel-first NCHW pairs with OIHW weights, channel-last NHWC with OHWI —
    the reference's mshadow ConvertLayout mapping (convolution-inl.h)."""
    spatial = layout.replace("N", "").replace("C", "")
    if layout[1] == "C":  # channel-first
        return (layout, "OI" + spatial, layout)
    return (layout, "O" + spatial + "I", layout)


def _bias_shape(layout: str):
    """Broadcast shape placing the channel dim per layout."""
    return tuple(-1 if c == "C" else 1 for c in layout)


def convolution(x, weight, bias=None, kernel=None, stride=1, dilate=1, pad=0,
                num_filter: Optional[int] = None, num_group: int = 1,
                no_bias: bool = False, layout: Optional[str] = None):
    """N-D convolution (ref: src/operator/nn/convolution.cc).

    layout selects NCHW/OIHW (reference default) or NHWC/OHWI (TPU-preferred
    channel-last). Grouped conv (num_group>1) maps to feature_group_count —
    depthwise convs stay a single fused XLA op instead of the reference's
    special depthwise kernel (src/operator/nn/depthwise_convolution-inl.h)."""
    n = x.ndim - 2
    layout = _norm_layout(layout, x.ndim)
    strides = _tuple(stride, n)
    dilation = _tuple(dilate, n)
    padding = [(p, p) for p in _tuple(pad, n)]
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, _conv_dn(layout))
    y = lax.conv_general_dilated(
        x, weight, window_strides=strides, padding=padding,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=None)
    if bias is not None and not no_bias:
        y = y + bias.reshape(_bias_shape(layout))
    return y


def deconvolution(x, weight, bias=None, kernel=None, stride=1, dilate=1, pad=0,
                  adj=0, num_filter: Optional[int] = None, num_group: int = 1,
                  no_bias: bool = False, target_shape=None,
                  layout: Optional[str] = None):
    """Transposed convolution (ref: src/operator/nn/deconvolution.cc).

    Implemented as the gradient of convolution: lax.conv_transpose with
    IOHW-style kernel (reference stores weight as (in, out/group, *k)).
    Channel-last layouts are handled by transposing around the channel-first
    kernel (deconv is off the model-zoo hot path; XLA fuses the transposes)."""
    lay = _norm_layout(layout, x.ndim)
    if lay[1] != "C":  # channel-last: NHWC x, IHWO-style weight
        perm = (0, x.ndim - 1) + tuple(range(1, x.ndim - 1))
        wperm = (0, weight.ndim - 1) + tuple(range(1, weight.ndim - 1))
        y = deconvolution(jnp.transpose(x, perm), jnp.transpose(weight, wperm),
                          bias, kernel=kernel, stride=stride, dilate=dilate,
                          pad=pad, adj=adj, num_filter=num_filter,
                          num_group=num_group, no_bias=no_bias,
                          target_shape=target_shape)
        inv = (0,) + tuple(range(2, x.ndim)) + (1,)
        return jnp.transpose(y, inv)
    n = x.ndim - 2
    strides = _tuple(stride, n)
    dilation = _tuple(dilate, n)
    pads = _tuple(pad, n)
    adjs = _tuple(adj, n)
    kshape = weight.shape[2:]
    # output padding semantics: out = (in-1)*s - 2p + dilate*(k-1) + 1 + adj
    padding = []
    for i in range(n):
        eff_k = dilation[i] * (kshape[i] - 1) + 1
        lo = eff_k - 1 - pads[i]
        hi = eff_k - 1 - pads[i] + adjs[i]
        padding.append((lo, hi))
    x_dilated_dn = lax.conv_dimension_numbers(
        x.shape, (weight.shape[1] * num_group, weight.shape[0] // num_group) + kshape,
        _conv_dn(_CHANNEL_FIRST[x.ndim]))
    # flip spatial dims + swap in/out channels → conv on lhs-dilated input
    w = jnp.flip(weight, axis=tuple(range(2, weight.ndim)))
    if num_group > 1:
        w = w.reshape((num_group, weight.shape[0] // num_group) + weight.shape[1:])
        w = jnp.moveaxis(w, 2, 1).reshape(
            (num_group * weight.shape[1], weight.shape[0] // num_group) + kshape)
    else:
        w = jnp.swapaxes(w, 0, 1)
    y = lax.conv_general_dilated(
        x, w, window_strides=(1,) * n, padding=padding,
        lhs_dilation=strides, rhs_dilation=dilation,
        dimension_numbers=x_dilated_dn, feature_group_count=num_group)
    if bias is not None and not no_bias:
        y = y + bias.reshape((1, -1) + (1,) * n)
    return y


# -- pooling -----------------------------------------------------------------

def pooling(x, kernel=1, pool_type: str = "max", stride=None, pad=0,
            global_pool: bool = False, count_include_pad: bool = True,
            pooling_convention: str = "valid", layout=None):
    """Max/avg/lp pooling (ref: src/operator/nn/pooling.cc); layout selects
    channel-first (NCHW, reference default) or channel-last (NHWC)."""
    n = x.ndim - 2
    lay = _norm_layout(layout, x.ndim)
    last = lay[1] != "C"  # channel-last
    if global_pool:
        axes = tuple(range(1, x.ndim - 1)) if last else tuple(range(2, x.ndim))
        if pool_type == "max":
            return jnp.max(x, axis=axes, keepdims=True)
        return jnp.mean(x, axis=axes, keepdims=True)
    ks = _tuple(kernel, n)
    strides = _tuple(stride if stride is not None else ks, n)
    pads = _tuple(pad, n)
    window = (1,) + ks + (1,) if last else (1, 1) + ks
    strides_f = (1,) + strides + (1,) if last else (1, 1) + strides
    if pooling_convention == "full":
        # ceil-mode: pad high edge enough that ceil division is covered
        sp = tuple((p, p + s - 1) for p, s in zip(pads, strides))
    else:
        sp = tuple((p, p) for p in pads)
    padding = ((0, 0),) + sp + ((0, 0),) if last else ((0, 0), (0, 0)) + sp
    if pool_type == "max":
        # float init stays the -inf PYTHON literal: jax pattern-matches it
        # into reduce_window_max (the primitive with a vjp rule) — a jnp
        # array init would fall back to generic reduce_window and kill
        # autodiff. int pooling (the quantized int8 path) needs the init
        # as a numpy scalar of the exact dtype or it weak-types to int32.
        if jnp.issubdtype(x.dtype, jnp.floating):
            init = -jnp.inf
        else:
            init = x.dtype.type(jnp.iinfo(x.dtype).min)
        return lax.reduce_window(x, init, lax.max, window, strides_f, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(x, 0.0, lax.add, window, strides_f, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in ks:
                denom *= k
            return s / denom
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides_f, padding)
        return s / cnt
    if pool_type == "lp":
        p = 2.0
        s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides_f, padding)
        return s ** (1.0 / p)
    raise MXNetError(f"unknown pool_type {pool_type}")


def adaptive_avg_pool2d(x, output_size):
    """Ref: src/operator/contrib/adaptive_avg_pooling.cc."""
    out_h, out_w = _tuple(output_size, 2)
    n, c, h, w = x.shape
    # split input into out_h x out_w cells via interpolated mean — exact for
    # divisible sizes, matches reference's integral-image approach otherwise
    x = x.reshape(n, c, out_h, h // out_h, out_w, w // out_w) if h % out_h == 0 and w % out_w == 0 \
        else _adaptive_pool_general(x, out_h, out_w)
    if x.ndim == 6:
        return x.mean(axis=(3, 5))
    return x


def _adaptive_pool_general(x, out_h, out_w):
    n, c, h, w = x.shape
    ys = jnp.linspace(0, h, out_h + 1)
    xs = jnp.linspace(0, w, out_w + 1)
    rows = []
    for i in range(out_h):
        cols = []
        y0, y1 = int(ys[i]), int(jnp.ceil(ys[i + 1]))
        for j in range(out_w):
            x0, x1 = int(xs[j]), int(jnp.ceil(xs[j + 1]))
            cols.append(x[:, :, y0:y1, x0:x1].mean(axis=(2, 3)))
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2)


# -- normalization -----------------------------------------------------------

def batch_norm_train(x, gamma, beta, moving_mean, moving_var,
                     eps: float = 1e-5, momentum: float = 0.9, axis: int = 1,
                     fix_gamma: bool = False, use_global_stats: bool = False):
    """Training-mode BN; returns (out, new_moving_mean, new_moving_var).

    Ref: src/operator/nn/batch_norm.cc — the reference mutates moving stats
    in-place inside the kernel; we return them functionally and the npx layer
    rebinds (visible to jit tracing via the mutation-watcher protocol)."""
    axis = axis % x.ndim  # negative axis (e.g. -1) must match positive ids
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    axes = tuple(i for i in range(x.ndim) if i != axis)
    stat_dt = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(stat_dt)
    if use_global_stats:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    else:
        mean = jnp.mean(xf, axis=axes)
        var = jnp.var(xf, axis=axes)
        # running stats keep their own dtype (f32 master buffers): the f32
        # blend would otherwise silently promote bf16 stat buffers, changing
        # checkpoint dtypes and the jit input signature
        new_mean = (moving_mean * momentum
                    + mean * (1 - momentum)).astype(moving_mean.dtype)
        new_var = (moving_var * momentum
                   + var * (1 - momentum)).astype(moving_var.dtype)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    # statistics in f32 for numeric safety, but the big activation tensor
    # is touched ONLY in its own dtype: fold (mean, var, gamma, beta) into
    # per-channel scale/shift f32 vectors, cast those C-sized vectors down,
    # apply. Under bf16 compute this keeps every NHWC intermediate bf16 —
    # mixing f32 scalars into the affine would promote the whole tensor to
    # f32 and double HBM traffic on an HBM-bound step (TPU perf note).
    inv = lax.rsqrt(var + eps)
    scale = (gamma * inv).astype(x.dtype).reshape(shape)
    shift = (beta - mean * gamma * inv).astype(x.dtype).reshape(shape)
    out = x * scale + shift
    return out, new_mean, new_var


def batch_norm_act_train(x, gamma, beta, moving_mean, moving_var,
                         eps: float = 1e-5, momentum: float = 0.9,
                         axis: int = 1, fix_gamma: bool = False,
                         use_global_stats: bool = False,
                         act_type: str = "relu"):
    """Training-mode BN fused with an activation; returns
    ``(out, new_moving_mean, new_moving_var)``.

    Dispatches to the single-pass Pallas kernel pair
    (``mxnet_tpu.kernels.bn_act``: one sweep for sum+sumsq statistics,
    one fused normalize+act sweep — the cross-op reduction fusion XLA
    won't form, "Operator Fusion in XLA" / PAPERS.md) when the kernels
    layer is active, the layout is channel-last and the shape tiles;
    every miss falls back to ``batch_norm_train`` + ``activation`` with
    the reason reported through the kernels registry (docs/kernels.md).
    Kernel-path variance is one-pass E[x²]−mean² (vs the reference's
    two-pass) — agreement is ~1e-6 relative on O(1) activations, the
    documented tolerance."""
    from ..kernels import bn_act as _kbn
    from ..kernels import registry as _kreg

    axis = axis % x.ndim
    kmode = None if use_global_stats else _kreg.select("bn_act")
    if kmode is not None:
        c = x.shape[axis]
        rows = _prodl(x.shape) // max(c, 1)
        if axis != x.ndim - 1:
            _kreg.fallback("bn_act", "layout not channel-last "
                           f"(axis={axis}, ndim={x.ndim})")
        elif not _kbn.supported_act(act_type):
            _kreg.fallback("bn_act", f"activation {act_type!r} not fused")
        elif _kbn.pick_row_block(rows) == 0:
            _kreg.fallback("bn_act",
                           f"shape not tile-able (rows={rows}, C={c})")
        elif (why := _kreg.mesh_ineligible(None)):
            # batch statistics reduce over the GLOBAL batch: a per-shard
            # kernel would change the semantics, and Mosaic kernels are
            # not auto-partitioned (kernels/registry.py:batch_mesh)
            _kreg.fallback("bn_act", why)
        else:
            g = jnp.ones_like(gamma) if fix_gamma else gamma
            out, mean, var = _kbn.bn_act_train(
                x, g, beta, eps, act_type,
                kmode == "interpret")
            _kreg.dispatched("bn_act", kmode)
            # moving-stat blend identical to batch_norm_train (running
            # buffers keep their own dtype — f32 master buffers)
            new_mean = (moving_mean * momentum
                        + mean * (1 - momentum)).astype(moving_mean.dtype)
            new_var = (moving_var * momentum
                       + var * (1 - momentum)).astype(moving_var.dtype)
            return out, new_mean, new_var
    out, new_mean, new_var = batch_norm_train(
        x, gamma, beta, moving_mean, moving_var, eps=eps,
        momentum=momentum, axis=axis, fix_gamma=fix_gamma,
        use_global_stats=use_global_stats)
    if act_type != "identity":
        out = activation(out, act_type)
    return out, new_mean, new_var


def _prodl(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def batch_norm_infer(x, gamma, beta, moving_mean, moving_var,
                     eps: float = 1e-5, axis: int = 1, fix_gamma: bool = False):
    axis = axis % x.ndim
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    # same dtype discipline as batch_norm_train: fold stats to per-channel
    # scale/shift, cast the small vectors, keep the activation in x.dtype
    inv = lax.rsqrt(moving_var + eps)
    scale = (gamma * inv).astype(x.dtype).reshape(shape)
    shift = (beta - moving_mean * gamma * inv).astype(x.dtype).reshape(shape)
    return x * scale + shift


def layer_norm(x, gamma, beta, axis: int = -1, eps: float = 1e-5):
    """Ref: src/operator/nn/layer_norm.cc."""
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


def group_norm(x, gamma, beta, num_groups: int = 1, eps: float = 1e-5):
    """Ref: src/operator/nn/group_norm.cc. x is (N, C, ...)."""
    n, c = x.shape[:2]
    orig = x.shape
    x = x.reshape((n, num_groups, c // num_groups) + orig[2:])
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(orig)
    shape = [1] * len(orig)
    shape[1] = c
    return x * gamma.reshape(shape) + beta.reshape(shape)


def instance_norm(x, gamma, beta, eps: float = 1e-5):
    """Ref: src/operator/instance_norm.cc. Normalize per (N, C) over spatial."""
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


def space_to_depth(x, block_size: int, layout: str = "NCHW"):
    """Move spatial blocks into channels (ref src/operator/tensor/
    matrix_op.cc space_to_depth, ONNX SpaceToDepth formula:
    reshape -> transpose [0,3,5,1,2,4] -> reshape).

    layout='NHWC' is the TPU-native variant (channel-last blocks) used by
    the s2d ResNet stem."""
    b = int(block_size)
    if layout == "NCHW":
        n, c, h, w = x.shape
        if h % b or w % b:
            raise MXNetError(f"H/W {h}x{w} not divisible by block {b}")
        x = x.reshape(n, c, h // b, b, w // b, b)
        x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
        return x.reshape(n, c * b * b, h // b, w // b)
    if layout == "NHWC":
        n, h, w, c = x.shape
        if h % b or w % b:
            raise MXNetError(f"H/W {h}x{w} not divisible by block {b}")
        x = x.reshape(n, h // b, b, w // b, b, c)
        x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
        return x.reshape(n, h // b, w // b, b * b * c)
    raise MXNetError(f"space_to_depth: unsupported layout {layout}")


def depth_to_space(x, block_size: int, layout: str = "NCHW"):
    """Inverse of space_to_depth (ref matrix_op.cc depth_to_space:
    reshape -> transpose [0,3,4,1,5,2] -> reshape)."""
    b = int(block_size)
    if layout == "NCHW":
        n, c, h, w = x.shape
        if c % (b * b):
            raise MXNetError(f"C={c} not divisible by block^2={b*b}")
        x = x.reshape(n, b, b, c // (b * b), h, w)
        x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
        return x.reshape(n, c // (b * b), h * b, w * b)
    if layout == "NHWC":
        n, h, w, c = x.shape
        if c % (b * b):
            raise MXNetError(f"C={c} not divisible by block^2={b*b}")
        x = x.reshape(n, h, w, b, b, c // (b * b))
        x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
        return x.reshape(n, h * b, w * b, c // (b * b))
    raise MXNetError(f"depth_to_space: unsupported layout {layout}")


def lrn(x, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (ref: src/operator/nn/lrn.cc)."""
    sq = jnp.square(x)
    pad = nsize // 2
    sq = jnp.pad(sq, ((0, 0), (pad, pad)) + ((0, 0),) * (x.ndim - 2))
    window = jnp.zeros(x.shape, x.dtype)
    acc = lax.reduce_window(sq, 0.0, lax.add,
                            (1, nsize) + (1,) * (x.ndim - 2),
                            (1, 1) + (1,) * (x.ndim - 2),
                            "valid")
    del window
    return x / (knorm + alpha / nsize * acc) ** beta


# -- activations -------------------------------------------------------------

_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "log_sigmoid": jax.nn.log_sigmoid,
    "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    "gelu": jax.nn.gelu,
    "erf_gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "silu": jax.nn.silu,
}


def activation(x, act_type: str = "relu"):
    """Ref: src/operator/nn/activation.cc."""
    fn = _ACTIVATIONS.get(act_type)
    if fn is None:
        raise MXNetError(f"unknown activation '{act_type}'")
    return fn(x)


def leaky_relu(x, gamma=None, act_type: str = "leaky", slope: float = 0.25,
               lower_bound: float = 0.125, upper_bound: float = 0.334, rng_key=None):
    """Ref: src/operator/leaky_relu.cc (leaky/prelu/elu/selu/gelu/rrelu)."""
    if act_type == "leaky":
        return jnp.where(x > 0, x, slope * x)
    if act_type == "prelu":
        g = gamma
        if g.ndim < x.ndim:
            g = g.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else g
        return jnp.where(x > 0, x, g * x)
    if act_type == "elu":
        return jnp.where(x > 0, x, slope * (jnp.exp(x) - 1))
    if act_type == "selu":
        return jax.nn.selu(x)
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "rrelu":
        if rng_key is not None:
            s = jax.random.uniform(rng_key, x.shape, x.dtype, lower_bound, upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(x > 0, x, s * x)
    raise MXNetError(f"unknown leaky_relu act_type '{act_type}'")


# -- softmax family ----------------------------------------------------------

def softmax(x, axis: int = -1, temperature: Optional[float] = None,
            length=None, use_length: bool = False):
    """Ref: src/operator/nn/softmax.cc; masked variant via length."""
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        mask = _length_mask(x, length, axis)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis: int = -1, temperature: Optional[float] = None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


def masked_softmax(x, mask, axis: int = -1, temperature: float = 1.0):
    x = x / temperature
    neg = jnp.finfo(x.dtype).min
    out = jax.nn.softmax(jnp.where(mask, x, neg), axis=axis)
    return jnp.where(mask, out, 0.0)


def masked_log_softmax(x, mask, axis: int = -1, temperature: float = 1.0):
    x = x / temperature
    neg = jnp.finfo(x.dtype).min
    return jnp.where(mask, jax.nn.log_softmax(jnp.where(mask, x, neg), axis=axis), -jnp.inf)


def _length_mask(x, length, axis):
    ar = jnp.arange(x.shape[axis])
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    ar = ar.reshape(shape)
    lshape = [1] * x.ndim
    for i, d in enumerate(length.shape):
        lshape[i] = d
    return ar < length.reshape(lshape)


def softmax_cross_entropy(logits, labels, sparse_label: bool = True, axis: int = -1):
    """Fused CE summed over the batch, 1-element output like the reference
    op (ref: src/operator/loss_binary_op.cc softmax_cross_entropy)."""
    logp = jax.nn.log_softmax(logits, axis=axis)
    if sparse_label:
        lab = labels.astype(jnp.int32)
        per = -jnp.take_along_axis(logp, lab[..., None], axis=axis).squeeze(axis)
    else:
        per = -(labels * logp).sum(axis=axis)
    return per.sum().reshape((1,))


# -- dropout -----------------------------------------------------------------

def dropout(x, key, p: float = 0.5, mode: str = "training", axes=()):
    """Ref: src/operator/nn/dropout.cc. Scaled inverted dropout."""
    if p <= 0.0:
        return x
    shape = list(x.shape)
    for ax in axes or ():
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


# -- embedding / indexing ----------------------------------------------------

def embedding(indices, weight, sparse_grad: bool = False):
    """Ref: src/operator/tensor/indexing_op.cc Embedding."""
    return jnp.take(weight, indices.astype(jnp.int32), axis=0)


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype=jnp.float32):
    oh = jax.nn.one_hot(indices, depth, dtype=dtype)
    return oh * (on_value - off_value) + off_value


def pick(x, index, axis: int = -1, keepdims: bool = False, mode: str = "clip"):
    """Ref: src/operator/tensor/broadcast_reduce_op_index.cc pick."""
    idx = index.astype(jnp.int32)
    if mode == "clip":
        idx = jnp.clip(idx, 0, x.shape[axis] - 1)
    else:
        idx = idx % x.shape[axis]
    picked = jnp.take_along_axis(x, jnp.expand_dims(idx, axis), axis=axis)
    return picked if keepdims else picked.squeeze(axis)


def topk(x, k: int = 1, axis: int = -1, ret_typ: str = "indices",
         is_ascend: bool = False, dtype=jnp.float32):
    """Ref: src/operator/tensor/ordering_op.cc."""
    xa = -x if is_ascend else x
    xa = jnp.moveaxis(xa, axis, -1)
    vals, idx = lax.top_k(xa, k)
    if is_ascend:
        vals = -vals
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis)
    if ret_typ == "indices":
        return idx.astype(dtype)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx.astype(dtype)
    if ret_typ == "mask":
        oh = jax.nn.one_hot(jnp.moveaxis(idx, axis, -1), x.shape[axis], dtype=x.dtype)
        return jnp.moveaxis(oh.sum(-2), -1, axis)
    raise MXNetError(f"unknown ret_typ {ret_typ}")


# -- sequence ops ------------------------------------------------------------

def sequence_mask(x, sequence_length=None, use_sequence_length: bool = False,
                  value: float = 0.0, axis: int = 0):
    """Ref: src/operator/sequence_mask.cc (time-major by default)."""
    if sequence_length is None or not use_sequence_length:
        return x
    T = x.shape[axis]
    ar = jnp.arange(T)
    shape = [1] * x.ndim
    shape[axis] = T
    batch_axis = 1 - axis
    lshape = [1] * x.ndim
    lshape[batch_axis] = x.shape[batch_axis]
    mask = ar.reshape(shape) < sequence_length.reshape(lshape)
    return jnp.where(mask, x, value).astype(x.dtype)


def sequence_last(x, sequence_length=None, use_sequence_length: bool = False, axis: int = 0):
    if sequence_length is None or not use_sequence_length:
        return lax.index_in_dim(x, x.shape[axis] - 1, axis, keepdims=False)
    idx = (sequence_length - 1).astype(jnp.int32)
    xm = jnp.moveaxis(x, axis, 0)          # (T, B, ...)
    return jnp.take_along_axis(
        xm, idx.reshape((1, -1) + (1,) * (xm.ndim - 2)), axis=0)[0]


def sequence_reverse(x, sequence_length=None, use_sequence_length: bool = False, axis: int = 0):
    if sequence_length is None or not use_sequence_length:
        return jnp.flip(x, axis)
    xm = jnp.moveaxis(x, axis, 0)
    T = xm.shape[0]
    ar = jnp.arange(T).reshape((-1,) + (1,) * (xm.ndim - 1))
    L = sequence_length.astype(jnp.int32).reshape((1, -1) + (1,) * (xm.ndim - 2))
    rev_idx = jnp.where(ar < L, L - 1 - ar, ar)
    out = jnp.take_along_axis(xm, jnp.broadcast_to(rev_idx, xm.shape), axis=0)
    return jnp.moveaxis(out, 0, axis)
