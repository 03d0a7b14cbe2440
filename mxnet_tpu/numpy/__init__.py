"""``mx.np`` — NumPy-compatible array API on TPU.

Re-imagines python/mxnet/numpy/multiarray.py (12.2k LoC of generated
``_npi_*`` FFI wrappers, SURVEY.md §2.4) the TPU way: instead of per-op C++
shims (src/api/operator/**), every function is a thin autograd-aware lift of
the corresponding ``jax.numpy`` function via ops.dispatch.wrap_op — jnp/XLA
already implements NumPy semantics, so the op corpus collapses to a name
table. The array type is the shared NDArray (mutable handle, tape-aware).

Divergences from the reference are documented in docs/divergences.md
(notably: default integer dtypes follow jnp, slices are copies).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _onp

from ..base import MXNetError
from ..ndarray.ndarray import (NDArray, array, waitall, from_jax, newaxis)
from ..ndarray import ndarray as _nd
from ..ops.dispatch import wrap_op, call, invoke

ndarray = NDArray  # mx.np.ndarray is the NDArray class

# dtype aliases (mx.np exposes numpy dtypes)
float16 = _onp.float16
float32 = _onp.float32
float64 = _onp.float64
bfloat16 = jnp.bfloat16
int8 = _onp.int8
int16 = _onp.int16
int32 = _onp.int32
int64 = _onp.int64
uint8 = _onp.uint8
uint16 = _onp.uint16
uint32 = _onp.uint32
uint64 = _onp.uint64
bool_ = _onp.bool_
pi = _onp.pi
e = _onp.e
inf = _onp.inf
nan = _onp.nan
dtype = _onp.dtype


# -- creation (ctx-aware) ----------------------------------------------------

def _creation(jfn):
    def f(*args, ctx=None, device=None, dtype=None, **kwargs):
        if dtype is not None:
            kwargs["dtype"] = jnp.dtype(dtype)
        out = jfn(*args, **kwargs)
        return NDArray(out, ctx=ctx or device)

    f.__name__ = jfn.__name__
    return f


def zeros(shape, dtype=float32, order="C", ctx=None, device=None):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jnp.zeros(shape, dtype=jnp.dtype(dtype) if dtype else jnp.float32),
                   ctx=ctx or device)


def ones(shape, dtype=float32, order="C", ctx=None, device=None):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jnp.ones(shape, dtype=jnp.dtype(dtype) if dtype else jnp.float32),
                   ctx=ctx or device)


def full(shape, fill_value, dtype=None, order="C", ctx=None, device=None, out=None):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    if isinstance(fill_value, NDArray):
        fill_value = fill_value._data
    res = NDArray(jnp.full(shape, fill_value, dtype=jnp.dtype(dtype) if dtype else None),
                  ctx=ctx or device)
    if out is not None:
        out._set_data(res._data)
        return out
    return res


def empty(shape, dtype=float32, order="C", ctx=None, device=None):
    return zeros(shape, dtype=dtype, ctx=ctx, device=device)


def eye(N, M=None, k=0, dtype=float32, ctx=None, device=None):
    return NDArray(jnp.eye(N, M, k, dtype=jnp.dtype(dtype)), ctx=ctx or device)


def identity(n, dtype=float32, ctx=None, device=None):
    return eye(n, dtype=dtype, ctx=ctx, device=device)


def arange(start, stop=None, step=1, dtype=None, ctx=None, device=None):
    return NDArray(jnp.arange(start, stop, step,
                              dtype=jnp.dtype(dtype) if dtype else None), ctx=ctx or device)


def linspace(start, stop, num=50, endpoint=True, retstep=False, dtype=None,
             axis=0, ctx=None, device=None):
    out = jnp.linspace(start, stop, num, endpoint=endpoint, retstep=retstep,
                       dtype=jnp.dtype(dtype) if dtype else None, axis=axis)
    if retstep:
        return NDArray(out[0], ctx=ctx or device), out[1]
    return NDArray(out, ctx=ctx or device)


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None,
             axis=0, ctx=None, device=None):
    return NDArray(jnp.logspace(start, stop, num, endpoint, base,
                                jnp.dtype(dtype) if dtype else None, axis), ctx=ctx or device)


def asarray(obj, dtype=None, ctx=None, device=None):
    return array(obj, dtype=dtype, ctx=ctx or device)


def ascontiguousarray(obj, dtype=None):
    return array(obj, dtype=dtype)


def copy(a):
    return a.copy() if isinstance(a, NDArray) else array(a)


def zeros_like(a, dtype=None, order="C", ctx=None, device=None):
    return NDArray(jnp.zeros_like(a._data if isinstance(a, NDArray) else a,
                                  dtype=jnp.dtype(dtype) if dtype else None), ctx=ctx or device)


def ones_like(a, dtype=None, order="C", ctx=None, device=None):
    return NDArray(jnp.ones_like(a._data if isinstance(a, NDArray) else a,
                                 dtype=jnp.dtype(dtype) if dtype else None), ctx=ctx or device)


def full_like(a, fill_value, dtype=None, order="C", ctx=None, device=None):
    return NDArray(jnp.full_like(a._data if isinstance(a, NDArray) else a, fill_value,
                                 dtype=jnp.dtype(dtype) if dtype else None), ctx=ctx or device)


def empty_like(a, dtype=None, order="C", ctx=None, device=None):
    return zeros_like(a, dtype=dtype, ctx=ctx, device=device)


def meshgrid(*xi, **kwargs):
    outs = jnp.meshgrid(*[x._data if isinstance(x, NDArray) else x for x in xi], **kwargs)
    return [NDArray(o) for o in outs]


def tril(m, k=0):
    return call(lambda x: jnp.tril(x, k), (m,), {}, name="tril")


def triu(m, k=0):
    return call(lambda x: jnp.triu(x, k), (m,), {}, name="triu")


# -- mechanically lifted jnp functions --------------------------------------
# Everything listed here is autograd-aware via ops.dispatch (NDArray args →
# differentiable inputs; scalars/config closed over). Mirrors the generated
# op table of the reference (python/mxnet/numpy/multiarray.py __all__).

_LIFTED = [
    # elementwise math
    "add", "subtract", "multiply", "divide", "true_divide", "floor_divide",
    "negative", "positive", "power", "float_power", "mod", "remainder", "fmod",
    "absolute", "abs", "fabs", "sign", "rint", "fix", "floor", "ceil", "trunc",
    "sqrt", "cbrt", "square", "reciprocal", "exp", "expm1", "exp2", "log",
    "log2", "log10", "log1p", "logaddexp", "logaddexp2",
    "sin", "cos", "tan", "arcsin", "arccos", "arctan", "arctan2",
    "sinh", "cosh", "tanh", "arcsinh", "arccosh", "arctanh",
    "degrees", "radians", "deg2rad", "rad2deg", "hypot", "copysign",
    "maximum", "minimum", "fmax", "fmin", "heaviside", "nan_to_num", "interp",
    "gcd", "lcm", "i0", "sinc", "ldexp", "frexp", "signbit", "nextafter",
    # comparison / logical
    "equal", "not_equal", "less", "less_equal", "greater", "greater_equal",
    "logical_and", "logical_or", "logical_xor", "logical_not",
    "isfinite", "isinf", "isnan", "isneginf", "isposinf", "isclose",
    "array_equal", "allclose",
    # bit ops
    "bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not", "invert",
    "left_shift", "right_shift",
    # reductions
    "sum", "prod", "mean", "std", "var", "min", "max", "amin", "amax", "ptp",
    "nansum", "nanprod", "nanmean", "nanstd", "nanvar", "nanmin", "nanmax",
    "all", "any", "count_nonzero", "median", "nanmedian", "quantile",
    "percentile", "nanquantile", "nanpercentile", "average",
    "argmax", "argmin", "nanargmax", "nanargmin",
    "cumsum", "cumprod", "nancumsum", "nancumprod",
    # sorting / searching
    "sort", "argsort", "lexsort", "partition", "argpartition", "searchsorted",
    "nonzero", "argwhere", "flatnonzero", "where", "extract", "diff", "ediff1d",
    "unwrap", "trapezoid",
    # linear algebra (top-level)
    "dot", "vdot", "inner", "outer", "matmul", "tensordot", "einsum", "kron",
    "cross", "trace", "diagonal", "diag", "diagflat", "diag_indices_from",
    # shape manipulation
    "reshape", "ravel", "transpose", "swapaxes", "moveaxis", "rollaxis",
    "expand_dims", "squeeze", "broadcast_to", "broadcast_arrays",
    "concatenate", "stack", "vstack", "hstack", "dstack", "column_stack",
    "split", "array_split", "vsplit", "hsplit", "dsplit",
    "tile", "repeat", "flip", "fliplr", "flipud", "roll", "rot90",
    "atleast_1d", "atleast_2d", "atleast_3d", "pad", "resize",
    "append", "insert", "delete",
    # indexing
    "take", "take_along_axis", "put_along_axis", "choose", "compress",
    "unravel_index", "ravel_multi_index", "indices", "ix_",
    "tril_indices", "triu_indices", "diag_indices",
    "select", "piecewise",
    # sets
    "unique", "intersect1d", "union1d", "setdiff1d", "setxor1d", "isin", "in1d",
    # statistics
    "bincount", "digitize", "histogram", "histogram2d", "histogramdd",
    "histogram_bin_edges", "corrcoef", "cov", "correlate", "convolve",
    # rounding
    "round", "around", "clip",
    # dtype & misc
    "astype",
    "real", "imag", "conj", "conjugate", "angle",
    "shape", "ndim", "size", "result_type", "can_cast", "promote_types",
    "isscalar", "iscomplexobj", "isrealobj",
    "vander", "gradient",
    # polynomial / windowing / misc numeric tail (ref src/operator/numpy/)
    "polyval", "polyfit", "polyadd", "polysub", "polymul", "polyder",
    "polyint", "roots",
    "trim_zeros", "apply_along_axis", "apply_over_axes",
    "hamming", "hanning", "blackman", "bartlett", "kaiser",
    "interp", "ediff1d", "i0", "sinc", "heaviside", "packbits", "unpackbits",
    "spacing", "unwrap", "nan_to_num", "searchsorted",
]

_g = globals()
_g["fix"] = wrap_op(jnp.trunc, "fix")  # jnp.fix is deprecated; same op
for _name in dict.fromkeys(_LIFTED):
    if _name in _g:
        continue
    _j = getattr(jnp, _name, None)
    if _j is None:
        continue
    _g[_name] = wrap_op(_j, _name)


def _to_raw(x):
    return x._data if isinstance(x, NDArray) else x


def may_share_memory(a, b):
    return False  # functional arrays never alias observably


def shares_memory(a, b):
    return False


def _seq_op(jfn, name):
    """Ops taking a *sequence* of arrays (concatenate family) — each element
    becomes a differentiable input. ``seq_input`` marks the node so a
    symbol-json reload regroups the graph inputs into one list argument
    (Symbol._interpret)."""

    def op(arrays, *args, **kwargs):
        arrays = list(arrays)
        nd = [a if isinstance(a, NDArray) else NDArray(jnp.asarray(a)) for a in arrays]
        import numbers

        attrs = {"seq_input": True}
        # vouch reloadable only when the WHOLE call is captured: at most
        # an axis argument, nothing else in the closure
        captured = len(args) <= 1 and set(kwargs) <= {"axis"}
        if args or "axis" in kwargs:
            axis = args[0] if args else kwargs["axis"]
            if axis is None:
                # None is meaningful (concatenate axis=None flattens) —
                # record it, or reload would replay the wrapper default
                attrs["axis"] = None
            elif isinstance(axis, numbers.Integral):
                attrs["axis"] = int(axis)
            else:
                captured = False   # unrecordable axis: refuse at reload
        if captured:
            attrs["__reloadable__"] = True
        return invoke(lambda *xs: jfn(list(xs), *args, **kwargs), nd,
                      name=name, attrs=attrs)

    op.__name__ = name
    return op


concatenate = _seq_op(jnp.concatenate, "concatenate")
stack = _seq_op(jnp.stack, "stack")
vstack = _seq_op(jnp.vstack, "vstack")
hstack = _seq_op(jnp.hstack, "hstack")
dstack = _seq_op(jnp.dstack, "dstack")
column_stack = _seq_op(jnp.column_stack, "column_stack")
row_stack = vstack


def expand_dims(a, axis):  # noqa: F811 — ensure method-consistent version
    return call(lambda x: jnp.expand_dims(x, axis), (a,), {}, name="expand_dims")


def split(ary, indices_or_sections, axis=0):  # noqa: F811 — returns list like numpy
    res = call(lambda x: tuple(jnp.split(x, indices_or_sections, axis=axis)),
               (ary,), {}, name="split",
               attrs={"pos_args": [None, indices_or_sections], "axis": axis})
    return list(res) if isinstance(res, tuple) else [res]


def array_split(ary, indices_or_sections, axis=0):  # noqa: F811
    res = call(lambda x: tuple(jnp.array_split(x, indices_or_sections, axis=axis)),
               (ary,), {}, name="array_split",
               attrs={"pos_args": [None, indices_or_sections], "axis": axis})
    return list(res) if isinstance(res, tuple) else [res]


def bfloat16_cast(a):
    return a.astype(jnp.bfloat16)


# numpy aliases jnp dropped (ref numpy<->mxnet parity table)
in1d = wrap_op(lambda ar1, ar2, assume_unique=False, invert=False:
               jnp.isin(ar1, ar2, assume_unique=assume_unique,
                        invert=invert).ravel(), "in1d")
msort = wrap_op(lambda a: jnp.sort(a, axis=0), "msort")
trapz = wrap_op(getattr(jnp, "trapezoid", getattr(jnp, "trapz", None)),
                "trapz")


from . import linalg  # noqa: E402
from . import random  # noqa: E402
from . import fft  # noqa: E402

__all__ = [n for n in _g if not n.startswith("_")]


def tri(N, M=None, k=0, dtype=None):
    """Lower-triangular ones matrix (ref _npi_tri)."""
    import jax.numpy as _jnp

    from ..ops.dispatch import call as _call

    return _call(lambda: _jnp.tri(N, M, k,
                                  dtype=_jnp.dtype(dtype)
                                  if dtype else _jnp.float32),
                 (), {}, name="tri")


def fill_diagonal(a, val, wrap=False):
    """In-place diagonal fill with numpy semantics (ref
    _npi_fill_diagonal): 2-D fills the main diagonal (wrap=True restarts
    the diagonal after each n-column block in tall matrices); ndim>2
    requires all-equal dims and fills a[i, i, ..., i]. Mutates ``a`` via
    the functional-update rebind (visible to jit tracing)."""
    import builtins as _bi

    import jax.numpy as _jnp

    from ..base import MXNetError as _Err

    if a.ndim == 2:
        rows, cols = a.shape
        if wrap and rows > cols:
            # numpy wrap: diagonal restarts every cols+1 rows
            r = _jnp.arange(rows)
            keep = (r % (cols + 1)) != cols
            rr = r[keep]
            cc = rr % (cols + 1)
            keep2 = cc < cols
            new = a._data.at[rr[keep2], cc[keep2]].set(val)
        else:
            n = _bi.min(a.shape)
            idx = _jnp.arange(n)
            new = a._data.at[idx, idx].set(val)
    elif a.ndim > 2:
        if len(set(a.shape)) != 1:
            raise _Err("fill_diagonal: all dimensions of a.ndim > 2 input "
                       "must be equal (numpy semantics)")
        idx = _jnp.arange(a.shape[0])
        new = a._data.at[tuple([idx] * a.ndim)].set(val)
    else:
        raise _Err("fill_diagonal: array must be at least 2-d "
                   "(numpy semantics)")
    a._set_data(new)
    return a


def constraint_check(data, msg="Constraint violated"):
    """All-true check returning 1.0, raising otherwise
    (ref _npx_constraint_check; eager-mode validation op used by
    gluon.probability)."""
    import jax.numpy as _jnp

    from ..base import MXNetError as _Err
    from ..ops.dispatch import call as _call

    ok = bool(_jnp.all(data._data))
    if not ok:
        raise _Err(msg)
    return _call(lambda x: _jnp.ones((), _jnp.float32), (data,), {},
                 name="constraint_check")

__all__ = list(__all__) + ["tri", "fill_diagonal", "constraint_check"]


def round_(x, decimals=0, out=None, **kwargs):
    """Legacy alias of round (ref numpy/multiarray.py round_)."""
    return round(x, decimals, out=out, **kwargs)


def triu_indices_from(arr, k=0):
    """Ref numpy/multiarray.py triu_indices_from."""
    if arr.ndim != 2:
        raise ValueError("input array must be 2-d")
    return triu_indices(arr.shape[-2], k=k, m=arr.shape[-1])


def set_printoptions(*args, **kwargs):
    """Printing config (ref numpy/arrayprint.py set_printoptions):
    NDArray repr renders through host numpy, so numpy's own options
    govern it directly."""
    return _onp.set_printoptions(*args, **kwargs)


def genfromtxt(*args, **kwargs):
    """Text loading on host then device placement (ref numpy/io.py
    genfromtxt wraps the official numpy one the same way)."""
    return from_jax(jnp.asarray(_onp.genfromtxt(*args, **kwargs)))


__all__ = list(__all__) + ["round_", "triu_indices_from",
                           "set_printoptions", "genfromtxt"]
