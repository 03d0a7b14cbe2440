"""``mx.np.random`` — stateful NumPy-style sampling over the global JAX key.

Ref: python/mxnet/numpy/random.py + src/operator/numpy/random/. The
reference holds curand Philox states per device (random_generator.h:125-158);
here one global splittable key (mxnet_tpu.random) feeds jax.random samplers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as _onp

from ..ndarray.ndarray import NDArray
from ..random import next_key, seed  # re-export seed

__all__ = ["seed", "uniform", "normal", "randn", "rand", "randint", "choice",
           "shuffle", "permutation", "beta", "gamma", "exponential", "laplace",
           "logistic", "gumbel", "pareto", "power", "rayleigh", "weibull",
           "chisquare", "multinomial", "multivariate_normal", "lognormal",
           "binomial", "bernoulli", "poisson", "geometric", "f",
           "standard_normal", "categorical"]


def _shape(size):
    if size is None:
        return ()
    return (size,) if isinstance(size, int) else tuple(size)


def _val(x):
    return x._data if isinstance(x, NDArray) else x


def uniform(low=0.0, high=1.0, size=None, dtype=None, ctx=None, device=None, out=None):
    dt = jnp.dtype(dtype) if dtype else jnp.float32
    shp = _shape(size) if size is not None else jnp.broadcast_shapes(
        jnp.shape(_val(low)), jnp.shape(_val(high)))
    res = jax.random.uniform(next_key(), shp, dtype=dt) * (_val(high) - _val(low)) + _val(low)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=ctx or device)


def normal(loc=0.0, scale=1.0, size=None, dtype=None, ctx=None, device=None, out=None):
    dt = jnp.dtype(dtype) if dtype else jnp.float32
    shp = _shape(size) if size is not None else jnp.broadcast_shapes(
        jnp.shape(_val(loc)), jnp.shape(_val(scale)))
    res = jax.random.normal(next_key(), shp, dtype=dt) * _val(scale) + _val(loc)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=ctx or device)


def standard_normal(size=None, dtype=None, ctx=None, device=None):
    return normal(0.0, 1.0, size=size, dtype=dtype, ctx=ctx, device=device)


def randn(*shape, dtype=None, ctx=None, device=None):
    return normal(0.0, 1.0, size=shape, dtype=dtype, ctx=ctx, device=device)


def rand(*shape, dtype=None, ctx=None, device=None):
    return uniform(0.0, 1.0, size=shape, dtype=dtype, ctx=ctx, device=device)


def randint(low, high=None, size=None, dtype=None, ctx=None, device=None, out=None):
    if high is None:
        low, high = 0, low
    dt = jnp.dtype(dtype) if dtype else jnp.int32
    res = jax.random.randint(next_key(), _shape(size), low, high, dtype=dt)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=ctx or device)


def choice(a, size=None, replace=True, p=None, ctx=None, device=None, out=None):
    aval = _val(a)
    if isinstance(aval, int):
        aval = jnp.arange(aval)
    res = jax.random.choice(next_key(), aval, _shape(size), replace=replace, p=_val(p) if p is not None else None)
    if out is not None:
        out._set_data(res)
        return out
    return NDArray(res, ctx=ctx or device)


def shuffle(x: NDArray):
    """In-place shuffle along axis 0 (ref: _npi_shuffle)."""
    x._set_data(jax.random.permutation(next_key(), x._data, axis=0))


def permutation(x, ctx=None, device=None):
    if isinstance(x, int):
        return NDArray(jax.random.permutation(next_key(), x), ctx=ctx or device)
    return NDArray(jax.random.permutation(next_key(), _val(x), axis=0), ctx=ctx or device)


def _simple(sampler):
    def f(*params, size=None, dtype=None, ctx=None, device=None, **kw):
        dt = jnp.dtype(dtype) if dtype else jnp.float32
        shp = _shape(size) if size is not None else jnp.broadcast_shapes(
            *[jnp.shape(_val(p)) for p in params]) if params else ()
        res = sampler(next_key(), *[_val(p) for p in params], shp, dt, **kw)
        return NDArray(res, ctx=ctx or device)

    return f


beta = _simple(lambda k, a, b, shp, dt: jax.random.beta(k, a, b, shp or None, dt))
exponential = _simple(lambda k, scale, shp, dt: jax.random.exponential(k, shp or None, dt) * scale) \
    if True else None
laplace = _simple(lambda k, loc, scale, shp, dt: jax.random.laplace(k, shp or None, dt) * scale + loc)
logistic = _simple(lambda k, loc, scale, shp, dt: jax.random.logistic(k, shp or None, dt) * scale + loc)
gumbel = _simple(lambda k, loc, scale, shp, dt: jax.random.gumbel(k, shp or None, dt) * scale + loc)
# numpy/reference semantics are Pareto II (Lomax, support [0, inf),
# ref python/mxnet/numpy/random.py:687); jax.random.pareto is classical
# Pareto on [1, inf) — shift it
pareto = _simple(lambda k, a, shp, dt: jax.random.pareto(k, a, shp or None, dt) - 1.0)
rayleigh = _simple(lambda k, scale, shp, dt: jnp.sqrt(-2.0 * jnp.log(
    jax.random.uniform(k, shp or jnp.shape(scale), dt, minval=jnp.finfo(dt).tiny))) * scale)
weibull = _simple(lambda k, a, shp, dt: jax.random.weibull_min(k, 1.0, a, shp or None, dt))
chisquare = _simple(lambda k, df, shp, dt: jax.random.chisquare(k, df, shp or None, dt))
power = _simple(lambda k, a, shp, dt: jax.random.uniform(k, shp or jnp.shape(a), dt) ** (1.0 / a))


def exponential(scale=1.0, size=None, dtype=None, ctx=None, device=None):  # noqa: F811
    dt = jnp.dtype(dtype) if dtype else jnp.float32
    shp = _shape(size) if size is not None else jnp.shape(_val(scale))
    return NDArray(jax.random.exponential(next_key(), shp, dt) * _val(scale), ctx=ctx or device)


def gamma(shape, scale=1.0, size=None, dtype=None, ctx=None, device=None):
    """numpy-compatible (shape, scale, size) signature (ref
    python/mxnet/numpy/random.py gamma); the _simple wrapper cannot carry
    the optional positional scale."""
    dt = jnp.dtype(dtype) if dtype else jnp.float32
    shp = _shape(size) if size is not None else jnp.broadcast_shapes(
        jnp.shape(_val(shape)), jnp.shape(_val(scale)))
    res = jax.random.gamma(next_key(), _val(shape), shp or None, dt) \
        * _val(scale)
    return NDArray(res, ctx=ctx or device)


def lognormal(mean=0.0, sigma=1.0, size=None, dtype=None, ctx=None, device=None):
    return normal(mean, sigma, size=size, dtype=dtype, ctx=ctx, device=device).exp()


def poisson(lam=1.0, size=None, dtype=None, ctx=None, device=None):
    shp = _shape(size) if size is not None else jnp.shape(_val(lam))
    return NDArray(jax.random.poisson(next_key(), _val(lam), shp or None), ctx=ctx or device)


def binomial(n, p, size=None, dtype=None, ctx=None, device=None):
    shp = _shape(size) if size is not None else jnp.broadcast_shapes(
        jnp.shape(_val(n)), jnp.shape(_val(p)))
    res = jax.random.binomial(next_key(), _val(n), _val(p), shp or None)
    return NDArray(res, ctx=ctx or device)


def bernoulli(prob, size=None, dtype=None, ctx=None, device=None, logit=None):
    if prob is None and logit is not None:
        prob = jax.nn.sigmoid(_val(logit))
    shp = _shape(size) if size is not None else jnp.shape(_val(prob))
    res = jax.random.bernoulli(next_key(), _val(prob), shp or None)
    dt = jnp.dtype(dtype) if dtype else jnp.float32
    return NDArray(res.astype(dt), ctx=ctx or device)


def geometric(p, size=None, ctx=None, device=None):
    shp = _shape(size) if size is not None else jnp.shape(_val(p))
    return NDArray(jax.random.geometric(next_key(), _val(p), shp or None), ctx=ctx or device)


def multinomial(n, pvals, size=None, ctx=None, device=None):
    shp = _shape(size)
    p = jnp.asarray(_val(pvals))
    res = jax.random.multinomial(next_key(), jnp.asarray(n), p,
                                 shape=shp + jnp.shape(p) if shp else None)
    return NDArray(res, ctx=ctx or device)


def categorical(key, logits, temperature: float = 1.0, top_k: int = 0):
    """Sample token ids from ``(..., V)`` logits — the decode loop's
    sampler (docs/serving.md).  Unlike the rest of this module it takes
    an EXPLICIT jax PRNG key instead of advancing the global one: the
    serve decode loop derives a per-request/per-step key
    (``jax.random.fold_in``), so generation is deterministic under a
    fixed seed regardless of what else samples in the process.

    jit-safe: ``temperature`` and ``top_k`` are static Python values, so
    every branch resolves at trace time.

    * ``temperature <= 0`` — greedy argmax (no randomness, key unused).
    * ``top_k > 0`` — keep only the k largest logits per row (ties at
      the k-th value all stay), renormalize, then sample.
    * otherwise plain temperature-scaled categorical.

    Returns int32 ids of shape ``logits.shape[:-1]`` (NDArray in ->
    NDArray out, raw array in -> raw array out)."""
    raw = _val(logits)
    wrap = isinstance(logits, NDArray)
    if temperature <= 0.0:
        ids = jnp.argmax(raw, axis=-1).astype(jnp.int32)
        return NDArray(ids) if wrap else ids
    raw = raw.astype(jnp.float32)
    if top_k > 0 and top_k < raw.shape[-1]:
        kth = jax.lax.top_k(raw, top_k)[0][..., -1:]
        raw = jnp.where(raw >= kth, raw, -jnp.inf)
    ids = jax.random.categorical(_val(key), raw / float(temperature),
                                 axis=-1).astype(jnp.int32)
    return NDArray(ids) if wrap else ids


def multivariate_normal(mean, cov, size=None, ctx=None, device=None, **kw):
    res = jax.random.multivariate_normal(next_key(), _val(mean), _val(cov),
                                         _shape(size) or None)
    return NDArray(res, ctx=ctx or device)


def f(dfnum, dfden, size=None, ctx=None, device=None):
    shp = _shape(size) or None
    res = jax.random.f(next_key(), dfnum, dfden, shp)
    return NDArray(res, ctx=ctx or device)
