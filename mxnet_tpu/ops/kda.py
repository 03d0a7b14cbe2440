"""Kimi Delta Attention (KDA): a gated delta-rule linear attention whose
state is one ``(d_k, d_v)`` matrix a head, constant in the context length
(Kimi Linear tech report, arXiv:2510.26692; fla ``KimiDeltaAttention``).

Per head, with a per-key-channel log-decay ``g_t <= 0`` and a scalar
``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                       (q_t already scaled)

Three forms of the same recurrence, all in float32 whatever the inputs,
and float32 on the device too: every matrix product in here is traced under
``jax.default_matmul_precision("highest")`` (a TPU's default rounds float32
operands to bf16, which would read the float32 state as bf16 every step;
``tests/test_chip_compile.py`` holds the compiled products to it).  The
state is carried in :data:`STATE_DTYPE`: ``begin_cache`` allocates it so and
each form returns it in the dtype it was given.

* :func:`kda_scan` -- token by token with ``lax.scan``; the oracle of the
  tests, never on the serving path;
* :func:`kda_step` -- one token for every row of a batch (the decode step):
  two passes over the state, one read for ``S^T k`` and ``S^T q`` together
  and one read-modify-write;
* :func:`kda_chunk` -- chunk-parallel (the prefill): within a chunk of 64
  the cumulative log-decays, a unit-lower-triangular solve for the
  delta-rule correction, then ONE state update a chunk, chunks in a
  ``lax.scan``.  Decay ratios ``exp(G_i - G_j)`` are formed from exponents
  that are never positive (sub-blocks of 16 against a reference row for
  the off-diagonal blocks, the exponent difference itself on the diagonal
  blocks), so a strong decay cannot overflow where ``k / exp(G)`` would.

Rows are ragged: a position at or beyond a row's ``n_tokens`` gets
``g = 0`` and ``beta = 0``, which leaves the state exactly as it was.
:func:`short_conv` is the causal depthwise convolution in front of q, k
and v, with the last ``K - 1`` real rows carried as state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["kda_scan", "kda_step", "kda_chunk", "short_conv", "mask_rows",
           "STATE_DTYPE"]

STATE_DTYPE = jnp.float32
CHUNK = 64
SUB = 16


def short_conv(x, w, tail, n_tokens):
    """Causal depthwise convolution over the last ``K`` positions.

    x: (B, T, C) the new rows; w: (C, K), ``w[:, K-1]`` weighs the current
    row; tail: (B, K-1, C) the rows before ``x`` (zeros at a sequence's
    start); n_tokens: (B,) how many of the T rows are real.  Returns
    ``(y (B, T, C), new_tail)`` -- the new tail is the last ``K - 1`` rows
    up to each row's true length, so ``n_tokens == 0`` leaves it as it
    was."""
    t, k = x.shape[1], w.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(xp[:, j:j + t] * w[:, j] for j in range(k))
    if t == 1:
        # the decode step: a shift by one row or none.  The per-row slice
        # below is a gather, which XLA runs as a loop over the rows -- at
        # 32 slots x 20 layers a fifth of the step's device time
        # (PERF.md section 6, PR 29)
        new_tail = jnp.where((n_tokens > 0)[:, None, None], xp[:, 1:],
                             xp[:, :-1])
    else:
        new_tail = jax.vmap(
            lambda rows, n: jax.lax.dynamic_slice_in_dim(rows, n, k - 1, 0))(
                xp, n_tokens.astype(jnp.int32))
    return y, new_tail.astype(tail.dtype)


def mask_rows(g, beta, n_tokens):
    """``g`` (B, T, H, dk) and ``beta`` (B, T, H) with the rows at or past
    ``n_tokens`` turned into the identity update (no decay, no write)."""
    real = jnp.arange(g.shape[1])[None, :] < n_tokens.astype(jnp.int32)[:, None]
    return (jnp.where(real[:, :, None, None], g, 0.0),
            jnp.where(real[:, :, None], beta, 0.0))


def kda_scan(q, k, v, g, beta, state, n_tokens=None):
    """Token by token.  q, k: (B, T, H, dk); v: (B, T, H, dv); g as q;
    beta: (B, T, H); state: (B, H, dk, dv).  Returns
    ``(o (B, T, H, dv) f32, new state in the dtype it came in)``."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if n_tokens is not None:
        g, beta = mask_rows(g, beta, n_tokens)

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(lambda s, row: kda_step(*row, s), state, rows)
    return jnp.moveaxis(o, 0, 1), s


def kda_step(q, k, v, g, beta, state):
    """One token a row.  q, k, g: (B, H, dk); v: (B, H, dv); beta: (B, H);
    state: (B, H, dk, dv).  Returns ``(new state in the dtype it came in,
    o (B, H, dv) f32)``.

    ``S' = Diag(exp g) S`` is read once for both ``S'^T k`` and ``S'^T q``;
    with ``u = beta (v - S'^T k)`` the new state is ``S' + k u^T`` and
    ``o = S'^T q + u (k . q)``, so the written state is not read again."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    with jax.named_scope("kda_step"), \
            jax.default_matmul_precision("highest"):
        s = state.astype(f32) * jnp.exp(g)[..., None]
        r = jnp.einsum("bhnk,bhkv->bhnv", jnp.stack([k, q], axis=2), s)
        u = beta[..., None] * (v - r[:, :, 0])
        o = r[:, :, 1] + u * jnp.sum(k * q, -1, keepdims=True)
        s = s + k[..., :, None] * u[..., None, :]
        return s.astype(state.dtype), o


def _decayed_gram(left, k, gcum, strict):
    """``M[i, j] = sum_c left[i, c] k[j, c] exp(G[i, c] - G[j, c])`` for
    ``j < i`` (``strict``) or ``j <= i``, zero elsewhere, per chunk.
    left, k, gcum: (..., CHUNK, dk) with ``gcum`` the cumulative log-decay
    inside the chunk (non-increasing along the chunk)."""
    lead = left.shape[:-2]
    c, dk = left.shape[-2:]
    nsub = c // SUB
    sub = lambda a: a.reshape(lead + (nsub, SUB, dk))
    ls, ks, gs = sub(left), sub(k), sub(gcum)
    ref = gs[..., :1, :]                       # G at each sub-block's start
    # off-diagonal blocks: rows of sub-block a against every EARLIER row,
    # both factors' exponents <= 0 around the reference row of a
    lhs = ls * jnp.exp(gs - ref)                               # (.., a, i, dk)
    before = (jnp.arange(c)[None, :]
              < (jnp.arange(nsub) * SUB)[:, None])             # (a, j)
    expo = ref - gcum[..., None, :, :]                         # (.., a, j, dk)
    rhs = jnp.where(before[..., None],
                    k[..., None, :, :] * jnp.exp(jnp.minimum(expo, 0.0)), 0.0)
    off = jnp.einsum("...aic,...ajc->...aij", lhs, rhs)
    off = off.reshape(lead + (c, c))
    # diagonal blocks: the exponent difference itself, masked before exp
    i, j = jnp.arange(SUB)[:, None], jnp.arange(SUB)[None, :]
    keep = (j < i) if strict else (j <= i)
    diff = gs[..., :, None, :] - gs[..., None, :, :]           # (.., a, i, j, dk)
    w = jnp.where(keep[..., None], jnp.exp(jnp.where(keep[..., None],
                                                     diff, 0.0)), 0.0)
    diag = jnp.einsum("...aic,...ajc,...aijc->...aij", ls, ks, w)
    eye = jnp.eye(nsub, dtype=left.dtype)
    # place each (SUB, SUB) block on the chunk's diagonal
    blocks = jnp.einsum("...aij,ab->...aibj", diag, eye)
    return off + blocks.reshape(lead + (c, c))


def kda_chunk(q, k, v, g, beta, state, n_tokens):
    """Chunk-parallel form over a padded prompt.  Shapes as
    :func:`kda_scan`; ``n_tokens`` (B,) is each row's true length inside
    the padded T.  Returns ``(o (B, T, H, dv) f32, new state in the dtype
    it came in)``."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    with jax.named_scope("kda_chunk"), \
            jax.default_matmul_precision("highest"):
        g, beta = mask_rows(g, beta, n_tokens)
        pad = -t % CHUNK
        if pad:                          # identity rows up to a whole chunk
            q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        n = (t + pad) // CHUNK
        # (B, T, H, d) -> (n, B, H, CHUNK, d): chunks lead, for the scan
        split = lambda a: a.reshape(b, n, CHUNK, h, -1).transpose(1, 0, 3, 2, 4)
        q, k, v, g = split(q), split(k), split(v), split(g)
        beta = split(beta[..., None])                           # (.., C, 1)
        gcum = jnp.cumsum(g, axis=-2)
        a_mat = _decayed_gram(k, k, gcum, strict=True)          # (.., C, C)
        p_mat = _decayed_gram(q, k, gcum, strict=False)
        decay = jnp.exp(gcum)                                   # <= 1
        k_in = k * decay                  # what a key sees of the old state
        q_in = q * decay
        g_end = gcum[..., -1:, :]
        k_out = k * jnp.exp(g_end - gcum)      # a key's write, at chunk end
        # U = (I + Diag(beta) A)^-1 Diag(beta) (V - K_in S0): solve once
        # for [beta V | beta K_in], the S0 part is applied in the scan
        lower = jnp.eye(CHUNK, dtype=f32) + beta * a_mat
        sol = jax.scipy.linalg.solve_triangular(
            lower, jnp.concatenate([beta * v, beta * k_in], -1),
            lower=True, unit_diagonal=True)
        w_v, w_k = sol[..., :dv], sol[..., dv:]

        def chunk(s, xs):
            w_v, w_k, q_in, p_mat, k_out, g_end = xs
            u = w_v - jnp.einsum("bhck,bhkv->bhcv", w_k, s)
            o = jnp.einsum("bhck,bhkv->bhcv", q_in, s) \
                + jnp.einsum("bhcj,bhjv->bhcv", p_mat, u)
            s = s * jnp.exp(g_end).swapaxes(-1, -2) \
                + jnp.einsum("bhck,bhcv->bhkv", k_out, u)
            return s, o

        s, o = jax.lax.scan(chunk, state.astype(f32),
                            (w_v, w_k, q_in, p_mat, k_out, g_end))
        o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * CHUNK, h, dv)
        return o[:, :t], s.astype(state.dtype)
