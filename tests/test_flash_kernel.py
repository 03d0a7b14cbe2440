"""The pallas flash-attention KERNEL itself, validated under the pallas
interpreter (no TPU needed) against attention_reference.

tests/test_op_gradients.py checks the flash custom-VJP path, but on CPU
that path dispatches to the jnp fallback — the kernel body
(ops/attention.py _flash_kernel) would only ever run on real hardware.
Interpret mode closes that gap for the kernel's MATH: a regression fails
HERE.  What the chip's compiler refuses is tests/test_chip_compile.py's
job, and a selected kernel that fails on the chip raises (PR 23) — it
never becomes a silent O(T^2) path.
"""
from __future__ import annotations

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels import registry as kreg
from mxnet_tpu.kernels.flash_bwd import flash_attention_bwd_pallas
from mxnet_tpu.ops.attention import (_flash_forward_pallas, _kernel_block,
                                     attention_reference, flash_attention)


def _qkv(b, h, t, d, seed=0):
    rs = onp.random.RandomState(seed)
    return tuple(jnp.asarray((rs.rand(b, h, t, d) - 0.5).astype("float32"))
                 for _ in range(3))


@pytest.mark.parametrize("t,d", [(16, 8), (32, 16), (64, 8)])
def test_kernel_matches_reference_dense(t, d):
    q, k, v = _qkv(2, 2, t, d, seed=t)
    scale = 1.0 / d ** 0.5
    got = _flash_forward_pallas(q, k, v, causal=False, scale=scale,
                                interpret=True)
    want = attention_reference(q, k, v, scale=scale)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)


def test_kernel_matches_reference_causal():
    t, d = 32, 8
    q, k, v = _qkv(1, 2, t, d, seed=3)
    scale = 1.0 / d ** 0.5
    got = _flash_forward_pallas(q, k, v, causal=True, scale=scale,
                                interpret=True)
    qpos = jnp.arange(t)
    mask = (qpos[:, None] >= qpos[None, :])[None, None]
    want = attention_reference(q, k, v, mask=mask, scale=scale)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)


def test_kernel_kv_valid_length():
    t, d = 32, 8
    b = 2
    q, k, v = _qkv(b, 2, t, d, seed=4)
    scale = 1.0 / d ** 0.5
    lens = jnp.asarray(onp.array([t // 2, t], "int32"))
    got = _flash_forward_pallas(q, k, v, causal=False, scale=scale,
                                kv_len=lens, interpret=True)
    mask = (jnp.arange(t)[None, :] < lens[:, None])[:, None, None, :]
    want = attention_reference(q, k, v, mask=mask, scale=scale)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)


def test_kernel_causal_plus_kv_len():
    t, d = 16, 8
    q, k, v = _qkv(1, 1, t, d, seed=5)
    scale = 1.0 / d ** 0.5
    lens = jnp.asarray(onp.array([10], "int32"))
    got = _flash_forward_pallas(q, k, v, causal=True, scale=scale,
                                kv_len=lens, interpret=True)
    qpos = jnp.arange(t)
    mask = ((qpos[:, None] >= qpos[None, :])
            & (qpos[None, :] < 10))[None, None]
    want = attention_reference(q, k, v, mask=mask, scale=scale)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)


def test_kernel_bf16_io():
    """bf16 in/out (the BERT path): f32 accumulation inside, output back
    in bf16 within bf16 tolerance of the f32 reference."""
    t, d = 32, 16
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(1, 2, t, d, seed=6))
    scale = 1.0 / d ** 0.5
    got = _flash_forward_pallas(q, k, v, causal=False, scale=scale,
                                interpret=True)
    assert got.dtype == jnp.bfloat16
    want = attention_reference(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), scale=scale)
    onp.testing.assert_allclose(
        onp.asarray(got).astype("float32"), onp.asarray(want),
        rtol=2e-2, atol=2e-2)


def test_kernel_uneven_block_sizes():
    """tq != tk exercises independent bq/bk selection."""
    d = 8
    rs = onp.random.RandomState(7)
    q = jnp.asarray((rs.rand(1, 2, 16, d) - 0.5).astype("float32"))
    k = jnp.asarray((rs.rand(1, 2, 64, d) - 0.5).astype("float32"))
    v = jnp.asarray((rs.rand(1, 2, 64, d) - 0.5).astype("float32"))
    scale = 1.0 / d ** 0.5
    got = _flash_forward_pallas(q, k, v, causal=False, scale=scale,
                                interpret=True)
    want = attention_reference(q, k, v, scale=scale)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)


def _full_mask(t, causal, lens):
    m = None
    if lens is not None:
        m = (jnp.arange(t)[None, :] < lens[:, None])[:, None, None, :]
    if causal:
        cm = jnp.tril(jnp.ones((t, t), bool))[None, None]
        m = cm if m is None else jnp.logical_and(m, cm)
    return m


@pytest.mark.parametrize("causal,with_len", [(False, False), (True, False),
                                             (False, True), (True, True)])
def test_backward_kernels_match_reference_grads(causal, with_len):
    """The Pallas VJP kernels (dq, dk/dv) against jax.grad of
    attention_reference — plain, causal, kv_len-masked and both."""
    b, h, t, d = 2, 2, 32, 8
    q, k, v = _qkv(b, h, t, d, seed=11 + causal + 2 * with_len)
    g = jnp.asarray(onp.random.RandomState(17)
                    .rand(b, h, t, d).astype("f4")) - 0.5
    scale = 1.0 / d ** 0.5
    lens = jnp.asarray(onp.array([t // 2, t], "int32")) if with_len else None
    out, lse = _flash_forward_pallas(q, k, v, causal, scale, kv_len=lens,
                                     interpret=True, return_lse=True)
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, g, out, lse, lens, causal, scale,
        bq=_kernel_block(t), bk=_kernel_block(t), interpret=True)

    def ref(q, k, v):
        m = _full_mask(t, causal, lens)
        return (attention_reference(q, k, v, mask=m, scale=scale) * g).sum()

    rq, rk, rv = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in [(dq, rq), (dk, rk), (dv, rv)]:
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                    rtol=2e-5, atol=2e-5)


def test_custom_vjp_end_to_end_interpret():
    """flash_attention's custom_vjp under MXNET_KERNELS=interpret: the
    Pallas forward's saved lse feeds the Pallas backward — gradients
    match jax.grad of the reference (the BERT-training path without the
    full-score-matrix fallback)."""
    b, h, t, d = 1, 2, 32, 8
    q, k, v = _qkv(b, h, t, d, seed=23)
    lens = jnp.asarray(onp.array([24], "int32"))

    with kreg.override("interpret"):
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   kv_valid_length=lens).sum()

        d1 = jax.grad(loss, (0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        m = _full_mask(t, True, lens)
        return attention_reference(q, k, v, mask=m).sum()

    d2 = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b_ in zip(d1, d2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b_),
                                    rtol=2e-5, atol=2e-5)


def test_forward_lse_values():
    """return_lse must be the true row log-sum-exp of the scaled logits
    (the backward kernels' correctness hinges on it)."""
    t, d = 16, 8
    q, k, v = _qkv(1, 1, t, d, seed=31)
    scale = 1.0 / d ** 0.5
    _, lse = _flash_forward_pallas(q, k, v, False, scale, interpret=True,
                                   return_lse=True)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    want = jax.scipy.special.logsumexp(logits, axis=-1)
    onp.testing.assert_allclose(onp.asarray(lse), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)


def test_pick_block_covers_bert_and_resnet_shapes():
    # the shapes the chip smoke runs must stay on the kernel path
    assert _kernel_block(128) == 128  # BERT seq 128
    assert _kernel_block(512) == 512  # long-seq
    assert _kernel_block(384) == 128  # SQuAD-style
    assert _kernel_block(100) == 0    # non-tileable -> fallback, by design
