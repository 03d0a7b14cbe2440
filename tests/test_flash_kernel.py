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


# ---------------------------------------------------------------------------
# The head-grouped program form (PR 38): hg heads of a batch row a program,
# operands in the arrays' own dtype, one backward program where one block is
# the sequence.
#
# Tolerances.  f32 inputs keep 2e-5: the products are f32 x f32 as before.
# bf16 inputs are held to 2e-2 of the reference's largest magnitude, against
# the f32 reference ON THE SAME bf16-rounded inputs: the result itself is
# rounded to bf16 (half an ulp = 2^-9 = 0.2% of its own size), and p and ds
# go to the MXU in bf16 (another 0.2% an element, averaged over up to T
# terms of a row, so it does not grow with T).  Read at these shapes:
# 0.3-0.5% (the XLA-CPU reference on bf16 inputs reads the same 0.3-0.5%
# against the f32 one), so 2e-2 leaves four times of room and still fails a
# wrong mask, a lost scale or a dropped block, which are errors of 10-100%.

def _ragged(b, t, blk):
    """Lengths with a row shorter than one block and a fully masked row."""
    lens = [t, max(blk // 2 - 3, 1), 0, t - 5][:b]
    return jnp.asarray(onp.array(lens, "int32"))


@pytest.mark.parametrize("masking", ["plain", "causal", "kv_len", "both"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hg", [4, 2, 1], ids=["hgH", "hg2", "hg1"])
@pytest.mark.parametrize("t", [128, 384])
def test_head_grouped_kernels_match_reference(t, hg, dtype, masking):
    """Forward-with-lse and backward in the head-grouped form against
    ``attention_reference`` and its ``jax.grad``: every head a program, a
    proper divisor, one head; T = 128 is one block (the single backward
    program), T = 384 is three (the dq and dk/dv kernels)."""
    b, h, d = 4, 4, 16
    causal = masking in ("causal", "both")
    q, k, v = (x.astype(dtype) for x in _qkv(b, h, t, d, seed=t + hg))
    g = (jnp.asarray(onp.random.RandomState(5).rand(b, h, t, d)
                     .astype("f4")) - 0.5).astype(dtype)
    lens = _ragged(b, t, 128) if masking in ("kv_len", "both") else None
    scale = 1.0 / d ** 0.5
    blk = _kernel_block(t)
    assert blk == 128
    out, lse = _flash_forward_pallas(q, k, v, causal, scale, kv_len=lens,
                                     interpret=True, return_lse=True, hg=hg)
    grads = flash_attention_bwd_pallas(q, k, v, g, out, lse, lens, causal,
                                       scale, bq=blk, bk=blk, hg=hg,
                                       interpret=True)
    assert out.dtype == q.dtype
    assert [x.dtype for x in grads] == [q.dtype] * 3     # written once, cast
    #                                                      inside the kernel

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    m = _full_mask(t, causal, lens)

    def ref(q, k, v):
        o = attention_reference(q, k, v, mask=m, scale=scale)
        return (o * g.astype(jnp.float32)).sum(), o

    (_, want), want_grads = jax.value_and_grad(ref, (0, 1, 2),
                                               has_aux=True)(*f32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", f32[0], f32[1]) * scale
    if m is not None:
        logits = jnp.where(m, logits, -jnp.inf)
    want_lse = jax.scipy.special.logsumexp(logits, axis=-1)
    if dtype == "float32":
        close = dict(rtol=2e-5, atol=2e-5)
    else:
        close = dict(rtol=0, atol=2e-2 * float(jnp.abs(want).max()))
    onp.testing.assert_allclose(onp.asarray(out, "float32"),
                                onp.asarray(want), **close)
    # a fully masked row: zeros out, lse -inf, zero gradients
    onp.testing.assert_allclose(
        onp.asarray(lse), onp.asarray(want_lse),
        rtol=2e-5 if dtype == "float32" else 2e-2,
        atol=2e-5 if dtype == "float32" else 2e-2)
    for got, ref_g in zip(grads, want_grads):
        if dtype != "float32":
            close = dict(rtol=0, atol=2e-2 * float(jnp.abs(ref_g).max()))
        onp.testing.assert_allclose(onp.asarray(got, "float32"),
                                    onp.asarray(ref_g), **close)
    if lens is not None:
        assert not onp.asarray(out, "float32")[2].any()
        assert onp.isneginf(onp.asarray(lse)[2]).all()
        assert not any(onp.asarray(x, "float32")[2].any() for x in grads)


@pytest.mark.parametrize("hg", [2, 1])
def test_one_backward_program_equals_the_two_kernels(hg):
    """Where one block is the sequence the backward is one program under
    the dk/dv kernel's name; the two kernels at the same shape give the
    same gradients (f32: to rounding)."""
    b, h, t, d = 2, 2, 128, 16
    q, k, v = _qkv(b, h, t, d, seed=41)
    g = jnp.asarray(onp.random.RandomState(3).rand(b, h, t, d)
                    .astype("f4")) - 0.5
    lens = jnp.asarray(onp.array([70, 128], "int32"))
    out, lse = _flash_forward_pallas(q, k, v, True, 0.25, kv_len=lens,
                                     interpret=True, return_lse=True, hg=hg)
    one, two = (flash_attention_bwd_pallas(
        q, k, v, g, out, lse, lens, True, 0.25, bq=t, bk=t, hg=hg,
        one_program=flag, interpret=True) for flag in (True, False))
    for a, b_ in zip(one, two):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b_),
                                    rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="one block a sequence"):
        flash_attention_bwd_pallas(q, k, v, g, out, lse, lens, True, 0.25,
                                   bq=64, bk=64, one_program=True,
                                   interpret=True)


@pytest.mark.parametrize("shape,dtype,form", [
    # bert-base.pretrain-s128: every head of a batch row, 128 programs
    ((12, 128, 128, 64), "bfloat16", (12, 128, 128)),
    ((12, 128, 128, 64), "float32", (12, 128, 128)),
    # long sequences: 512-row blocks, as many heads as the budget takes in
    # whole 128-lane tiles
    ((12, 1024, 1024, 64), "bfloat16", (4, 512, 512)),
    ((12, 1024, 1024, 64), "float32", (2, 512, 512)),
    ((8, 2048, 2048, 128), "bfloat16", (2, 512, 512)),
    # between: 384 rides as three 128-blocks, 256 as one 256-block
    ((12, 384, 384, 64), "bfloat16", (12, 128, 128)),
    ((12, 256, 256, 64), "bfloat16", (12, 256, 256)),
    # an odd number of 64-lane heads has no divisor but itself: the blocks
    # are halved until the whole row of heads fits (GPT-2 XL's 25 at 1k)
    ((7, 128, 128, 64), "bfloat16", (7, 128, 128)),
    ((7, 1024, 1024, 64), "bfloat16", (7, 256, 256)),
    ((25, 1024, 1024, 64), "bfloat16", (25, 128, 128)),
    # ... and where even 128-row blocks do not, the shape is ineligible
    ((25, 1024, 1024, 64), "float32", (0, 128, 128)),
], ids=lambda v: str(v).replace(" ", ""))
def test_train_form_follows_the_shapes(shape, dtype, form):
    from mxnet_tpu.ops.attention import _train_form

    h, tq, tk, d = shape
    assert _train_form(h, tq, tk, d, jnp.dtype(dtype)) == form
    hg = form[0]
    assert hg == 0 or (h % hg == 0 and (hg == h or hg * d % 128 == 0))


def test_no_form_is_a_counted_fallback():
    """A shape no head group fits is decided before the call: the
    reference path, ``kernels.fallbacks.flash_attention`` and a warning."""
    from mxnet_tpu import telemetry as tel

    q = jnp.zeros((1, 25, 1024, 64), jnp.float32)
    before = tel.snapshot().get("kernels.fallbacks.flash_attention",
                                {"value": 0})["value"]
    kreg.reset_warned()
    with kreg.override("interpret"), pytest.warns(
            RuntimeWarning, match="shape not tile-able"):
        out = jax.eval_shape(lambda q: flash_attention(q, q, q), q)
    assert out.shape == q.shape
    assert tel.snapshot()["kernels.fallbacks.flash_attention"][
        "value"] == before + 1
