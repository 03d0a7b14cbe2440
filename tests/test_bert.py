"""BERT + fused attention tests.

Mirrors the reference's op-test strategy (SURVEY.md §4): numeric reference
comparison + gradient checks, plus an end-to-end convergence smoke test like
tests/python/train/."""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu import numpy_extension as npx
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.model_zoo.bert import (BERTForPretrain, get_bert,
                                            MultiHeadAttentionCell)
from mxnet_tpu.ops.attention import attention_reference, flash_attention


def _rand(*shape, seed=0):
    return jnp.asarray(onp.random.RandomState(seed).rand(*shape), jnp.float32)


def test_flash_attention_matches_reference_causal():
    q, k, v = (_rand(2, 4, 64, 32, seed=s) for s in range(3))
    out = flash_attention(q, k, v, causal=True)
    t = jnp.arange(64)
    mask = (t[:, None] >= t[None, :])[None, None]
    ref = attention_reference(q, k, v, mask=mask)
    assert jnp.abs(out - ref).max() < 1e-2


def test_flash_attention_padding_mask():
    q, k, v = (_rand(2, 2, 16, 8, seed=s) for s in range(3))
    vl = jnp.array([16, 9])
    mask = (jnp.arange(16)[None, :] < vl[:, None])[:, None, None, :]
    out = flash_attention(q, k, v, mask=mask)
    ref = attention_reference(q, k, v, mask=mask)
    assert jnp.abs(out - ref).max() < 1e-4
    # masked-out keys must not influence output
    v2 = v.at[1, :, 12:].set(99.0)
    out2 = flash_attention(q, k, v2, mask=mask)
    assert jnp.abs(out2 - out).max() < 1e-4


def test_flash_attention_grad_matches_reference():
    q, k, v = (_rand(1, 2, 32, 16, seed=s) for s in range(3))

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    t = jnp.arange(32)
    mask = (t[:, None] >= t[None, :])[None, None]

    def f_ref(q, k, v):
        return attention_reference(q, k, v, mask=mask).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert jnp.abs(a - b).max() < 1e-3


def test_npx_multi_head_attention_autograd():
    x = mx.np.array(onp.random.RandomState(0).rand(2, 8, 32), dtype='float32')
    x.attach_grad()
    with autograd.record():
        out = npx.multi_head_attention(x, x, x, num_heads=4)
        out.sum().backward()
    assert out.shape == (2, 8, 32)
    assert float((x.grad ** 2).sum()) > 0


@pytest.fixture(scope="module")
def tiny_bert():
    mx.random.seed(0)
    bert = get_bert("bert_12_768_12", vocab_size=97, max_length=32,
                    num_layers=2, units=32, hidden_size=64, num_heads=4,
                    dropout=0.0)
    net = BERTForPretrain(bert, vocab_size=97)
    net.initialize(mx.init.Xavier())
    return net


def test_bert_forward_shapes(tiny_bert):
    B, T, PP = 3, 12, 4
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.randint(0, 97, (B, T)), dtype='int32')
    tt = mx.np.zeros((B, T), dtype='int32')
    vl = mx.np.array([12, 7, 9], dtype='int32')
    mp = mx.np.array(rs.randint(0, 7, (B, PP)), dtype='int32')
    scores, nsp = tiny_bert(x, tt, vl, mp)
    assert scores.shape == (B, PP, 97)
    assert nsp.shape == (B, 2)
    seq, pooled = tiny_bert.bert(x, tt, vl)
    assert seq.shape == (B, T, 32) and pooled.shape == (B, 32)


def test_bert_padding_invariance(tiny_bert):
    """Tokens past valid_length must not change the valid positions."""
    rs = onp.random.RandomState(1)
    base = rs.randint(0, 97, (1, 10))
    x1 = mx.np.array(base, dtype='int32')
    base2 = base.copy()
    base2[0, 6:] = 5  # change padding region
    x2 = mx.np.array(base2, dtype='int32')
    vl = mx.np.array([6], dtype='int32')
    tt = mx.np.zeros((1, 10), dtype='int32')
    s1, _ = tiny_bert.bert(x1, tt, vl)
    s2, _ = tiny_bert.bert(x2, tt, vl)
    assert onp.allclose(onp.asarray(s1._data)[:, :6],
                        onp.asarray(s2._data)[:, :6], atol=1e-5)


@pytest.mark.slow
def test_bert_pretrain_loss_decreases(tiny_bert):
    """End-to-end MLM+NSP training on random data overfits a tiny batch."""
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from jax.sharding import PartitionSpec as P

    net = tiny_bert
    B, T, PP = 4, 16, 4
    rs = onp.random.RandomState(2)
    x = rs.randint(0, 97, (B, T)).astype('int32')
    tt = onp.zeros((B, T), 'int32')
    vl = onp.full((B,), T, 'int32')
    mp = rs.randint(0, T, (B, PP)).astype('int32')
    mlm_y = rs.randint(0, 97, (B, PP)).astype('int32')
    nsp_y = rs.randint(0, 2, (B,)).astype('int32')

    L = gloss.SoftmaxCrossEntropyLoss()

    def loss_fn(preds, y):
        scores, nsp = preds
        mlm_l, nsp_l = y
        a = L(mx.nd.NDArray(scores), mx.nd.NDArray(mlm_l))._data.mean()
        b = L(mx.nd.NDArray(nsp), mx.nd.NDArray(nsp_l))._data.mean()
        return a + b

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = ShardedTrainer(net, loss_fn, mesh=mesh, optimizer="adam",
                        learning_rate=3e-3, batch_spec=P("dp"))
    losses = [tr.step((x, tt, vl, mp), (mlm_y, nsp_y)) for _ in range(12)]
    assert losses[-1] < losses[0] * 0.7, losses


def test_flash_attention_masked_grad_matches_reference():
    """The blockwise flash backward under a padding mask (non-divisible
    valid lengths, some fully-masked key blocks)."""
    q, k, v = (_rand(2, 2, 32, 8, seed=s + 7) for s in range(3))
    vl = jnp.array([32, 5])
    mask = (jnp.arange(32)[None, :] < vl[:, None])[:, None, None, :]

    gf = jax.grad(lambda q, k, v: (flash_attention(q, k, v, mask=mask)
                                   ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (attention_reference(q, k, v, mask=mask)
                                   ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert jnp.abs(a - b).max() < 1e-3


def test_flash_attention_kv_valid_length():
    """kv_valid_length path (pallas-eligible) vs explicit boolean mask."""
    q, k, v = (_rand(3, 2, 32, 16, seed=s + 3) for s in range(3))
    vl = jnp.array([32, 17, 1])
    mask = (jnp.arange(32)[None, :] < vl[:, None])[:, None, None, :]
    out = flash_attention(q, k, v, kv_valid_length=vl)
    ref = attention_reference(q, k, v, mask=mask)
    assert jnp.abs(out - ref).max() < 1e-4
    # gradient path
    gf = jax.grad(lambda q: flash_attention(q, k, v, kv_valid_length=vl)
                  .sum())(q)
    gr = jax.grad(lambda q: attention_reference(q, k, v, mask=mask).sum())(q)
    assert jnp.abs(gf - gr).max() < 1e-3
