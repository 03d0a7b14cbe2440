"""RNN tests (ref: tests/python/unittest/test_gluon_rnn.py + rnn op tests).

Correctness model follows the reference's: forward vs a plain-numpy
recurrence, fused-layer vs explicit-cell consistency, gradient flow, and a
small LSTM language-model convergence smoke.
"""
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn, rnn


# -- numpy reference recurrences ---------------------------------------------

def _sig(x):
    return 1.0 / (1.0 + onp.exp(-x))


def np_lstm_layer(x, wi, wh, bi, bh, h0, c0):
    T, B, _ = x.shape
    H = wh.shape[1]
    h, c = h0.copy(), c0.copy()
    ys = []
    for t in range(T):
        g = x[t] @ wi.T + bi + h @ wh.T + bh
        i, f, gg, o = (g[:, :H], g[:, H:2*H], g[:, 2*H:3*H], g[:, 3*H:])
        c = _sig(f) * c + _sig(i) * onp.tanh(gg)
        h = _sig(o) * onp.tanh(c)
        ys.append(h)
    return onp.stack(ys), h, c


def np_gru_layer(x, wi, wh, bi, bh, h0):
    T, B, _ = x.shape
    H = wh.shape[1]
    h = h0.copy()
    ys = []
    for t in range(T):
        xp = x[t] @ wi.T + bi
        hp = h @ wh.T + bh
        r = _sig(xp[:, :H] + hp[:, :H])
        z = _sig(xp[:, H:2*H] + hp[:, H:2*H])
        n = onp.tanh(xp[:, 2*H:] + r * hp[:, 2*H:])
        h = (1 - z) * n + z * h
        ys.append(h)
    return onp.stack(ys), h


def _layer_params(layer, l="l0"):
    return tuple(onp.array(getattr(layer, f"{l}_{n}").data().asnumpy())
                 for n in ("i2h_weight", "h2h_weight", "i2h_bias", "h2h_bias"))


def test_lstm_matches_numpy():
    T, B, C, H = 5, 3, 4, 6
    layer = rnn.LSTM(H)
    layer.initialize()
    x = mx.np.random.uniform(size=(T, B, C))
    out = layer(x)
    wi, wh, bi, bh = _layer_params(layer)
    ref, _, _ = np_lstm_layer(onp.array(x.asnumpy()), wi, wh, bi, bh,
                              onp.zeros((B, H)), onp.zeros((B, H)))
    onp.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5, atol=1e-6)


def test_gru_matches_numpy():
    T, B, C, H = 4, 2, 3, 5
    layer = rnn.GRU(H)
    layer.initialize()
    x = mx.np.random.uniform(size=(T, B, C))
    out = layer(x)
    wi, wh, bi, bh = _layer_params(layer)
    ref, _ = np_gru_layer(onp.array(x.asnumpy()), wi, wh, bi, bh,
                          onp.zeros((B, H)))
    onp.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5, atol=1e-6)


def test_rnn_relu_shapes_and_states():
    T, B, C, H, L = 6, 2, 5, 4, 2
    layer = rnn.RNN(H, num_layers=L, activation="relu")
    layer.initialize()
    x = mx.np.random.uniform(size=(T, B, C))
    states = layer.begin_state(batch_size=B)
    out, new_states = layer(x, states)
    assert out.shape == (T, B, H)
    assert new_states[0].shape == (L, B, H)


def test_bidirectional_lstm():
    T, B, C, H = 5, 2, 3, 4
    layer = rnn.LSTM(H, bidirectional=True)
    layer.initialize()
    x = mx.np.random.uniform(size=(T, B, C))
    out = layer(x)
    assert out.shape == (T, B, 2 * H)
    # backward half at t=0 must equal a reversed-input forward pass's last step
    wi, wh, bi, bh = _layer_params(layer, "r0")
    xr = onp.array(x.asnumpy())[::-1]
    ref, hT, _ = np_lstm_layer(xr, wi, wh, bi, bh, onp.zeros((B, H)),
                               onp.zeros((B, H)))
    onp.testing.assert_allclose(out.asnumpy()[0, :, H:], hT, rtol=1e-5,
                                atol=1e-6)


def test_ntc_layout():
    B, T, C, H = 3, 5, 4, 6
    layer = rnn.LSTM(H, layout="NTC")
    layer.initialize()
    x = mx.np.random.uniform(size=(B, T, C))
    out = layer(x)
    assert out.shape == (B, T, H)


def test_variable_length_masking():
    T, B, C, H = 6, 3, 4, 5
    layer = rnn.LSTM(H, use_sequence_length=True)
    layer.initialize()
    x = mx.np.random.uniform(size=(T, B, C))
    lens = mx.np.array([6, 3, 1], dtype="int32")
    out, states = layer(x, layer.begin_state(batch_size=B),
                        sequence_length=lens)
    out_np = out.asnumpy()
    # hidden state frozen after each sequence's end
    onp.testing.assert_allclose(out_np[3, 1], out_np[2, 1], rtol=1e-6)
    onp.testing.assert_allclose(out_np[5, 2], out_np[0, 2], rtol=1e-6)
    # final h equals last valid step's output
    h_final = states[0].asnumpy()[0]
    onp.testing.assert_allclose(h_final[1], out_np[2, 1], rtol=1e-6)


def test_fused_vs_cell_consistency():
    """LSTM fused layer == LSTMCell.unroll with the same weights."""
    T, B, C, H = 4, 2, 3, 5
    layer = rnn.LSTM(H)
    layer.initialize()
    x = mx.np.random.uniform(size=(T, B, C))
    out_fused = layer(x)

    cell = rnn.LSTMCell(H)
    cell.initialize()
    cell(x[0], cell.begin_state(batch_size=B))  # shape init
    wi, wh, bi, bh = _layer_params(layer)
    cell.i2h_weight.set_data(mx.np.array(wi))
    cell.h2h_weight.set_data(mx.np.array(wh))
    cell.i2h_bias.set_data(mx.np.array(bi))
    cell.h2h_bias.set_data(mx.np.array(bh))
    out_cells, _ = cell.unroll(T, x, layout="TNC")
    onp.testing.assert_allclose(out_cells.asnumpy(), out_fused.asnumpy(),
                                rtol=1e-5, atol=1e-6)


def test_rnn_gradients_flow():
    T, B, C, H = 4, 2, 3, 5
    layer = rnn.LSTM(H, num_layers=2)
    layer.initialize()
    x = mx.np.random.uniform(size=(T, B, C))
    with mx.autograd.record():
        out = layer(x)
        loss = (out * out).sum()
    loss.backward()
    for name, p in layer.collect_params().items():
        g = p.grad()
        assert g is not None and float(mx.np.abs(g).sum()) > 0, name


def test_sequential_residual_dropout_cells():
    B, C, H = 2, 6, 6
    stack = rnn.SequentialRNNCell()
    stack.add(rnn.LSTMCell(H), rnn.ResidualCell(rnn.GRUCell(H)),
              rnn.DropoutCell(0.5))
    stack.initialize()
    x = mx.np.random.uniform(size=(B, 5, C))
    out, states = stack.unroll(5, x, layout="NTC")
    assert out.shape == (B, 5, H)
    assert len(states) == 3  # lstm h,c + gru h


def test_bidirectional_cell_unroll():
    B, T, C, H = 2, 4, 3, 5
    bi = rnn.BidirectionalCell(rnn.LSTMCell(H), rnn.LSTMCell(H))
    bi.initialize()
    x = mx.np.random.uniform(size=(B, T, C))
    out, states = bi.unroll(T, x, layout="NTC")
    assert out.shape == (B, T, 2 * H)
    assert len(states) == 4


def test_lstm_lm_convergence():
    """Tiny LSTM language model memorizes a repeated sequence (ref
    example/rnn word_lm)."""
    V, E, H, T, B = 20, 16, 32, 8, 4
    rs = onp.random.RandomState(0)
    corpus = rs.randint(0, V, size=(B, T + 1)).astype("int32")

    class LM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(V, E)
            self.lstm = rnn.LSTM(H, layout="NTC")
            self.out = nn.Dense(V, flatten=False)

        def forward(self, x):
            return self.out(self.lstm(self.embed(x)))

    net = LM()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 1e-2})
    x = mx.np.array(corpus[:, :-1])
    y = mx.np.array(corpus[:, 1:])
    losses = []
    for _ in range(60):
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(B)
        losses.append(float(loss.mean().asnumpy()))
    assert losses[-1] < losses[0] * 0.3, (losses[0], losses[-1])
