"""Model builder ``bert_pretrain``: BERT masked-LM + next-sentence
pre-training through the program's normal path (``BERTForPretrain`` +
``ShardedTrainer``), exactly as ``chip_smoke.py:_bert_trainer`` builds it.

A training builder gives the ``train_stream`` driver: ``build``,
``make_batch``, ``loss_fn``, ``predict_loss`` and ``flops_per_sample``.
"""
import numpy as onp


def build(config, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, get_bert

    mx.random.seed(seed)
    net = BERTForPretrain(get_bert(
        "bert_12_768_12", vocab_size=config["vocab_size"],
        max_length=config["max_position_embeddings"],
        dropout=config["hidden_dropout_prob"],
        num_layers=config["num_hidden_layers"], units=config["hidden_size"],
        hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        token_type_vocab_size=config["type_vocab_size"]))
    net.initialize(mx.init.Xavier())
    return net


def make_batch(config, traffic, rs):
    """One host batch ``(x, y)`` of numpy arrays: token ids, token types,
    ragged valid lengths (so ``kv_len`` rides the flash kernels), masked
    positions inside the shortest valid length; MLM and NSP labels."""
    b, seq, npred = traffic["batch"], traffic["seq"], traffic["masked"]
    lo = traffic["valid_length_min"]
    vocab = config["vocab_size"]
    x = (rs.randint(0, vocab, size=(b, seq)).astype("int32"),
         onp.zeros((b, seq), "int32"),
         rs.randint(lo, seq + 1, size=(b,)).astype("int32"),
         rs.randint(0, lo, size=(b, npred)).astype("int32"))
    y = (rs.randint(0, vocab, size=(b, npred)).astype("int32"),
         rs.randint(0, 2, size=(b,)).astype("int32"))
    return x, y


def loss_fn(pred, y):
    """Per-sample MLM (mean over the masked positions) + NSP cross-entropy,
    in float32 (chip_smoke.py's)."""
    import jax
    import jax.numpy as jnp

    mlm_scores, nsp_scores = pred
    mlm_y, nsp_y = y
    lp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
    mlm = -jnp.take_along_axis(lp, mlm_y[..., None], -1)[..., 0]
    lp2 = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
    nsp = -jnp.take_along_axis(lp2, nsp_y[:, None], -1)[:, 0]
    return jnp.mean(mlm, axis=-1) + nsp


def predict_loss(net, x, y):
    """Mean loss of the net's hybridized PREDICT-mode forward (no dropout)
    on one host batch -- what the plain reference is compared with."""
    import jax.numpy as jnp

    import mxnet_tpu as mx

    net.hybridize()
    mlm, nsp = net(*[mx.np.array(a) for a in x])
    per = loss_fn((mlm._data, nsp._data), tuple(jnp.asarray(a) for a in y))
    return float(jnp.mean(per))


def flops_per_sample(config, traffic):
    """Operations the forward and backward passes REQUIRE for one sequence
    (backward = 2 x forward, recomputation not counted), from the shapes:
    per token and layer the four attention projections and the two FFN
    matmuls (2 FLOP per multiply-add), attention scores and values over the
    padded length; per sample the pooler and NSP head; per masked position
    the MLM transform and the tied vocabulary decoder."""
    h, i = config["hidden_size"], config["intermediate_size"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    seq, npred = traffic["seq"], traffic["masked"]
    per_token_layer = 2 * (4 * h * h + 2 * h * i) + 4 * seq * h
    forward = (seq * layers * per_token_layer
               + 2 * h * h + 2 * h * 2
               + npred * (2 * h * h + 2 * h * vocab))
    return 3.0 * forward
