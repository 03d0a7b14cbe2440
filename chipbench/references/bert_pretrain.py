"""Plain reference for ``bert_pretrain``: BERT's forward pass and the
MLM + NSP loss in straightforward float32 ``jax.numpy`` -- no kernel, no
hybridize, no mixed precision, matmuls at ``highest`` precision.

It follows the program's block (``gluon/model_zoo/bert.py``), which follows
Devlin et al. 2018: post-norm layers, erf GELU, learned positions, tanh
pooler, MLM decoder tied to the word embedding.  Dropout is off (predict
mode).  Keys past a row's valid length are masked; query rows are not.
"""
import math

# The program's predict-mode forward multiplies f32 weights at the TPU's
# default (bf16-pass) matmul precision; the reference is f32 at ``highest``.
# On the chip the two differ by 0.001-0.05% of the loss (batches of 32, 64
# and 128; my chip runs, PR 26); 0.2% is five times the largest gap seen and
# far below what a wrong mask, a dropped layer or a forward in bf16
# activations would move.
LOSS_RTOL = 0.002


def _ln(x, g, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    import jax

    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _dense(x, p, name):
    return x @ p[name + ".weight"].T + p[name + ".bias"]


def scores(p, config, x):
    """(mlm_scores (B, P, V), nsp_scores (B, 2)) in float32."""
    import jax.numpy as jnp

    tokens, types, valid, mpos = x
    t = tokens.shape[1]
    heads = config["num_attention_heads"]
    eps = 1e-12
    h = (p["bert.word_embed.weight"][tokens]
         + p["bert.token_type_embed.weight"][types]
         + p["bert.position_weight"][:t][None])
    h = _ln(h, p["bert.embed_layer_norm.gamma"],
            p["bert.embed_layer_norm.beta"], eps)
    key_ok = jnp.arange(t)[None, :] < valid[:, None]          # (B, T)
    for layer in range(config["num_hidden_layers"]):
        pre = f"bert.encoder.layers.{layer}."
        b, _, u = h.shape
        d = u // heads
        q, k, v = jnp.split(_dense(h, p, pre + "attention.qkv"), 3, axis=-1)
        q, k, v = (a.reshape(b, t, heads, d).transpose(0, 2, 1, 3)
                   for a in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
        s = jnp.where(key_ok[:, None, None, :], s, -jnp.inf)
        w = jnp.exp(s - s.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        a = jnp.einsum("bhqk,bhkd->bhqd", w, v)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, u)
        h = _ln(h + _dense(a, p, pre + "attention.proj"),
                p[pre + "layer_norm_att.gamma"],
                p[pre + "layer_norm_att.beta"], eps)
        f = _dense(_gelu(_dense(h, p, pre + "ffn.ffn1")), p, pre + "ffn.ffn2")
        h = _ln(h + f, p[pre + "layer_norm_ffn.gamma"],
                p[pre + "layer_norm_ffn.beta"], eps)
    pooled = jnp.tanh(_dense(h[:, 0], p, "bert.pooler"))
    nsp = _dense(pooled, p, "nsp")
    hid = jnp.take_along_axis(h, mpos[..., None], axis=1)
    hid = _ln(_gelu(_dense(hid, p, "mlm_transform")),
              p["mlm_layer_norm.gamma"], p["mlm_layer_norm.beta"], eps)
    mlm = hid @ p["bert.word_embed.weight"].T + p["mlm_bias"]
    return mlm, nsp


def loss(params, config, x, y):
    """Mean MLM + NSP loss of one host batch on ``params`` (name -> array,
    the program's parameter names), as a Python float."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        mlm, nsp = scores(p, config, tuple(jnp.asarray(a) for a in x))
        mlm_y, nsp_y = (jnp.asarray(a) for a in y)
        lp = jax.nn.log_softmax(mlm, -1)
        l_mlm = -jnp.take_along_axis(lp, mlm_y[..., None], -1)[..., 0]
        lp2 = jax.nn.log_softmax(nsp, -1)
        l_nsp = -jnp.take_along_axis(lp2, nsp_y[:, None], -1)[:, 0]
        return float(jnp.mean(jnp.mean(l_mlm, -1) + l_nsp))
