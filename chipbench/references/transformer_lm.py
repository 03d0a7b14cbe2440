"""Plain reference for ``transformer_lm``: a GPT-2 forward pass in
straightforward float32 ``jax.numpy`` over a whole sequence -- no cache, no
kernel, no batching, matmuls at ``highest`` precision.

It follows the program's block (``gluon/model_zoo/decoder.py``), not the
paper, where they part: learned absolute positions, pre-norm layers
(x + attn(ln(x)); x + ffn(ln(x))), final LayerNorm, head tied to the word
embedding plus an ``out_bias`` the published model lacks (zeros here), and
**erf GELU** (``bert.py:PositionwiseFFN``) where GPT-2 has the tanh form.

One layer is one jitted function called ``n_layer`` times with that layer's
weights upcast to f32 on the fly, so neither the compile nor the memory
grows with depth (XL: one layer's f32 weights are 123 MB).
"""
import functools
import math

# The server computes in bf16 (weights, activations, KV cache; f32 softmax
# and accumulation); the reference in f32.  chip_smoke.py measured 1.4% of
# max|ref| between the program's own kernel and reference paths at GPT-2
# small; bf16 rounding through 48 layers against f32 is reckoned a few
# percent.  A token the server chose must have a reference logit within
# this share of max|ref| of its row's reference maximum.  An int8 cache or
# a position shifted by one moves logits by tens of percent of max|ref| at
# random weights (chipbench/tests/test_reference.py shows the shift fails).
LOGIT_RTOL = 0.05
PAD = 256


def _ln(x, g, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _layer(x, w, heads, eps):
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    t, u = x.shape
    d = u // heads
    h = _ln(x, w["ln_att.gamma"], w["ln_att.beta"], eps)
    qkv = h @ w["attention.qkv.weight"].T + w["attention.qkv.bias"]
    q, k, v = (a.reshape(t, heads, d).transpose(1, 0, 2)
               for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(d)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,hkd->hqd", p, v).transpose(1, 0, 2).reshape(t, u)
    x = x + a @ w["attention.proj.weight"].T + w["attention.proj.bias"]
    h = _ln(x, w["ln_ffn.gamma"], w["ln_ffn.beta"], eps)
    f = h @ w["ffn.ffn1.weight"].T + w["ffn.ffn1.bias"]
    f = 0.5 * f * (1.0 + jax.lax.erf(f / math.sqrt(2.0)))
    return x + f @ w["ffn.ffn2.weight"].T + w["ffn.ffn2.bias"]


def logits(params, config, tokens):
    """(T, V) float32 logits of one sequence of token ids on ``params``
    (name -> array under the program's parameter names; any float dtype)."""
    import jax
    import jax.numpy as jnp

    eps = config["layer_norm_epsilon"]
    heads = config["n_head"]
    layer = jax.jit(functools.partial(_layer, heads=heads, eps=eps))
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        n = len(tokens)
        # pad to a multiple of PAD (few compiled shapes); causal attention
        # keeps the padding out of the rows that are returned
        padded = min(-(-n // PAD) * PAD, config["n_positions"])
        tok = jnp.zeros((padded,), jnp.int32).at[:n].set(
            jnp.asarray(tokens, jnp.int32))
        pos = jnp.arange(padded)
        x = (jnp.asarray(params["word_embed.weight"])[tok].astype(f32)
             + jnp.asarray(params["position_weight"])[pos].astype(f32))
        for i in range(config["n_layer"]):
            pre = f"layers.{i}."
            w = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            x = layer(x, w)
        x = _ln(x, jnp.asarray(params["ln_f.gamma"]).astype(f32),
                jnp.asarray(params["ln_f.beta"]).astype(f32), eps)
        head = jax.jit(lambda h, e, b: h @ e.astype(f32).T + b.astype(f32))
        return head(x, jnp.asarray(params["word_embed.weight"]),
                    jnp.asarray(params["out_bias"]))[:n]
