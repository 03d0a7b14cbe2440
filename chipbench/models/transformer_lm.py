"""Model builder ``transformer_lm``: the program's ``TransformerLM`` (GPT-2
shaped: learned positions, pre-norm LayerNorm, GELU, tied head) in bf16 with
random weights from the seed, as ``chip_smoke.py:_gpt2_small`` builds it.

A serving builder gives the ``serve_closed`` driver ``build``.
"""


def build(config, seed):
    import jax.numpy as jnp

    import mxnet_tpu as mx

    mx.random.seed(seed)
    lm = mx.gluon.model_zoo.get_model(
        "transformer_lm", dtype=jnp.bfloat16,
        vocab_size=config["vocab_size"], units=config["n_embd"],
        hidden_size=config["assumed"]["n_inner"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        max_length=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"])
    lm.initialize(mx.init.Xavier())
    lm.cast(jnp.bfloat16)
    return lm
