"""Model builder ``mellum``: the program's ``MellumLM``
(gluon/model_zoo/mellum.py: grouped-query attention with rotary positions on
full and window layers, a held-expert MoE with a softmax router) from the
configuration under its published keys, bf16, random weights from the seed
-- every parameter is created and initialised in its own dtype, so no
float32 copy of the 3.5 G parameters exists at any time.

A serving builder gives the ``serve_closed`` driver ``build``; the roofline
readers of this configuration call the byte functions below.  **They count
only what ANY implementation must move**: a count of work the program did
not have to do would let a share pass 100%.
"""

BF16 = 2


def build(config, seed):
    import jax.numpy as jnp

    import mxnet_tpu as mx

    mx.random.seed(seed)
    lm = mx.gluon.model_zoo.get_model("mellum", config=config,
                                      dtype=jnp.bfloat16)
    lm.initialize()
    return lm


def moe_layers(config):
    return config["mlp_layer_types"].count("sparse")


def moe_experts_bytes(config, experts_hit):
    """The three matrices of every held expert that saw a token
    (``serve.moe_experts_hit``: summed over layers and calls), bf16.  An
    expert nobody chose need not be read, so it is not counted."""
    return experts_hit * 3 * config["hidden_size"] \
        * config["moe_intermediate_size"] * BF16


def _position_bytes(config, kind):
    """K and V of every KV head of one position, over the layers of
    ``kind``, bf16 (2048 B a layer at the published sizes)."""
    return config["layer_types"].count(kind) * 2 \
        * config["num_key_value_heads"] * config["head_dim"] * BF16


def attn_full_bytes(config, positions):
    """Every live row is read once a step by each full layer:
    ``positions`` (``serve.step_live_positions``: live rows summed over
    slots and steps) x full layers x a row."""
    return positions * _position_bytes(config, "full_attention")


def attn_window_bytes(config, positions):
    """A window layer reads the rows inside its window only:
    ``positions`` (``serve.step_window_positions``: min(live, window) summed
    over slots and steps) x window layers x a row."""
    return positions * _position_bytes(config, "sliding_attention")
