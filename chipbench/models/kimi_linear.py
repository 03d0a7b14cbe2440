"""Model builder ``kimi_linear``: the program's ``KimiLinearLM``
(gluon/model_zoo/kimi_linear.py: KDA and MLA mixers, a held-expert MoE) from
the configuration under its published keys, bf16, random weights from the
seed -- every parameter is created and initialised in its own dtype, so no
float32 copy of the 4.3 G parameters exists at any time.

A serving builder gives the ``serve_closed`` driver ``build``; the roofline
readers of this configuration call the byte and operation functions below.
**They count only what ANY implementation must move or compute**: a count of
work the program did not have to do would let a share pass 100%.
"""

BF16, F32 = 2, 4


def build(config, seed):
    import jax.numpy as jnp

    import mxnet_tpu as mx

    mx.random.seed(seed)
    lm = mx.gluon.model_zoo.get_model("kimi_linear", config=config,
                                      dtype=jnp.bfloat16)
    lm.initialize()
    return lm


def moe_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def kda_step_bytes(config, slot_steps):
    """One decode step reads and writes each OCCUPIED slot's state once:
    ``slot_steps`` (occupied slots summed over the steps) x KDA layers x
    heads x d_k x d_v float32, twice.  The q, k, v, g rows (KB a slot) are
    left out."""
    lin = config["linear_attn_config"]
    state = lin["num_heads"] * lin["head_dim"] ** 2 * F32
    return 2 * slot_steps * len(lin["kda_layers"]) * state


def moe_experts_bytes(config, experts_hit):
    """The three matrices of every held expert that saw a token
    (``serve.moe_experts_hit``: summed over layers and calls), bf16.  An
    expert nobody chose need not be read, so it is not counted."""
    return experts_hit * 3 * config["hidden_size"] \
        * config["moe_intermediate_size"] * BF16


def mla_decode_bytes(config, positions):
    """Every live latent row is read once a step by each MLA layer:
    ``positions`` (live rows summed over slots and steps) x layers x
    (kv_lora_rank + qk_rope_head_dim) bf16."""
    row = (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * BF16
    return positions * len(config["linear_attn_config"]["full_attn_layers"]) \
        * row


def kda_chunk_flops(config, tokens, chunk=64):
    """Multiply-adds x 2 of the chunk-parallel recurrence for ``tokens``
    prompt tokens, per token and head: the two decayed Gram matrices
    (2 x 2*C*d_k), the triangular solve (C*(d_k + d_v)), the old state
    applied to keys and queries (2 x 2*d_k*d_v), the intra-chunk output
    (2*C*d_v) and the state update (2*d_k*d_v).  Projections, conv and
    gates are outside the scope and not counted."""
    lin = config["linear_attn_config"]
    d = lin["head_dim"]
    per = 4 * chunk * d + chunk * 2 * d + 6 * d * d + 2 * chunk * d
    return tokens * lin["num_heads"] * len(lin["kda_layers"]) * per
