"""Model builder ``ouro``: the program's ``OuroLM``
(gluon/model_zoo/ouro.py: one stack of grouped-query attention and gated-FFN
layers run ``total_ut_steps`` times over the same weights, a norm on each
branch's output, a K‖V cache a layer AND a pass) from the configuration
under its published keys, in the ``dtype`` it states (bf16), random weights
from the seed -- every parameter is created and initialised in its own dtype
and exists once.

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
    lm = mx.gluon.model_zoo.get_model("ouro", config=config,
                                      dtype=jnp.dtype(config["dtype"]))
    lm.initialize()
    # the reference holds the stream's precision on this very block and the
    # programs the server compiles from it (references/ouro.py)
    from lib import served

    return served.keep(config, lm)


def attn_full_bytes(config, positions):
    """Every live row is read once a step by each layer IN EACH PASS (pass
    ``r`` attends to its own keys and values, which no other pass holds):
    ``positions`` (``serve.step_live_positions``: live rows summed over
    slots and steps) x passes x layers x K and V of every KV head
    (8192 B at the published sizes), bf16."""
    return positions * config["total_ut_steps"] \
        * config["num_hidden_layers"] * 2 * config["num_key_value_heads"] \
        * config["head_dim"] * BF16


def loop_dense_bytes(config, passes):
    """The stack's matrices, streamed once a pass: ``passes``
    (``serve.stack_passes``: passes summed over the window's decode steps
    and prefill pieces) x layers x the seven matrices of a layer (q, k, v,
    o; gate, up, down), bf16 -- 4.93 GB a pass at the published sizes.

    Why every pass must read them again: 4.93 GB does not stay on the chip
    (its fast memory holds some tens of MB), and pass ``r + 1`` of layer 1
    needs pass ``r`` of layer 48, so no order of the work lets a matrix
    serve two passes while it is there; rows of one batch share a read, so
    the bytes do not grow with the slots.  Left out, so that the share reads
    low and never high: the head's 0.2 GB a forward, the norms' scales and
    the activations."""
    d, f = config["hidden_size"], config["intermediate_size"]
    qkvo = d * config["head_dim"] * (2 * config["num_attention_heads"]
                                     + 2 * config["num_key_value_heads"])
    return passes * config["num_hidden_layers"] * (qkvo + 3 * d * f) * BF16
