"""Plain reference for ``ouro``: the forward pass of an Ouro looped decoder
(ByteDance Ouro 1.4B / 2.6B "LoopLM"; the keys of the published
``config.json``) over ONE whole sequence in straightforward float32
``jax.numpy`` -- no cache, no kernel, no batching, matmuls at ``highest``
precision.

``R = total_ut_steps``, ``L = num_hidden_layers``; ONE set of layer weights
``theta_1..theta_L``, used by every pass.  ``x = E[ids]``; for pass ``r =
1..R``, for layer ``l = 1..L``:

* ``u = RMS(x; g1_l)``; ``q = u Wq``, ``k = u Wk``, ``v = u Wv``
  (``num_attention_heads`` on ``num_key_value_heads`` heads of ``head_dim``,
  no bias; the program keeps the three in one ``qkv`` matrix, rows in that
  order);
* rotary on q and k, half-split form (``x * cos + (-x2 ‖ x1) * sin``),
  ``inv_freq_i = rope_theta ** (-2i / d)``, position = the token's index,
  the same in every pass;
* scores ``q k^T / sqrt(d)`` against THIS pass's keys of THIS layer, key
  ``j`` visible to query ``i`` iff ``j <= i``; softmax in float32;
  ``x <- x + RMS((P v) Wo; g2_l)`` -- a norm on the branch's output too
  (``assumed.sandwich_norm``; without it ``x + (P v) Wo``);
* ``s = RMS(x; g3_l)``; ``m = (SiLU(s Wgate) * s Wup) Wdown``;
  ``x <- x + RMS(m; g4_l)``;

and the pass ends ``h_r = RMS(x; g_f)``, the final norm, from which the next
pass starts (``assumed.pass_norm``; without it the next pass starts from
``x``).  ``logits = h_R W_head^T``.  The exit gate (``assumed.exit_gate``):
``lambda_r = sigmoid(h_r . w_g + b_g)``, ``p_r = lambda_r prod_{j<r} (1 -
lambda_j)`` for ``r < R`` and ``p_R = prod_{j<R} (1 - lambda_j)``; a row
leaves the loop at the first ``r`` with ``sum_{j<=r} p_j >=
early_exit_threshold`` (``exit_pdf``, ``exit_pass``), which at the published
threshold 1 is ``R``.

What ``config.json`` does not state is the configuration file's
``assumed``: the two extra norms a layer, the norm that ends a pass, the
gate's form, no bias and no QK-norm in attention.  One layer is one jitted
function, called ``R x L`` times with that layer's weights upcast to
float32 on the fly, so neither the compile nor the memory grows with the
depth or the passes (a published-width layer is 205 MB in f32); the sequence
is padded to ``assumed.reference_pad`` positions so that it compiles once a
process (every layer is causal: the padding never reaches a compared row).

**What decides ``correct``** (``lib/checks.greedy_agrees`` takes this
module's ``logits`` and ``LOGIT_RTOL`` and nothing else, so everything below
answers through the logits: they come back all NaN where a limit is not
held, which ``greedy_agrees`` reads as not correct, and a line on stderr
says which limit and by how much):

* ``LOGIT_RTOL`` holds the STRUCTURE: how far below its row's maximum the
  reference logit of a token the server chose may lie.  ``fault`` computes a
  WRONG model on purpose (``FAULTS``), for the demonstrations that it is
  tight.  What it cannot hold is the precision: with random weights 192
  layer applications multiply a rounding a hundredfold, so a sound bf16
  server reads 1-9% of max|ref| and a residual stream in bf16 10-12%
  (PERF.md section 6, PR 37) -- no limit separates the two there.
* The PRECISION of the residual stream is held on the object that was
  served -- the block the builder built and the programs the server
  compiled from it (``served``; the builder hands it over through
  ``lib/served.py``) -- in two steps, each where nothing is multiplied:

  - ``STREAM_RTOL``, a cell against the float32 layer on the same input.
    As every pass ends -- the stream at its largest, a branch a tenth of
    it -- the stream this module computed anyway is put through the served
    block's own last ``MixerCell`` (the object the programs were traced
    from: norms, projections, rotary, the append to a K‖V leaf in the
    cache's dtype, the decode kernel, the post-norms and both adds), and
    what the cell returns is held to this module's float32 layer on that
    input, worst entry over max|reference|.  Same input, so the number
    holds the rounding of ONE layer application.
  - ``LEAF_SHARE``, the compiled programs against their own cells.  The
    served prefill program of every prompt bucket and the served one-token
    step program, at the cell's slots and capacity
    (``deployment.served``), forward the sequence's beginning; the same
    tokens against the same caches go through :func:`chain_leaf` -- the
    block's first cells called one at a time by THIS module's loop, which
    carries the stream between them in float32 by construction -- and the
    K‖V rows that the program and the loop wrote into the leaf of the
    first pass's SECOND cell are compared entry by entry.  That leaf is a
    tap of the stream as the stack hands it from the first cell to the
    second: a program that hands it on in float32 writes the loop's rows
    but for the few entries where the compiler's reassociations flip a
    rounding; one whose stack, call boundary or compiled step keeps it in
    bf16 writes rows of which half differ.  So near the embedding
    because nothing is multiplied yet: at the END of the stack program and
    loop differ by as much as two draws of the same rounding noise (each
    flipped rounding flips others downstream, a sound program reads 7-8%
    of max|logit| there and a bf16 stack 24-25%: PERF.md section 6,
    PR 37), and no limit lies between with room.  Done once a served
    block: the programs do not change between requests.

  Together: the stack hands a cell's stream on as the cell returned it,
  in the programs that were timed; and a cell returns a float32 stream.
  The control that must fail is the PROGRAM with its stream in bf16
  (``tests/test_ouro.py``: where a branch is added, which ``STREAM_RTOL``
  refuses; between the cells, which ``LEAF_SHARE`` refuses), never a copy
  of it in this file.
"""
import functools
import math
import sys
import weakref

# The server computes in bf16 (weights and matrix-product operands, the K‖V
# cache; float32 accumulation, residual stream, norms, rotary and softmax)
# and the reference in float32: R x L = 192 layer applications at the
# published sizes, each with two branches whose operands are rounded.  A
# token the server chose greedily must have a reference logit within this
# share of max|ref| of its row's reference maximum
# (lib/checks.greedy_agrees).  It lies between the served program's largest
# reading and the nearest wrong model's (PERF.md section 6, PR 37).
LOGIT_RTOL = 0.25
# The served block's cell against this module's float32 layer on the same
# input stream, the last layer of every pass, worst entry as a share of
# max|reference|.  The served cell rounds its matrix products' operands, the
# cache and the probabilities to bf16 and keeps the stream in float32; a
# cell that keeps the stream in bf16 rounds entries of 30-60 to a quarter.
# Readings: PERF.md section 6, PR 37.
STREAM_RTOL = 1e-3
# Of the K‖V entries that a served program wrote into leaf TAP (the first
# pass's second cell: the stream as the stack handed it on from the first),
# the share that differs from what the loop over the block's own cells
# wrote there.  Readings: PERF.md section 6, PR 37.
LEAF_SHARE = 0.05
TAP = 1
FAULTS = ("stream_bf16", "no_post_norm", "no_pass_norm", "one_pass_short",
          "neighbour_cache")
# ``served=`` of :func:`logits`: the block the builder handed to
# ``lib/served.py`` for this configuration
BUILT = object()


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotary(x, cos, sin):
    """x (T, H, d); cos, sin (T, d / 2)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, cos, sin, seen, *, heads, kv_heads, dh, eps, sandwich,
           stream_bf16):
    """One layer application on the stream ``x`` (T, U): ``(x, (k, v))``,
    the keys (rotated) and values it made.  ``seen``: the ``(k, v)`` its
    queries attend to INSTEAD of their own (the fault of a pass reading its
    neighbour's cache), or None."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = {name: a.astype(f32) for name, a in w.items()}
    t, g = x.shape[0], heads // kv_heads
    # the fault: the stream rounded to bf16 wherever a branch is added
    keep = (lambda a: a.astype(jnp.bfloat16).astype(f32)) if stream_bf16 \
        else (lambda a: a)
    post = (lambda y, name: _rms(y, w[name], eps)) if sandwich \
        else (lambda y, name: y)

    u = _rms(x, w["ln_mixer.gamma"], eps)
    qkv = (u @ w["mixer.qkv.weight"].T).reshape(t, heads + 2 * kv_heads, dh)
    q, k, v = qkv[:, :heads], qkv[:, heads:heads + kv_heads], \
        qkv[:, heads + kv_heads:]
    q, k = _rotary(q, cos, sin), _rotary(k, cos, sin)
    ks, vs = (k, v) if seen is None else seen
    scores = jnp.einsum("qhgd,khd->hgqk", q.reshape(t, kv_heads, g, dh),
                        ks) / math.sqrt(dh)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                       axis=-1)
    a = jnp.einsum("hgqk,khd->qhgd", p, vs).reshape(t, heads * dh)
    x = keep(x + post(a @ w["mixer.o_proj.weight"].T, "post_mixer.gamma"))

    s = _rms(x, w["ln_ffn.gamma"], eps)
    gate = s @ w["ffn.gate.weight"].T
    m = (gate * jax.nn.sigmoid(gate) * (s @ w["ffn.up.weight"].T)) \
        @ w["ffn.down.weight"].T
    return keep(x + post(m, "post_ffn.gamma")), (k, v)


@functools.lru_cache(maxsize=None)
def _jitted_layer(**static):
    import jax

    return jax.jit(functools.partial(_layer, **static))


@functools.lru_cache(maxsize=None)
def _final_norm(eps):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, g: _rms(x, g.astype(jnp.float32), eps))


def _nd(a, dtype=None):
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray

    return NDArray(jnp.asarray(a, dtype))


def _gap(got, want):
    """Worst entry of ``got - want`` as a share of max|want|."""
    import jax.numpy as jnp

    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def cell_gap(served, x_in, x_out, n):
    """``STREAM_RTOL``'s reading: the served block's last cell on the stream
    ``x_in`` (T, U) float32 from an empty leaf, against ``x_out``, what this
    module's float32 layer made of the same stream; rows ``:n``.  Called
    under no precision of this module's: as the server runs it."""
    import jax.numpy as jnp

    t = x_in.shape[0]
    start, count = _nd([0], jnp.int32), _nd([t], jnp.int32)
    cell = served.layers[len(served.layers) - 1]
    got, _, _ = cell(_nd(x_in[None]), served.begin_cache(1, t)[-1],
                     (start, count, served.positions(start, t)))
    if got._data.dtype != jnp.float32:
        return float("inf")                   # no float32 stream to compare
    return _gap(got._data[0, :n], x_out[:n])


def chain_leaf(served, params, tokens, cache, cache_len, n_tokens):
    """The K‖V leaf that cell ``TAP`` of the first pass writes for
    ``tokens`` (B, T) against ``cache``, through the served block's first
    CELLS called one at a time by this loop: the embedding is this
    module's, and the stream between the cells is float32 because this
    loop carries it.  None where a cell hands back anything else."""
    import jax.numpy as jnp

    x = jnp.asarray(params["word_embed.weight"])[tokens].astype(jnp.float32)
    lens = _nd(cache_len, jnp.int32)
    step = (lens, _nd(n_tokens, jnp.int32),
            served.positions(lens, tokens.shape[1]))
    for i in range(TAP + 1):
        out, leaves, _ = served.layers[i](_nd(x), cache[i], step)
        x = out._data
        if x.dtype != jnp.float32:
            return None
    return leaves[0]._data


def _differing(got, want):
    """Share of entries that are not the same number to half a bf16 ulp."""
    import jax.numpy as jnp

    if want is None:
        return 1.0
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.mean(jnp.abs(got - want) > 2.0 ** -9 * jnp.maximum(
        jnp.abs(got), jnp.abs(want))))


def leaf_shares(served, params, config, tokens):
    """``LEAF_SHARE``'s readings, ``{program: share}``: the served prefill
    program of every prompt bucket from an empty row cache, then the served
    one-token step program on all slots -- each slot the row cache of the
    largest bucket cut at another length, fed the token that followed
    there -- against :func:`chain_leaf` on the same tokens and caches: the
    share of the entries either wrote into leaf ``TAP`` that differ.  The
    shapes are the cell's (``deployment.served``), so the prefill programs
    are the executables the server ran and the step program is
    ``serve.decode``'s own stepper over this block, compiled to the same
    program."""
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu.serve import decode

    shapes = config["deployment"]["served"]
    slots, capacity = shapes["slots"], shapes["capacity"]
    tokens = onp.asarray(tokens, onp.int32)
    shares, row = {}, None
    for bucket in sorted(shapes["prompt_buckets"]):
        t = min(bucket, len(tokens) - 1)
        toks = onp.zeros((1, bucket), onp.int32)
        toks[0, :t] = tokens[:t]
        want = chain_leaf(served, params, toks,
                          served.begin_cache(1, capacity), [0], [t])
        row = served(_nd(toks), served.begin_cache(1, capacity),
                     _nd([0], jnp.int32), _nd([t], jnp.int32))[1]
        shares[f"prefill {bucket}"] = _differing(
            row[TAP][0]._data[:, :, :t], want[:, :, :t])
    lens = t - onp.arange(slots) * max(1, t // (2 * slots))
    nxt = tokens[lens]
    cache = tuple(tuple(_nd(jnp.repeat(leaf._data, slots, axis=0))
                        for leaf in leaves) for leaves in row)
    del row
    want = chain_leaf(served, params, nxt[:, None], cache, lens,
                      onp.ones(slots, onp.int32))
    stepper = decode._DecodeStepper(served)
    stepper.hybridize(donate_args=(2,))
    cache = stepper(_nd(onp.zeros(slots), jnp.int32),
                    decode.DecodeEntry._step_inputs(
                        nxt, onp.ones_like(nxt), lens, onp.ones_like(nxt)),
                    cache)[2]
    at = (onp.arange(slots), slice(None), lens)     # the row each slot wrote
    shares[f"step {slots}"] = _differing(
        cache[TAP][0]._data[at], None if want is None else want[at])
    return shares


def _forward(params, config, tokens, fault, served):
    """``(ends, gaps)``: :func:`passes`' list, and :func:`cell_gap`'s
    reading at every pass's last layer where ``served`` is a block."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    c = config
    assumed = c.get("assumed", {})
    if c.get("rope_scaling") is not None or assumed.get("qk_norm") \
            or assumed.get("attention_bias"):
        raise ValueError("this reference knows plain rotary positions and "
                         "attention without bias or QK-norm only")
    dh, eps, f32 = c["head_dim"], c["rms_norm_eps"], jnp.float32
    n = len(tokens)
    pad = max(assumed.get("reference_pad", 0), n)
    own = _jitted_layer(
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        dh=dh, eps=eps,
        sandwich=assumed.get("sandwich_norm", False)
        and fault != "no_post_norm",
        stream_bf16=fault == "stream_bf16")
    pass_norm = assumed.get("pass_norm", False) and fault != "no_pass_norm"
    loops = c["total_ut_steps"] - (fault == "one_pass_short")
    highest = functools.partial(jax.default_matmul_precision, "highest")
    inv = float(c["rope_theta"]) ** (-jnp.arange(0, dh, 2, dtype=f32) / dh)
    angle = jnp.arange(pad, dtype=f32)[:, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    stack = []
    for i in range(c["num_hidden_layers"]):
        pre = f"layers.{i}."
        stack.append({k[len(pre):]: v for k, v in params.items()
                      if k.startswith(pre)})
    g_f = jnp.asarray(params["ln_f.gamma"])
    with highest():
        tok = jnp.zeros((pad,), jnp.int32).at[:n].set(
            jnp.asarray(tokens, jnp.int32))
        x = jnp.asarray(params["word_embed.weight"])[tok].astype(f32)
    ends, gaps, before = [], [], [None] * len(stack)
    for r in range(loops):
        for i, w in enumerate(stack):
            seen = before[i] if fault == "neighbour_cache" else None
            x_in = x
            with highest():
                x, before[i] = own(x, w, cos, sin, seen)
            if served is not None and i == len(stack) - 1:
                gaps.append(cell_gap(served, x_in, x, n))
        with highest():
            h = _final_norm(eps)(x, g_f)
        ends.append(h[:n])
        if pass_norm:
            x = h
    return ends, gaps


def passes(params, config, tokens, fault=None):
    """``[h_1, .., h_R]``, each (T, U) float32: the stream as every pass
    ends, through the final norm.  ``fault``: one of ``FAULTS``."""
    return _forward(params, config, tokens, fault, None)[0]


# served block -> whether its programs held LEAF_SHARE
_PROGRAMS = weakref.WeakKeyDictionary()


def _say(what, readings, limit, name, held):
    print(f"[reference ouro] {what} reads {readings} against {name} "
          f"{limit:g}: {'held' if held else 'NOT HELD'}", file=sys.stderr,
          flush=True)
    return held


def logits(params, config, tokens, fault=None, served=BUILT):
    """(T, V) float32 logits of one sequence of token ids on ``params``
    (name -> array under the program's parameter names; any float dtype).
    ``served``: the block that was served (by default the one the builder
    kept for ``config``, ``lib/served.py``; None for the plain reference
    and nothing else) -- the logits are all NaN where it does not hold
    ``STREAM_RTOL`` on this sequence or ``LEAF_SHARE`` (read once a
    block, on the first sequence it is asked about).  ``fault``: one of
    ``FAULTS``, a wrong model on purpose; the check that decides
    ``correct`` passes none."""
    import jax
    import jax.numpy as jnp

    if served is BUILT:
        from lib import served as kept

        served = kept.block_of(config)
    ends, gaps = _forward(params, config, tokens, fault, served)
    held = True
    if served is not None:
        held = _say(f"stream: {len(tokens)} tokens, the served block's last "
                    "cell as each pass ends", [f"{g:.2e}" for g in gaps],
                    STREAM_RTOL, "STREAM_RTOL",
                    max(gaps) <= STREAM_RTOL)       # a NaN gap is not held
        if served not in _PROGRAMS:
            read = leaf_shares(served, params, config, tokens)
            _PROGRAMS[served] = _say(
                f"programs: of the rows the served programs wrote into leaf "
                f"{TAP}, the share that differs from their own cells'",
                {k: f"{g:.2e}" for k, g in read.items()},
                LEAF_SHARE, "LEAF_SHARE", max(read.values()) <= LEAF_SHARE)
        held = held and _PROGRAMS[served]
    with jax.default_matmul_precision("highest"):
        out = ends[-1] @ jnp.asarray(params["head.weight"]).astype(
            jnp.float32).T
    return out if held else jnp.full_like(out, jnp.nan)


def exit_pdf(params, config, tokens):
    """(T, R) float32: the probability that a position leaves the loop
    after pass ``r``; sums to one over ``R``."""
    import jax
    import jax.numpy as jnp

    w_g = jnp.asarray(params["exit_gate.weight"]).astype(jnp.float32)[0]
    b_g = jnp.asarray(params["exit_gate.bias"]).astype(jnp.float32)[0]
    with jax.default_matmul_precision("highest"):
        lam = [jax.nn.sigmoid(h @ w_g + b_g)
               for h in passes(params, config, tokens)]
    stay, out = jnp.ones_like(lam[0]), []
    for gate in lam[:-1]:
        out.append(gate * stay)
        stay = stay * (1.0 - gate)
    return jnp.stack(out + [stay], axis=-1)


def exit_pass(pdf, threshold):
    """The pass (1-based) at which each row leaves the loop: the first
    ``r`` whose cumulative probability reaches ``threshold``, ``R`` at the
    latest."""
    import numpy as onp

    cum = onp.cumsum(onp.asarray(pdf, onp.float64), axis=-1)
    reached = cum >= threshold
    reached[..., -1] = True             # the last pass ends the loop
    return reached.argmax(-1) + 1
