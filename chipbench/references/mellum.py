"""Plain reference for ``mellum``: the forward pass of a Mellum decoder (the
keys of the published ``config.json``; grouped-query attention as HF's
Qwen3-MoE computes it, whose keys the config carries) over ONE whole
sequence in straightforward float32 ``jax.numpy`` -- no cache, no ring, no
kernel, no batching, matmuls at ``highest`` precision.  Attention is taken a
block of query rows at a time so that an 8k-token sequence fits; nothing
else is blocked.

``h_0 = E[ids]``; layers ``l = 0..L-1``:

* ``a = RMS(h; g1)``; ``q = a Wq`` (``num_attention_heads`` x ``head_dim``),
  ``k = a Wk``, ``v = a Wv`` (``num_key_value_heads`` x ``head_dim``), no
  bias (the program keeps the three in one ``qkv`` matrix, rows in that
  order); with ``assumed.qk_norm``, q and k are RMS-normalised over each
  head's ``head_dim`` (a scale a layer);
* rotary on q and k, half-split form (``x * cos + (-x2 ‖ x1) * sin``),
  position = the token's index.  ``rope_parameters[layer type]``:
  ``default`` is ``inv_freq_i = theta ** (-2i / d)``; ``yarn`` is, with
  ``c(n) = d ln(L / (2 pi n)) / (2 ln theta)``, ``low = floor(c(beta_fast))``,
  ``high = ceil(c(beta_slow))``, ``ramp_i = clip((i - low) / (high - low),
  0, 1)``: ``(1 - ramp_i) f_i + ramp_i f_i / factor``, and cosine and sine
  both times ``attention_factor``;
* scores ``q k^T / sqrt(d)``, a KV head serving its ``g`` query heads; key
  ``j`` visible to query ``i`` iff ``j <= i`` and, in a
  ``sliding_attention`` layer, ``j > i - sliding_window``; softmax in
  float32; ``h <- h + (P v) Wo``;
* ``m = RMS(h; g2)``; ``p = softmax(m Wr^T)`` over all
  ``published.num_experts``; the ``num_experts_per_tok`` largest;
  ``w_e = p_e / sum of the chosen`` (``norm_topk_prob``); ``h <- h + sum
  over chosen e in HELD of w_e (SiLU(m Wg_e) * m Wu_e) Wd_e``, HELD being
  experts ``deployment.held_start ..`` + ``num_experts``.  What the other
  experts would add is left out, here as in the program (guide
  ``model-configs`` section 4).

``logits = RMS(h_L; g) W_head^T`` over the rows of the vocabulary held.

Departures from the published model are the configuration file's
(``assumed``, ``departures``): QK-norm is assumed (the config has no key for
it), no multi-token-prediction head is built (the config declares none), the
vocabulary is the slice held.

One layer is one jitted function per layer type, called with that layer's
weights upcast to f32 on the fly, so neither the compile nor the memory
grows with depth (a published-width expert stack of 16 is 3 x 132 MB in
f32).

**Three limits decide ``correct``** (``lib/checks.greedy_agrees`` calls
``logits`` and reads ``LOGIT_RTOL``; it knows of nothing else, so the other
two answer through the logits: all NaN when one is not held, which
``greedy_agrees`` reads as not correct):

* ``LOGIT_RTOL`` holds the structure: how far below its row's maximum the
  reference logit of a token the server chose may lie.  ``fault`` computes a
  WRONG model on purpose (``FAULTS``), for the demonstrations that it is
  tight; what it cannot tell from the server's own bf16 rounding (angles in
  bf16, a window one position too long) is the next limit's.
* ``ATTN_RTOL`` holds the positions, the window and the precision of the
  attention path.  In the first layer of each type the heads this module
  computed anyway (q and k normalised, not yet rotated, and v) are also put
  through the attention UNDER TEST -- by default the program's own
  (``program_attention``: ``gluon/model_zoo/mellum.py``'s ``rope_inv_freq``,
  ``rope_tables`` and ``attend``, which is all a served layer does between
  its projections: rotary, the append to a K‖V leaf in the cache's dtype --
  a ring of window + chunk rows in a window layer -- and the decode kernel),
  in pieces of ``assumed.prefill_chunk`` queries as an admission forwards a
  long prompt -- and its output is held to this module's float32 attention
  on the same heads, worst entry over max|reference|.  Same inputs, so the
  number holds the bf16 rounding of the heads and the probabilities and
  nothing of the layers before.  ``bf16_rotary_attention`` and
  ``wide_window_attention`` are the controls that must fail.
* ``ROUTE_RTOL`` holds the precision of the router, which the logits cannot:
  the configuration states float32 for it, and a bf16 router moves the
  logits no further than the bf16 matrix products of a sound server do (both
  swap an expert where the top-k choice sits on a near-tie).  In every
  layer the rows ``m`` that this module computed anyway are also put through
  the router UNDER TEST -- by default the program's own
  (``program_router``: ``gluon/model_zoo/mixer_lm.py:route_rows``, the
  served layer's whole path from its normed rows to the choice, casts
  included) -- and its choice and weights are held to this module's float32
  probabilities on the same rows.  ``bf16_router`` is the control that must
  fail.
"""
import functools
import math

# The server computes in bf16 (weights and matrix-product operands, K‖V
# cache; float32 accumulation, residual stream, norms, rotary, softmax and
# router) and the reference in f32.  A token the server chose greedily must
# have a reference logit within this share of max|ref| of its row's
# reference maximum (lib/checks.greedy_agrees).  Readings at the published
# widths on the chip and the faults that bound it from above: PERF.md
# section 6, PR 33.
LOGIT_RTOL = 0.04
# The router under test against this module's float32 probabilities on the
# same rows, worst layer and token: the probability mass its choice gives
# away against the best choice, plus how far its weights lie from the
# chosen probabilities renormalised, both as shares of 1.  A float32 router
# reads rounding (a swap at an exact tie gives away nothing); one that
# rounds its operands to bf16 reads the size of bf16's rounding.  Readings:
# PERF.md section 6, PR 33.
ROUTE_RTOL = 1e-4
# The attention under test against this module's float32 attention on the
# same heads, first layer of each type, worst entry as a share of
# max|reference|.  The served path rounds the heads and the probabilities
# to bf16 and reads 0.35-0.47% at the published widths; a window one
# position too long lets one more key into every row's sum and reads 2.4%,
# angles in bf16 9-43% (my chip runs, PR 33: PERF.md section 6).  The limit
# lies between the two nearest readings, about twice from each.
ATTN_RTOL = 0.01
QUERY_BLOCK = 512
FAULTS = ("no_attention_factor", "window_off_by_one", "no_renorm",
          "rotary_bf16", "no_yarn", "experts_off_by_one")


def program_router(m, w_r, k, renorm):
    """The served layer's own path from its normed rows ``m`` (T, d)
    float32 to the choice."""
    from mxnet_tpu.gluon.model_zoo import mixer_lm

    return mixer_lm.route_rows(m, w_r, None, k, 1.0, renorm)


def _bf16_tables(rope, start, t):
    """Rotary tables with the angle's product in bf16, the nearest precision
    below the float32 that is stated: past position 256 bf16 no longer holds
    every integer."""
    import jax.numpy as jnp

    inv, gain = rope
    pos = start[:, None] + jnp.arange(t)[None]
    angle = (pos.astype(jnp.bfloat16)[..., None]
             * jnp.asarray(inv, jnp.bfloat16)).astype(jnp.float32)
    return gain * jnp.cos(angle), gain * jnp.sin(angle)


@functools.lru_cache(maxsize=None)
def _program_piece(rope_json, dh, window, tables):
    """One jitted piece of the program's attention path: (q, k, v (1, t, H,
    dh) float32, the K‖V leaf, start (1,)) -> (o (1, Hq, t, dh), leaf)."""
    import json

    import jax
    from mxnet_tpu.gluon.model_zoo import mellum

    rope = mellum.rope_inv_freq(json.loads(rope_json), dh)
    tables = tables or mellum.rope_tables

    def piece(q, k, v, kv, start):
        cos, sin = tables(rope, start, q.shape[1])
        return mellum.attend(q, k, v, kv, start, cos, sin, window)

    return jax.jit(piece)


def program_attention(q, k, v, rope_params, window, chunk, dtype,
                      tables=None, widen=0):
    """The served layer's own path from its heads -- q (T, Hq, dh), k and v
    (T, Hkv, dh) float32, q and k normalised and not yet rotated -- to its
    attention output (T, Hq * dh): pieces of ``chunk`` queries against one
    K‖V leaf in ``dtype``, every position's for a full layer, a ring of
    ``window + chunk`` rows under a window.  ``tables`` and ``widen`` are
    the controls' (other rotary tables; a window that many positions too
    long, on a ring with the room for it)."""
    import json

    import jax.numpy as jnp

    t, hq, dh = q.shape
    pad = -t % chunk                   # whole pieces; the rest comes last
    q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    rows = t + pad if window is None \
        else window + chunk * (2 if widen else 1)
    piece = _program_piece(json.dumps(rope_params, sort_keys=True), dh,
                           None if window is None else window + widen, tables)
    kv = jnp.zeros((1, k.shape[1], rows, 2 * dh), dtype)
    out = []
    for start in range(0, t, chunk):
        at = slice(start, start + chunk)
        o, kv = piece(q[None, at], k[None, at], v[None, at], kv,
                      jnp.asarray([start], jnp.int32))
        out.append(o[0].transpose(1, 0, 2).reshape(chunk, hq * dh))
    return jnp.concatenate(out)[:t]


bf16_rotary_attention = functools.partial(program_attention,
                                          tables=_bf16_tables)
wide_window_attention = functools.partial(program_attention, widen=1)


def bf16_router(m, w_r, k, renorm):
    """The control: the plain router with its product's operands rounded
    to bf16, the nearest precision below the float32 that is stated."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    p = jax.nn.softmax((m.astype(bf16) @ w_r.astype(bf16).T
                        ).astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(p, k)
    return (w / w.sum(-1, keepdims=True) if renorm else w), idx


def rope_inv_freq(params, d):
    """``(inv_freq (d / 2,) float32, gain)`` of one ``rope_parameters``
    entry (the closed form in this module's docstring)."""
    import numpy as onp

    theta = float(params["rope_theta"])
    f = theta ** (-onp.arange(0, d, 2, dtype=onp.float64) / d)
    if params.get("rope_type", "default") == "default":
        return f.astype(onp.float32), 1.0
    span, factor = params["original_max_position_embeddings"], params["factor"]
    c = lambda n: d * math.log(span / (2 * math.pi * n)) / (2 * math.log(theta))
    low = max(math.floor(c(params["beta_fast"])), 0)
    high = min(math.ceil(c(params["beta_slow"])), d - 1)
    ramp = onp.clip((onp.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return ((1 - ramp) * f + ramp * f / factor).astype(onp.float32), \
        float(params["attention_factor"])


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


def _attention(q, k, v, window):
    """q (T, Hq, d), k, v (T, Hkv, d) -> (T, Hq * d): softmax attention, a
    block of ``QUERY_BLOCK`` query rows at a time."""
    import jax
    import jax.numpy as jnp

    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qb = min(QUERY_BLOCK, t)
    kpos = jnp.arange(t)

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        rows = rows.reshape(qb, hkv, g, d)
        s = jnp.einsum("qhgd,khd->hgqk", rows, k) / math.sqrt(d)
        qpos = i * qb + jnp.arange(qb)
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen = seen & (kpos[None, :] > qpos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(qb, hq * d)

    return jax.lax.map(block, jnp.arange(t // qb)).reshape(t, hq * d)


def _layer(x, w, cos, sin, *, heads, kv_heads, dh, eps, window, qk_norm,
           top_k, renorm, held_start, fault, router):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = {k: v.astype(f32) for k, v in w.items()}
    t = x.shape[0]
    a = _rms(x, w["ln_mixer.gamma"], eps)
    qkv = (a @ w["mixer.qkv.weight"].T).reshape(t, heads + 2 * kv_heads, dh)
    q, k, v = qkv[:, :heads], qkv[:, heads:heads + kv_heads], \
        qkv[:, heads + kv_heads:]
    if qk_norm:
        q = _rms(q, w["mixer.q_norm.gamma"], eps)
        k = _rms(k, w["mixer.k_norm.gamma"], eps)
    heads = (q, k, v)                   # what the attention under test gets
    q, k = _rotary(q, cos, sin), _rotary(k, cos, sin)
    if window is not None and fault == "window_off_by_one":
        window = window + 1
    o = _attention(q, k, v, window)
    x = x + o @ w["mixer.o_proj.weight"].T

    m = _rms(x, w["ln_ffn.gamma"], eps)
    p = jax.nn.softmax(m @ w["ffn.router.weight"].T, axis=-1)
    top, idx = jax.lax.top_k(p, top_k)
    gap = jnp.zeros((), f32)
    if router is not None:
        t_w, t_idx = router(m, w["ffn.router.weight"], top_k, renorm)
        mine = jnp.take_along_axis(p, t_idx, axis=-1)
        given_away = top.sum(-1) - mine.sum(-1)
        if renorm:
            mine = mine / mine.sum(-1, keepdims=True)
        gap = jnp.max(given_away + jnp.abs(t_w - mine).max(-1))
    if renorm and fault != "no_renorm":
        top = top / top.sum(-1, keepdims=True)

    n_held = w["ffn.experts_gate"].shape[0]

    def expert(y, e_w):
        e, gate, up, down = e_w
        if fault == "experts_off_by_one":   # its neighbour's tokens
            e = (e + 1) % n_held
        weight = jnp.sum(jnp.where(idx == held_start + e, top, 0.0), -1)
        out = ((m @ gate) * jax.nn.sigmoid(m @ gate) * (m @ up)) @ down
        return y + weight[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(n_held), w["ffn.experts_gate"], w["ffn.experts_up"],
        w["ffn.experts_down"]))
    return x + y, gap, heads + (o,)


@functools.lru_cache(maxsize=None)
def _jitted_layer(**static):
    import jax

    return jax.jit(functools.partial(_layer, **static))


def logits(params, config, tokens, fault=None, router=program_router,
           attention=program_attention):
    """(T, V) float32 logits of one sequence of token ids on ``params``
    (name -> array under the program's parameter names; any float dtype) --
    all NaN when ``router``, put through every layer's rows beside this
    module's float32 one, departs from it by more than ``ROUTE_RTOL``
    (``router(m, w_r, k, renorm) -> (weights, indices)``), or when
    ``attention``, put through the heads of the first layer of each type,
    departs from this module's by more than ``ATTN_RTOL``
    (``attention(q, k, v, rope_parameters entry, window, chunk, dtype) ->
    o``); None for no such comparison.  ``fault``: one of ``FAULTS``, a
    wrong model on purpose; the check that decides ``correct`` passes
    none."""
    import sys

    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    c = config
    assumed = c.get("assumed", {})
    dh, eps = c["head_dim"], c["rms_norm_eps"]
    f32 = jnp.float32
    n = len(tokens)
    # one padded length (so each layer type compiles once a process) when
    # the configuration states it, else whole query blocks; every layer is
    # causal, so the padding never reaches a compared row
    pad = max(assumed.get("reference_pad", 0), n)
    pad = -(-pad // QUERY_BLOCK) * QUERY_BLOCK if pad > QUERY_BLOCK else pad
    kinds, gaps, attn = {}, [], {}
    for kind in set(c["layer_types"]):
        inv, gain = rope_inv_freq(c["rope_parameters"][kind], dh)
        if fault == "no_attention_factor":
            gain = 1.0
        if fault == "no_yarn":
            inv = rope_inv_freq(dict(c["rope_parameters"][kind],
                                     rope_type="default"), dh)[0]
        angle = jnp.arange(pad, dtype=f32)[:, None] * jnp.asarray(inv)
        if fault == "rotary_bf16":
            angle = (jnp.arange(pad).astype(jnp.bfloat16)[:, None]
                     * jnp.asarray(inv, jnp.bfloat16)).astype(f32)
        kinds[kind] = (
            gain * jnp.cos(angle), gain * jnp.sin(angle),
            _jitted_layer(
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], dh=dh, eps=eps,
                window=c["sliding_window"]
                if kind == "sliding_attention" else None,
                qk_norm=assumed.get("qk_norm", False),
                top_k=c["num_experts_per_tok"], renorm=c["norm_topk_prob"],
                held_start=c.get("deployment", {}).get("held_start", 0),
                fault=fault, router=router))
    highest = functools.partial(jax.default_matmul_precision, "highest")
    with highest():
        tok = jnp.zeros((pad,), jnp.int32).at[:n].set(
            jnp.asarray(tokens, jnp.int32))
        x = jnp.asarray(params["word_embed.weight"])[tok].astype(f32)
    for i, kind in enumerate(c["layer_types"]):
        pre = f"layers.{i}."
        w = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        cos, sin, layer = kinds[kind]
        with highest():
            x, gap, (q, k, v, o) = layer(x, w, cos, sin)
        gaps.append(gap)
        if attention is not None and kind not in attn:
            # as the server runs it: under no precision of this module's
            got = attention(
                q, k, v, c["rope_parameters"][kind],
                c["sliding_window"] if kind == "sliding_attention" else None,
                assumed.get("prefill_chunk", QUERY_BLOCK),
                jnp.dtype(c.get("dtype", "bfloat16")))
            attn[kind] = float(jnp.abs(got[:n] - o[:n]).max()
                               / jnp.abs(o[:n]).max())
    with highest():
        out = _head(eps)(x[:n], jnp.asarray(params["ln_f.gamma"]),
                         jnp.asarray(params["head.weight"]))
    held = True
    if attention is not None:
        worst = max(attn.values())
        held = worst <= ATTN_RTOL                     # a NaN gap is not held
        print(f"[reference mellum] attention: {n} tokens, first layer of "
              f"each type reads {attn} against ATTN_RTOL {ATTN_RTOL:g}: "
              f"{'held' if held else 'NOT HELD'}", file=sys.stderr,
              flush=True)
    if router is not None:
        gaps = [float(g) for g in gaps]
        worst = max(range(len(gaps)), key=gaps.__getitem__)
        routed = gaps[worst] <= ROUTE_RTOL
        held = held and routed
        print(f"[reference mellum] router: {n} tokens, worst layer {worst} "
              f"reads {gaps[worst]:.3e} against ROUTE_RTOL {ROUTE_RTOL:g}: "
              f"{'held' if routed else 'NOT HELD'}", file=sys.stderr,
              flush=True)
    return out if held else jnp.full_like(out, jnp.nan)


@functools.lru_cache(maxsize=None)
def _head(eps):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda h, g, e: _rms(h, g.astype(jnp.float32), eps)
                   @ e.astype(jnp.float32).T)
