"""Plain reference for ``kimi_linear``: the forward pass of a Kimi-Linear
decoder (Kimi Linear tech report, arXiv:2510.26692; HF ``modeling_kimi.py``
/ fla's ``KimiDeltaAttention``) over ONE whole sequence in straightforward
float32 ``jax.numpy`` -- no cache, no kernel, no batching, no chunking,
matmuls at ``highest`` precision.  This is the one copy: the program's own
tests (``tests/test_kimi_linear.py``) load this file by path.

Block, layers ``i = 0..L-1``: ``x = x + mixer_i(RMSNorm(x))``;
``x = x + ffn_i(RMSNorm(x))``; final RMSNorm; ``logits = h @ W_head.T``
(untied).  No position encoding anywhere.  Layer ``i`` is KDA when ``i+1``
is in ``linear_attn_config.kda_layers`` and MLA when it is in
``full_attn_layers`` (1-based lists).  ``ffn_i`` is a dense SiLU-gated FFN
for ``i < first_k_dense_replace`` and the MoE layer after.

* KDA (``_kda``), token by token with ``lax.scan``: q, k, v from one
  projection each, a causal depthwise conv over the last 4 positions (no
  bias) and SiLU; q and k L2-normalised per head; per head and key channel
  ``g_t = -exp(A_log) * softplus(W_f_b W_f_a x_t + dt_bias)``;
  ``beta_t = sigmoid(W_b x_t)``;
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``;
  ``o_t = S_t^T (q_t * d_k**-0.5)``; out =
  ``W_o(sigmoid(W_g_b W_g_a x_t) * RMSNorm_head(o_t))``.
* MLA (``_mla``), expanded, NoPE: ``mla_use_nope`` is true, so no rotary is
  applied and the ``qk_rope_head_dim`` channels are a plain key part shared
  by all heads; softmax scale ``(nope + rope) ** -0.5``.
* MoE (``_moe``), a loop over the HELD experts: ``s = sigmoid(W_r x)`` over
  all ``published.num_experts`` router outputs, the ``num_experts_per_token``
  largest of ``s + e_score_correction`` chosen, weights
  ``routed_scaling_factor * s_e / (sum_chosen s + 1e-20)``, and the result
  is ``sum over chosen AND held e of w_e E_e(x) + E_shared(x)``: what the
  experts on the deployment's other chips would add is left out, here as in
  the program (guide ``model-configs`` section 4).

Departures from the published model are the configuration file's
(``assumed``, ``departures``): the low rank of the two gates (128, fla's
``head_v_dim``), the L2 norm's eps 1e-6, no bias on the output gate's
second projection.

One layer is one jitted function per layer kind, called with that layer's
weights upcast to f32 on the fly, so neither the compile nor the memory
grows with depth (a published-width expert stack of 16 is 3 x 151 MB in
f32).

**Two limits decide ``correct``** (``lib/checks.greedy_agrees`` calls
``logits`` and reads ``LOGIT_RTOL``; it knows of nothing else):

* ``LOGIT_RTOL`` holds the structure: how far below its row's maximum the
  reference logit of a token the server chose may lie.  ``faults`` are the
  demonstrations that it is tight (a decay shifted by one position, a
  router without its renormalisation, held experts multiplied through
  their neighbours' output projection).
* ``STATE_RTOL`` holds the precision of the recurrent state, which the
  logits cannot (a bf16 state moves them as far as the bf16 matrix
  products of a sound server do).  In every KDA layer the rows
  ``q, k, v, g, beta`` that this module computed anyway are also put
  through the recurrence UNDER TEST -- by default the program's own two
  forms (``program_recurrence``: ``mxnet_tpu.ops.kda.kda_chunk`` over the
  head of the sequence, ``kda_step`` token by token over the rest, the
  state carried in the dtype the program's cache holds it in) -- and its
  outputs are compared with this module's float32 scan on the same rows.
  Same inputs, so nothing but the recurrence's own arithmetic is in the
  number.  When the worst layer reads over ``STATE_RTOL`` the returned
  logits are NaN, which ``greedy_agrees`` reads as not correct.
  ``scan_recurrence`` with a bf16 state is the control that must fail.
  What this cannot see is the server's cache itself (the check is handed
  tokens and weights): that ``begin_cache`` allocates the state in
  ``ops.kda.STATE_DTYPE`` and that both forms hand it back so is held by
  ``tests/test_kimi_linear.py``.
"""
import functools

# The server computes in bf16 (weights and matrix-product operands, latent
# cache; float32 accumulation, residual stream, norms, softmax, router and
# recurrent state) and the reference in f32.  A token the server chose
# greedily must have a reference logit within this share of max|ref| of its
# row's reference maximum (lib/checks.greedy_agrees).  Readings at the
# published widths on the chip (PERF.md section 6, PR 29): the server's own
# tokens read 0.2-2.0% of max|ref| (mean 1.0%, the longest outputs the
# most: with random weights a top-8 choice sits on a near-tie for a share
# of the tokens, a bf16 rounding swaps a held expert there, and those
# swaps, not the arithmetic, are the reading); the reference's own greedy
# tokens when it is computed with the decay shifted by one position read
# 4.8% and 5.7%, without the renormalisation 45-57%.  So 4%: twice the
# largest sound reading, five sixths of the smallest faulty one.  The
# reading saturates at "a swap happened", so no precision is told apart by
# it: a bf16 recurrent state reads 1.3% and 1.6%.  That is STATE_RTOL's.
LOGIT_RTOL = 0.04
# The recurrence under test against this module's float32 scan on the same
# rows, worst KDA layer, max|o - o_ref| over max|o_ref|.  Readings on the
# chip at the published widths (PERF.md section 6, PR 29; 1 536 and 2 560
# tokens): the program's chunk form + step form, float32 state, every
# product at ``highest``, read 6.7e-5 and 8.8e-5; the scan with the state
# kept in bf16, the nearest precision below the stated float32, reads
# 1.77e-2 and 1.48e-2; the program's forms with their products left at the
# chip's default precision (float32 operands rounded to bf16: the state is
# then READ as bf16 every step, though kept in float32) read 3.4e-3 and
# 3.2e-3.  So 1e-3, the geometric middle of the program and the bf16 state:
# eleven times the program's largest reading, a third of the unpinned
# products' and a fifteenth of the bf16 state's smallest.
STATE_RTOL = 1e-3
PREFILL = 512          # rows the recurrence under test may take in one piece
PAD = 256
L2_EPS = 1e-6


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _gated(x, gate, up, down):
    """``W_down(SiLU(W_gate x) * W_up x)``, weights (in, out)."""
    return (_silu(x @ gate) * (x @ up)) @ down


def _conv4(x, w):
    """Causal depthwise convolution over the last ``K`` positions:
    ``y_t[c] = sum_j w[c, j] x_{t-K+1+j}[c]`` (``w[:, K-1]`` weighs the
    current row), zeros before the sequence."""
    import jax.numpy as jnp

    t, k = x.shape[0], w.shape[1]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    return sum(xp[j:j + t] * w[:, j] for j in range(k))


def scan_recurrence(q, k, v, g, beta, n_chunk=None, state_dtype=None):
    """The KDA recurrence token by token: q (scaled), k, v, g (T, H, d),
    beta (T, H) -> o (T, H, dv) float32, the state kept in ``state_dtype``
    (float32 unless told) between tokens.  ``n_chunk`` is what a recurrence
    under test is told and means nothing here."""
    import jax
    import jax.numpy as jnp

    state_dtype = jnp.float32 if state_dtype is None else state_dtype

    def step(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = s.astype(jnp.float32) * jnp.exp(g_t)[:, :, None]   # (H, dk, dv)
        u = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + jnp.einsum("hk,hv->hkv", k_t,
                           b_t[:, None] * (v_t - u))
        s = s.astype(state_dtype)
        return s, jnp.einsum("hk,hkv->hv", q_t, s.astype(jnp.float32))

    s0 = jnp.zeros(k.shape[1:] + v.shape[2:], state_dtype)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def program_recurrence(q, k, v, g, beta, n_chunk):
    """The recurrence as the PROGRAM computes it when it serves, on the
    reference's rows: ``ops.kda.kda_chunk`` over the first ``n_chunk`` rows
    (a prompt's prefill; at most ``PREFILL``), then ``ops.kda.kda_step`` a
    token, the state allocated in ``ops.kda.STATE_DTYPE`` as ``begin_cache``
    does and carried as each form hands it back.  Traced at the DEFAULT
    matrix-product precision, not this module's: what the program's code
    does not pin for itself it does not get here either."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import kda

    t = q.shape[0]
    head = min(t, PREFILL)
    with jax.default_matmul_precision("default"):
        s0 = jnp.zeros((1,) + k.shape[1:] + v.shape[2:], kda.STATE_DTYPE)
        o_head, s = kda.kda_chunk(*(a[None, :head] for a in (q, k, v, g, beta)),
                                  s0, jnp.reshape(n_chunk, (1,)))
        later = jnp.arange(t) >= n_chunk          # stepped; the rest idle
        rows = (q, k, v, jnp.where(later[:, None, None], g, 0.0),
                jnp.where(later[:, None], beta, 0.0))

        def step(s, row):
            s, o = kda.kda_step(*(a[None] for a in row), s)
            return s, o[0]

        o_step = jax.lax.scan(step, s, rows)[1]
    o_head = jnp.pad(o_head[0], ((0, t - head), (0, 0), (0, 0)))
    return jnp.where(later[:, None, None], o_step, o_head)


def _kda(x, w, heads, dk, eps, state_dtype, shift_decay, recurrence, n_real,
         n_chunk):
    """-> (mixer output (T, U), the recurrence under test's distance from
    the float32 scan on this layer's rows)."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    n = heads * dk
    qkv = _silu(_conv4(x @ w["qkv.weight"].T, w["conv_weight"]))
    q, k, v = (a.reshape(t, heads, dk) for a in jnp.split(qkv, 3, -1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = (x @ w["f_a.weight"].T) @ w["f_b.weight"].T + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f).reshape(t, heads, dk)
    if shift_decay:                  # the fault: decay of the row before
        g = jnp.concatenate([jnp.zeros_like(g[:1]), g[:-1]], 0)
    beta = jax.nn.sigmoid(x @ w["b_proj.weight"].T)              # (T, H)

    o = scan_recurrence(q, k, v, g, beta, None, state_dtype)     # (T, H, dv)
    gap = jnp.zeros((), jnp.float32)
    if recurrence is not None:
        real = (jnp.arange(t) < n_real)[:, None, None]
        tried = recurrence(q, k, v, g, beta, n_chunk)
        gap = jnp.max(jnp.where(real, jnp.abs(tried - o), 0.0)) \
            / jnp.max(jnp.where(real, jnp.abs(o), 0.0))
    o = _rms(o, w["o_norm.gamma"], eps).reshape(t, n)
    gate = jax.nn.sigmoid((x @ w["g_a.weight"].T) @ w["g_b.weight"].T)
    return (gate * o) @ w["o_proj.weight"].T, gap


def _mla(x, w, heads, nope, rope, dv, rank, eps):
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    q = (x @ w["q_proj.weight"].T).reshape(t, heads, nope + rope)
    ckv = x @ w["kv_a.weight"].T                                 # (T, r + rope)
    c = _rms(ckv[:, :rank], w["kv_norm.gamma"], eps)
    kpe = ckv[:, rank:]
    kv = (c @ w["kv_b.weight"].T).reshape(t, heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kpe[:, None], (t, heads, rope))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * (nope + rope) ** -0.5
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, kv[..., nope:]).reshape(t, heads * dv)
    return o @ w["o_proj.weight"].T


def _moe(x, w, k, scale, held_start, renormalize, wrong_expert=0):
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x @ w["router.weight"].T)                 # (T, E_all)
    _, idx = jax.lax.top_k(s + w["e_score_correction"], k)
    chosen = jnp.take_along_axis(s, idx, -1)                     # (T, k)
    if renormalize:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    chosen = chosen * scale
    y = _gated(x, w["shared.gate.weight"].T, w["shared.up.weight"].T,
               w["shared.down.weight"].T)
    held = w["experts_gate"].shape[0]
    for e in range(held):                                        # held only
        w_e = jnp.sum(jnp.where(idx == held_start + e, chosen, 0.0), -1)
        # the fault: a token's rows meet the NEXT held expert's output
        # projection, as a grouped product with its groups off by one would
        down = w["experts_down"][(e + wrong_expert) % held]
        y = y + w_e[:, None] * _gated(x, w["experts_gate"][e],
                                      w["experts_up"][e], down)
    return y


def _layer(x, w, n_real, n_chunk, *, kind, dense, cfg, state_dtype, faults,
           recurrence):
    """-> (x after the layer, its KDA state gap: 0 for an MLA layer)."""
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    mix = {k[len("mixer."):]: v for k, v in w.items() if k.startswith("mixer.")}
    ffn = {k[len("ffn."):]: v for k, v in w.items() if k.startswith("ffn.")}
    h = _rms(x, w["ln_mixer.gamma"], eps)
    gap = jnp.zeros((), jnp.float32)
    if kind == "kda":
        lin = cfg["linear_attn_config"]
        y, gap = _kda(h, mix, lin["num_heads"], lin["head_dim"], eps,
                      state_dtype, "shift_decay" in faults, recurrence,
                      n_real, n_chunk)
        x = x + y
    else:
        x = x + _mla(h, mix, cfg["num_attention_heads"],
                     cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"], eps)
    h = _rms(x, w["ln_ffn.gamma"], eps)
    if dense:
        return x + _gated(h, ffn["gate.weight"].T, ffn["up.weight"].T,
                          ffn["down.weight"].T), gap
    return x + _moe(h, ffn, cfg["num_experts_per_token"],
                    cfg["routed_scaling_factor"],
                    cfg["deployment"]["held_start"],
                    cfg["moe_renormalize"] and "no_renormalize" not in faults,
                    int("wrong_expert" in faults)), gap


def layer_kinds(config):
    """[("kda" | "mla", dense FFN?)] per layer, from the 1-based lists."""
    lin = config["linear_attn_config"]
    kinds = []
    for i in range(config["num_hidden_layers"]):
        if i + 1 in lin["kda_layers"]:
            kind = "kda"
        elif i + 1 in lin["full_attn_layers"]:
            kind = "mla"
        else:
            raise ValueError(f"layer {i + 1} is in neither kda_layers nor "
                             "full_attn_layers")
        kinds.append((kind, i < config["first_k_dense_replace"]))
    return kinds


def logits(params, config, tokens, state_dtype=None, faults=(),
           recurrence=program_recurrence):
    """(T, V) float32 logits of one sequence of token ids on ``params``
    (name -> array under the program's parameter names; any float dtype)
    -- all NaN when ``recurrence``, put through every KDA layer's rows
    beside this module's float32 scan, departs from it by more than
    ``STATE_RTOL`` (``recurrence(q, k, v, g, beta, n_chunk) -> o``, told
    to take the first ``n_chunk`` = half the sequence, at most ``PREFILL``,
    in its prefill form; None for no such comparison).  ``state_dtype``
    (default float32) and ``faults`` (of "shift_decay", "no_renormalize",
    "wrong_expert") put a fault into THIS module's forward, for the
    demonstrations that ``LOGIT_RTOL`` is tight; the check that decides
    ``correct`` passes neither.  Sequences are padded to a multiple of
    ``assumed.reference_pad`` where the configuration gives one (one
    compiled shape for every request of a cell: a layer's compile is 15 s
    on the chip, its run a tenth of that), of ``PAD`` otherwise."""
    import sys

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    state_dtype = f32 if state_dtype is None else state_dtype
    cfg = {k: config[k] for k in (
        "rms_norm_eps", "linear_attn_config", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "num_experts_per_token", "routed_scaling_factor", "moe_renormalize")}
    cfg["deployment"] = {"held_start": config["deployment"]["held_start"]}
    fns, gaps = {}, []
    with jax.default_matmul_precision("highest"):
        n = len(tokens)
        # pad to a multiple of PAD (few compiled shapes); every layer is
        # causal, so the padding stays out of the rows that are returned
        pad = config.get("assumed", {}).get("reference_pad", PAD)
        padded = -(-n // pad) * pad
        tok = jnp.zeros((padded,), jnp.int32).at[:n].set(
            jnp.asarray(tokens, jnp.int32))
        sizes = (jnp.int32(n), jnp.int32(min(n // 2, PREFILL)))
        x = jnp.asarray(params["word_embed.weight"])[tok].astype(f32)
        for i, (kind, dense) in enumerate(layer_kinds(config)):
            if (kind, dense) not in fns:
                fns[kind, dense] = jax.jit(functools.partial(
                    _layer, kind=kind, dense=dense, cfg=cfg,
                    state_dtype=state_dtype, faults=tuple(faults),
                    recurrence=recurrence))
            pre = f"layers.{i}."
            w = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            x, gap = fns[kind, dense](x, w, *sizes)
            gaps.append(gap)
        head = jax.jit(lambda h, g, e: _rms(h, g.astype(f32),
                                            config["rms_norm_eps"])
                       @ e.astype(f32).T)
        out = head(x, jnp.asarray(params["ln_f.gamma"]),
                   jnp.asarray(params["head.weight"]))[:n]
    if recurrence is None:
        return out
    gaps = [float(g) for g in gaps]
    worst = max(range(len(gaps)), key=gaps.__getitem__)
    held = gaps[worst] <= STATE_RTOL                  # a NaN gap is not held
    print(f"[reference kimi_linear] KDA state: {len(tokens)} tokens, worst "
          f"layer {worst} reads {gaps[worst]:.3e} of max|o| against "
          f"STATE_RTOL {STATE_RTOL:g}: {'held' if held else 'NOT HELD'}",
          file=sys.stderr, flush=True)
    return out if held else jnp.full_like(out, jnp.nan)
