"""The comparisons that decide ``correct``."""
import numpy as onp


def greedy_agrees(ref_logits, n_prompt, generated, rtol):
    """Every token the server chose greedily must have a reference logit
    within ``rtol`` x max|ref| of its row's reference maximum (logits, not
    tokens: with random weights the argmax moves on rounding).  Returns
    (ok, worst gap as a share of max|ref|, exact argmax matches)."""
    ref = onp.asarray(ref_logits, onp.float32)
    gen = onp.asarray(generated, onp.int64)
    rows = ref[n_prompt - 1:n_prompt - 1 + len(gen)]
    if len(rows) != len(gen) or not onp.isfinite(rows).all():
        return False, float("inf"), 0
    scale = float(onp.abs(ref).max())
    gap = rows.max(-1) - rows[onp.arange(len(gen)), gen]
    worst = float(gap.max()) / scale
    return worst <= rtol, worst, int((rows.argmax(-1) == gen).sum())
