"""Driver ``train_stream``: the user's training loop, streamed.

A pool of seeded host batches is cycled through ``trainer.step(x, y)``
without ``block=`` (lazy loss, the trainer keeps its two steps in flight);
``drain()`` stands at both ends of the window and the losses are read after
the closing drain.  Samples per second is taken over ALL steps dispatched
and ALL the window's seconds: every dispatched step has retired by the
closing drain.

``correct``: every loss of the window is finite; the mean loss of the last
whole pass over the pool is below the first's; and the net's hybridized
predict-mode forward and the plain float32 reference give the same loss on
pool batch 0 (tolerance in the reference's file).  That comparison is made
AFTER the window, because it is the benchmark's work and not the program's
set-up (before the window it was 25-28 s of a 65 s ``setup_s``, and the part
that varied from run to run), but on the INITIAL weights, kept on the host
and put back into the net: a net trained on random labels predicts nearly
uniformly, and its loss (ln vocab + ln 2) no longer depends on the forward
pass -- on the trained weights program and reference agreed to 0.0 or 9e-8
(my chip runs, PR 26).
"""
import math
import time
import types

import numpy as onp


def prepare(*, config, traffic, model, reference, devices, seed, log):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import lr_scheduler
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    run = types.SimpleNamespace()
    run.traffic, run.chips = traffic, len(devices)
    run.config, run.model, run.reference = config, model, reference
    rs = onp.random.RandomState(seed % (2 ** 32))
    run.net = net = model.build(config, seed)
    run.initial = {k: onp.asarray(p.data()._data)
                   for k, p in net.collect_params().items()}
    log("[train_stream] net built and initialised, weights kept on the host")
    run.pool = [model.make_batch(config, traffic, rs)
                for _ in range(traffic["pool"])]

    opt = config["optimizer"]
    sched = lr_scheduler.PolyScheduler(
        max_update=opt["total_steps"], base_lr=opt["learning_rate"], pwr=1,
        final_lr=0.0, warmup_steps=opt["warmup_steps"])
    mesh = make_mesh(dict(traffic["mesh"]), devices=devices)
    run.trainer = ShardedTrainer(
        net, model.loss_fn, mesh=mesh, optimizer=opt["name"],
        learning_rate=opt["learning_rate"], weight_decay=opt["weight_decay"],
        lr_scheduler=sched, compute_dtype=jnp.dtype(config["compute_dtype"]),
        partition=traffic["partition"])
    log("[train_stream] trainer built")
    for i in range(traffic["warmup_steps"]):        # compile + settle
        run.trainer.step(*run.pool[i % len(run.pool)], block=True)
    jax.block_until_ready(run.trainer.pvals)
    log(f"[train_stream] {traffic['warmup_steps']} warm-up steps done")
    return run


def measure(run, seconds, on_close):
    trainer, pool = run.trainer, run.pool
    losses, n = [], 0
    trainer.drain()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        losses.append(trainer.step(*pool[n % len(pool)]))
        n += 1
    trainer.drain()
    window = time.perf_counter() - t0
    on_close()
    run.losses = [float(l) for l in losses]
    bad = sum(1 for l in run.losses if not math.isfinite(l))
    rate = n * run.traffic["batch"] / window / run.chips
    return {"attempted": n, "failed": bad, "window_s": window,
            "steps": n, "samples_per_s_per_chip": rate,
            "metrics": {"train.samples_per_s_per_chip": rate}}


def verify(run, result, log):
    import jax.numpy as jnp

    params = {}
    for k, p in run.net.collect_params().items():
        params[k] = jnp.asarray(run.initial[k])
        p.data()._set_data(params[k])
    x0, y0 = run.pool[0]
    got = run.model.predict_loss(run.net, x0, y0)
    want = run.reference.loss(params, run.config, x0, y0)
    ref = {"program_loss": got, "reference_loss": want,
           "rel_gap": abs(got - want) / abs(want),
           "rtol": run.reference.LOSS_RTOL}
    log(f"[train_stream] predict-mode loss {got:.6f} vs plain f32 reference "
        f"{want:.6f} on the initial weights (gap {ref['rel_gap']:.2e}, rtol "
        f"{ref['rtol']})")
    k, losses = len(run.pool), run.losses
    passes = len(losses) // k
    first = last = None
    if passes >= 2:
        first = sum(losses[:k]) / k
        last = sum(losses[(passes - 1) * k:passes * k]) / k
    notes = dict(ref, first_pass_loss=first, last_pass_loss=last,
                 steps=len(losses),
                 tokens_per_s_per_chip=result["samples_per_s_per_chip"]
                 * run.traffic["seq"])
    ok = (ref["rel_gap"] <= ref["rtol"] and result["failed"] == 0
          and first is not None and last < first)
    log(f"[train_stream] {len(losses)} steps, first-pass loss {first}, "
        f"last-pass loss {last}, non-finite {result['failed']}")
    return ok, notes
