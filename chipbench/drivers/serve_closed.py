"""Driver ``serve_closed``: a closed loop of clients on the decode server.

``serve.register_decode`` with the traffic file's server block, then
``clients`` callers that each submit their next request when the last one
resolved (callers that wait for a reply: evaluation harnesses, batch and
synthetic-data pipelines).  ONE client thread does all submitting: the
request's ``on_token`` callback (which the program runs on its decode
worker's thread) only appends ``time.perf_counter()`` to a list and, at the
terminal ``None``, hands the client's id to that thread through a queue.

The loop starts ``ramp_s`` seconds before the window (set-up) so that the
clients are out of step with each other when it opens, runs through it, and
stops submitting when it closes; requests in flight at either edge count
for the tokens they deliver inside the window and for nothing else.  Every
percentile is worked out after the window.

``correct``: every request submitted in the window resolved with the number
of tokens it asked for; and, once ``serve.shutdown_decode()`` has released
the cache, four finished requests (the first finished, the longest prompt,
the longest output, one drawn from the seed) agree with the plain float32
reference forward over prompt + output (``lib/checks.greedy_agrees``).
"""
import gc
import queue
import threading
import time

import numpy as onp

NAME = "chipbench_lm"
PERCENTILES = (50, 75, 95)     # of TTFT and of the gaps, by name


class Req:
    __slots__ = ("client", "prompt", "n_out", "t_submit", "times", "t_done",
                 "future", "error")

    def __init__(self, client, prompt, n_out):
        self.client, self.prompt, self.n_out = client, prompt, n_out
        self.t_submit, self.times, self.t_done = None, [], None
        self.future, self.error = None, None

    @property
    def ok(self):
        return (self.error is None and self.t_done is not None
                and len(self.times) == self.n_out)


class Run:
    def __init__(self, server, stream, clients):
        self.server, self.stream, self.n_clients = server, stream, clients
        self.reqs, self.done_q = [], queue.Queue()
        self.stopping = False
        self.thread = threading.Thread(target=self._loop,
                                       name="chipbench-clients", daemon=True)

    def _submit(self, client):
        prompt, n_out = next(self.stream)
        r = Req(client, prompt, n_out)

        def on_token(tok, r=r, q=self.done_q):
            if tok is None:
                r.t_done = time.perf_counter()
                q.put(r.client)
            else:
                r.times.append(time.perf_counter())

        self.reqs.append(r)
        r.t_submit = time.perf_counter()
        try:
            r.future = self.server.submit(prompt, max_new_tokens=n_out,
                                          on_token=on_token)
        except Exception as e:  # noqa: BLE001 - a refused request is a miss
            r.error = e
            self.done_q.put(client)

    def _loop(self):
        for c in range(self.n_clients):
            self._submit(c)
        active = self.n_clients
        while active:
            c = self.done_q.get()
            if self.stopping:
                active -= 1
            else:
                self._submit(c)

    def stop(self, timeout):
        self.stopping = True
        self.thread.join(timeout)
        if self.thread.is_alive():
            raise RuntimeError("closed-loop clients did not finish within "
                               f"{timeout}s of the window's end")
        for r in self.reqs:
            if r.error is None and r.future is not None:
                try:
                    r.future.result(0)
                except Exception as e:  # noqa: BLE001 - counted as failed
                    r.error = e


def prepare(*, config, traffic, model, reference, devices, seed, log):
    from lib.traffic import request_stream
    from mxnet_tpu import serve

    lm = model.build(config, seed)
    log("[serve_closed] model built and initialised")
    srv = traffic["server"]
    serve.register_decode(
        NAME, lm, slots=srv["slots"],
        prompt_buckets=tuple(srv["prompt_buckets"]),
        capacity_buckets=tuple(srv["capacity_buckets"]),
        max_new_tokens=srv["max_new_tokens"],
        prefill_workers=srv["prefill_workers"],
        prefix_cache=srv["prefix_cache"])
    log("[serve_closed] register_decode done (grid built and warmed)")
    run = Run(serve.decode_server(NAME),
              request_stream(traffic["lengths"], config["vocab_size"], seed),
              traffic["clients"])
    run.lm, run.config, run.reference, run.seed = lm, config, reference, seed
    run.thread.start()
    time.sleep(traffic["ramp_s"])
    return run


def measure(run, seconds, on_close):
    from lib.stats import percentile

    t0 = time.perf_counter()
    time.sleep(seconds)
    t1 = time.perf_counter()
    on_close()              # counters are read here, not after the drain
    run.stop(timeout=120.0)
    window = t1 - t0
    mine = [r for r in run.reqs if t0 <= r.t_submit < t1]
    ttft = [(r.times[0] - r.t_submit) * 1e3 if r.ok else float("inf")
            for r in mine]
    tokens, gaps = 0, []
    for r in run.reqs:
        ts = r.times
        tokens += sum(1 for t in ts if t0 <= t < t1)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 <= b < t1]
    run.window = mine
    finished = sum(1 for r in run.reqs
                   if r.t_done is not None and t0 <= r.t_done < t1)
    metrics = {"serve.tokens_per_s": tokens / window}
    for q in PERCENTILES:
        metrics[f"serve.ttft_p{q}_ms"] = percentile(ttft, q)
        metrics[f"serve.gap_p{q}_ms"] = percentile(gaps, q)
    return {"attempted": len(mine), "failed": sum(1 for r in mine if not r.ok),
            "window_s": window, "tokens": tokens,
            "requests_finished_per_s": finished / window,
            "samples": {"ttft": len(ttft), "gap": len(gaps)},
            "metrics": metrics}


def verify(run, result, log):
    from lib.checks import greedy_agrees
    from mxnet_tpu import serve

    serve.shutdown_decode(60.0)
    run.server = None
    gc.collect()                        # the slot cache goes with the server
    done = [r for r in run.window if r.ok]
    notes = {k: result[k] for k in ("samples", "tokens",
                                    "requests_finished_per_s")}
    notes["latency_ms"] = {k: v for k, v in result["metrics"].items()
                           if k.endswith("_ms")}
    if not done:
        return False, dict(notes, why="no request finished in the window")
    rs = onp.random.RandomState(run.seed % (2 ** 32))
    picks = {id(r): r for r in (
        min(done, key=lambda r: r.t_done),
        max(done, key=lambda r: len(r.prompt)),
        max(done, key=lambda r: r.n_out),
        done[int(rs.randint(len(done)))])}
    params = {k: p.data()._data
              for k, p in run.lm.collect_params().items()}
    rtol = run.reference.LOGIT_RTOL
    ok, checks = result["failed"] == 0, []
    for r in picks.values():
        out = r.future.result(0)
        seq = onp.concatenate([r.prompt, onp.asarray(out, "int32")])
        ref = onp.asarray(run.reference.logits(params, run.config, seq))
        good, worst, exact = greedy_agrees(ref, len(r.prompt), out, rtol)
        checks.append({"prompt": len(r.prompt), "out": len(out),
                       "worst_gap_of_max_ref": worst, "argmax_equal": exact})
        ok = ok and good
    log(f"[serve_closed] reference check (rtol {rtol}): {checks}")
    return ok, dict(notes, reference=checks, rtol=rtol)
