"""Offer a request stream to a server and time every token on our own clock.

One driver for both loops, chosen by the traffic file's ``arrivals``:

* ``{"kind": "closed", "outstanding": N}`` - N requests are outstanding at all
  times (queued or decoding); each completion submits the next.  A backlog:
  the server is never short of work, and tokens per second is what counts.
* ``{"kind": "open", "rate_rps": r, "cv": c}`` - arrivals on a schedule drawn
  from the seed whether or not earlier requests have finished.  A request is
  timed from the moment it was DUE, so the generator's own lag counts against
  the request (the discipline of ``benchmark/opperf/generation.py``).

Both run ``lead_s`` seconds of the same traffic before the window so that the
window sees the steady state, and measure on the benchmark's clock inside
the public ``submit(..., on_token=)`` callback.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

_perf = time.perf_counter


class _Request:
    __slots__ = ("prompt", "max_new", "due", "submitted", "first", "last",
                 "n", "done", "handle", "tokens")

    def __init__(self, prompt, max_new, due=None):
        self.prompt, self.max_new, self.due = prompt, max_new, due
        self.submitted = self.first = self.last = self.done = None
        self.n = 0
        self.handle = None
        self.tokens = None


class _Client:
    """Submits requests and records, per request, the time of its first and
    latest token, and for the whole stream the time of every token."""

    def __init__(self, server, eos):
        self.server, self.eos = server, eos
        self.token_times = []
        self.finished = queue.SimpleQueue()
        self.shed = 0

    def _on_token(self, req):
        def callback(result, token):
            now = _perf()
            if req.n == 0:
                req.first = now
            req.last = now
            req.n += 1
            self.token_times.append(now)
            if req.tokens is not None:
                req.tokens.append(int(token))
            if token == self.eos or req.n >= req.max_new:
                req.done = now
                self.finished.put(req)
        return callback

    def submit(self, req):
        from incubator_mxnet_tpu.serving.generation import AdmissionError

        req.submitted = _perf()
        try:
            req.handle = self.server.submit(
                req.prompt, max_new_tokens=req.max_new,
                on_token=self._on_token(req))
        except AdmissionError:
            self.shed += 1
            req.done = req.submitted


def _percentile(values, q):
    """Nearest-rank percentile; +inf entries (failed requests) rank last."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(np.ceil(q * len(ordered))) - 1)]


def run(system, ctx):
    from .. import traffic_gen

    server, traffic = system["server"], ctx.traffic
    arrivals = traffic["arrivals"]
    open_loop = arrivals["kind"] == "open"
    seconds = ctx.window_seconds
    lead_s = float(traffic["lead_s"])
    specs = traffic_gen.requests(traffic, ctx.seed, int(traffic["population"]),
                                 system["vocab"], first_id=system["first_token"])
    reqs = ([_Request(s["prompt"], s["max_new"], s["due_s"]) for s in specs]
            if open_loop else [])
    # the probe that set-up decoded alone rides amid the load, before the window
    alone = system["alone"]
    amid = _Request(alone["prompt"], alone["max_new"])
    amid.tokens = []  # this one keeps its tokens, to compare
    client = _Client(server, system["eos"])

    stop = threading.Event()
    feed_until = [float("inf")]
    samples = []  # (time, active_slots, total_slots), traced runs only
    exhausted = []

    def feeder():
        """Open loop: submit each request when it is due."""
        for req in reqs:
            if req.due > feed_until[0]:
                return
            wait = req.due - _perf()
            if wait > 0 and stop.wait(wait):
                return
            client.submit(req)
        exhausted.append(True)

    def sample_occupancy():
        while not stop.wait(0.05):
            st = server.stats()
            samples.append((_perf(), st["active_slots"], st["total_slots"]))

    threads = []

    def start(target, name):
        threads.append(threading.Thread(target=target, name=name))
        threads[-1].start()

    nxt = 0

    def issue_next():
        """Closed loop: the population's next request, round and round."""
        nonlocal nxt
        spec = specs[nxt % len(specs)]
        nxt += 1
        reqs.append(_Request(spec["prompt"], spec["max_new"]))
        client.submit(reqs[-1])

    def hold(until):
        """Pass the time until ``until``: asleep in an open loop (the feeder
        thread submits), and in a closed loop submitting one new request per
        completion."""
        while True:
            left = until - _perf()
            if left <= 0:
                return
            if open_loop:
                time.sleep(min(left, 0.05))
                continue
            try:
                client.finished.get(timeout=min(left, 0.05))
            except queue.Empty:
                continue
            issue_next()

    t_start = _perf()
    try:
        if open_loop:
            for req in reqs:
                req.due += t_start
            start(feeder, "chipbench-feeder")
        else:
            for _ in range(int(arrivals["outstanding"])):
                issue_next()
        client.submit(amid)
        hold(t_start + lead_s)
        with ctx.window() as window:
            if ctx.trace:
                start(sample_occupancy, "chipbench-sampler")
            before = server.stats()
            hold(window.t0 + seconds)
            after = server.stats()
        t0, t1 = window.t0, window.t1
        if open_loop:
            feed_until[0] = t1 + float(traffic.get("tail_s", 0.0))
            measured = [r for r in reqs if t0 <= r.due < t1]
            waiting_for = measured + [amid]
        else:
            measured = [r for r in reqs if r.done is not None and t0 <= r.done < t1]
            waiting_for = [amid]
        deadline = t1 + float(traffic["drain_s"])
        while _perf() < deadline and any(r.done is None for r in waiting_for):
            time.sleep(0.02)
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30.0)
    if exhausted:
        raise RuntimeError("the traffic file's population of arrivals ran out "
                           "before the run ended: raise \"population\"")
    recompiles = system["recompiles"]()
    errored = sum(1 for r in reqs + [amid] if r.handle is not None
                  and r.handle.finish_reason == "error")
    unfinished = sum(1 for r in measured if r.done is None)
    failed = client.shed + errored + unfinished
    for r in reqs:  # free the slots of whatever is still in flight
        if r.handle is not None and r.done is None:
            r.handle.cancel()

    tokens_in_window = sum(1 for t in client.token_times if t0 <= t < t1)
    rate = tokens_in_window / (t1 - t0)
    values = {"serve_tokens_per_s": rate, "completed_tokens_per_s": rate}
    if open_loop:
        inf = float("inf")
        served = lambda r: r.done is not None and r.n > 0
        ttft = [(r.first - r.due) * 1e3 if served(r) else inf for r in measured]
        # a request of one token has no gap between tokens; a failed one has
        # missed this limit too
        tpot = [(r.last - r.first) / (r.n - 1) * 1e3 if served(r) else inf
                for r in measured if r.n != 1]
        lag = [(r.submitted - r.due) * 1e3 if r.submitted is not None else inf
               for r in measured]
        values["ttft_p95_ms"] = _percentile(ttft, 0.95)
        values["tpot_p95_ms"] = _percentile(tpot, 0.95)
        values["ttft_p50_ms"] = _percentile(ttft, 0.50)
        values["tpot_p50_ms"] = _percentile(tpot, 0.50)
        values["generator_lag_p95_ms"] = _percentile(lag, 0.95)
    d_iter = after["iterations"] - before["iterations"]
    d_tok = (sum(t["tokens"] for t in after["tenants"].values())
             - sum(t["tokens"] for t in before["tenants"].values()))
    occupancy = [a / n for t, a, n in samples if t0 <= t < t1]
    counts = {"decode_batch_mean": d_tok / d_iter if d_iter else None,
              "iterations_per_s": d_iter / (t1 - t0),
              "slot_occupancy": 100.0 * float(np.mean(occupancy)) if occupancy else None}
    amid_ok = amid.done is not None and np.array_equal(
        np.asarray(amid.tokens, np.int32), alone["tokens"])
    ctx.say(f"window {t1 - t0:.3f} s: {len(measured)} requests measured, "
            f"{tokens_in_window} tokens ({rate:.1f} tokens/s), shed {client.shed}, "
            f"errored {errored}, unfinished {unfinished}, recompiles {recompiles}; "
            f"server counts before {_brief(before)} after {_brief(after)}; "
            f"alone equals amid: {amid_ok}")
    if open_loop:
        ctx.say("latency " + ", ".join(
            f"{k} {v:.3f}" for k, v in values.items() if k.endswith("_ms")))
    ctx.say(f"scheduler {counts}")
    checks = dict(system["checks"])
    checks.update({"no_compile_in_window": recompiles == 0,
                   "no_request_errored": errored == 0,
                   "alone_equals_amid": bool(amid_ok),
                   "requests_measured": len(measured) > 0})
    return {"values": values, "counts": counts, "checks": checks,
            "attempted": len(measured) + client.shed, "failed": failed,
            "programs": system["programs"]}


def _brief(stats):
    keys = ("iterations", "completed", "queue_depth", "active_slots")
    out = {k: stats[k] for k in keys}
    out["tokens"] = sum(t["tokens"] for t in stats["tenants"].values())
    out["shed"] = sum(t["shed"] for t in stats["tenants"].values())
    return out
