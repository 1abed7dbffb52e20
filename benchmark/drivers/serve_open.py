"""Open-loop serving: requests are due on a schedule that is part of
the traffic file (Poisson arrivals from ``schedule_seed``, the same in
every run), whatever the server does.  Latency runs from the instant a request
was DUE; how late the generator handed it over is reported.  Sending
stops at the end of the window and the server drains; only requests due
inside the window count."""
from __future__ import annotations

import time

import numpy as np

from benchmark.drivers.serve_common import Harness, Sent


def schedule(tr: dict, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start): a Poisson process of
    ``rate_rps`` conditioned on its count — exactly round(rate x seconds)
    arrivals, placed independently and uniformly in the window from
    ``schedule_seed``.  The offered rate is then the stated rate in
    every window, and the same in every run."""
    r = np.random.RandomState(tr["schedule_seed"])
    n = int(round(seconds * tr["rate_rps"]))
    return np.sort(r.uniform(0.0, seconds, size=n))


def offer(h: Harness, due: np.ndarray, prompts: np.ndarray):
    """Send request ``i`` at ``due[i]`` seconds from now, whatever the
    server does; stop after the last and let the server drain.  Returns
    (records, window start, requests unresolved when sending stopped)."""
    import jax

    sent = []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for i, d in enumerate(due):
            with jax.profiler.TraceAnnotation("bench.wait_for_due_time"):
                while True:
                    left = t0 + d - time.perf_counter()
                    if left <= 0:
                        break
                    # sleep to 10 ms before the due time, then in short
                    # naps: the hand-over is late by well under 1 ms
                    time.sleep(left - 0.01 if left > 0.02
                               else min(left, 0.002))
            rec = Sent(prompts[i], t0 + d)
            h.send(rec)
            sent.append(rec)
        backlog = h.outstanding
        h.drain()
    return sent, t0, backlog


def run(ctx) -> dict:
    h = Harness(ctx)
    h.warm()
    seconds = ctx.window_seconds()
    due = schedule(ctx.traffic, seconds)
    prompts = h.prompts(len(due))
    ctx.clock.mark("schedule and prompts")
    h.open_window()
    sent, t0, backlog = offer(h, due, prompts)
    if ctx.trace:
        ctx.stop_trace()
    ctx.say(f"[window] offered {len(due) / seconds:.3f} requests/s; "
            f"{backlog} unresolved when sending stopped")
    out = h.finish(sent, t0)
    out["counters"]["backlog_at_end"] = backlog
    return out
