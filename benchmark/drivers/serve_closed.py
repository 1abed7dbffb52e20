"""Closed-loop serving: ``clients`` callers each wait for their reply
and then send the next prompt, so the server is never offered more than
``clients`` requests at once and — with several batches' worth of
clients — never finds its queue empty.  One generator thread plays all
the clients: a resolved future hands its client back through a queue.
A client sends its next request only while the window is open; what is
in flight then drains.  Latency runs from the hand-over."""
from __future__ import annotations

import queue
import time

from benchmark.drivers.serve_common import Harness, Sent


def run(ctx) -> dict:
    import jax

    h = Harness(ctx)
    tr = ctx.traffic
    h.warm()
    seconds = ctx.window_seconds()
    # more prompts than the window can use, all from the seed
    pool = h.prompts(int(seconds * tr["max_requests_per_s"]) + tr["clients"])
    ctx.clock.mark("prompts")
    h.open_window()
    free = queue.SimpleQueue()
    for c in range(tr["clients"]):
        free.put(c)
    sent = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.await_result"):
                try:
                    free.get(timeout=max(t_end - time.perf_counter(), 0.0))
                except queue.Empty:
                    break
            now = time.perf_counter()
            if now >= t_end:
                break
            if len(sent) >= len(pool):
                raise RuntimeError("the prompt pool ran out: raise "
                                   "max_requests_per_s in the traffic file")
            with jax.profiler.TraceAnnotation("bench.next_prompt"):
                rec = Sent(pool[len(sent)], now)
            h.send(rec).add_done_callback(lambda f: free.put(0))
            sent.append(rec)
        h.drain()
    if ctx.trace:
        ctx.stop_trace()
    return h.finish(sent, t0)
