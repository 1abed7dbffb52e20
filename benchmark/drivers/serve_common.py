"""What the two serving drivers share: the server built from the
configuration, warm-up of every program the cell can dispatch, the
bookkeeping of one request, and the check of served tokens against the
plain reference after the window.

A load loop (open or closed) hands ``finish`` a list of ``Sent`` records
— one per request SENT INSIDE the window — and the window's own start
(first timed dispatch).  Rates are whole requests over first dispatch ->
last completion; latencies are client side."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np


class Sent:
    """One request: when it was due, when it was handed to the server,
    when its future resolved (the server's worker thread calls back),
    and the result."""

    __slots__ = ("prompt", "t_due", "t_sent", "t_done", "result")

    def __init__(self, prompt, t_due):
        self.prompt, self.t_due = prompt, t_due
        self.t_sent = self.t_done = self.result = None


class Harness:
    def __init__(self, ctx):
        import jax.numpy as jnp

        from bigdl_tpu.serving import InferenceServer

        from benchmark import program

        self.ctx, self.tr = ctx, ctx.traffic
        tr = self.tr
        self.ref = program.reference_for(ctx.config, ctx.overlay)
        self.model = program.build_model(ctx.config, ctx.seed, ctx.clock,
                                         self.ref)
        self.server = InferenceServer(
            self.model, max_batch=tr["max_batch"],
            max_queue=tr.get("max_queue", 256),
            generate_dtype=jnp.dtype(tr["generate_dtype"])).start()
        self.rng = np.random.RandomState(
            (ctx.seed * 7919 + 17) % (2 ** 32))
        self.vocab = ctx.config["vocab_size"]
        self.lock = threading.Lock()
        self.done = threading.Condition(self.lock)
        self.outstanding = 0

    # -- prompts -------------------------------------------------------
    def prompts(self, n: int) -> np.ndarray:
        """``n`` prompts of the cell's length, 1-based ids, from the seed."""
        return self.rng.randint(1, self.vocab + 1,
                                size=(n, self.tr["prompt_len"])
                                ).astype(np.int32)

    # -- warm-up: every (bucket, prompt length, max_new) ----------------
    def warm(self):
        from bigdl_tpu.serving.batcher import bucket_ladder

        tr = self.tr
        warm = self.prompts(tr["max_batch"])
        for bucket in bucket_ladder(tr["max_batch"]):
            sig = ("gen", bucket, tr["prompt_len"], tr["max_new"])
            for _ in range(5):  # a burst the batcher split is sent again
                futs = [self.server.submit_generate(p, tr["max_new"])
                        for p in warm[:bucket]]
                res = [f.result(timeout=1800) for f in futs]
                bad = [r for r in res if not r.ok]
                if bad:
                    raise RuntimeError(f"warm-up request failed: "
                                       f"{bad[0].status.name} {bad[0].error}")
                if sig in self.signatures():
                    break
        want = {("gen", b, tr["prompt_len"], tr["max_new"])
                for b in bucket_ladder(tr["max_batch"])}
        have = set(self.signatures())
        if not want <= have:
            raise RuntimeError(f"warm-up left programs cold: "
                               f"{sorted(want - have)}")
        self.ctx.clock.mark("warm-up of every bucket (compiles or cache "
                            "loads)")

    def signatures(self):
        return [s for s in self.server.compile_stats()["buckets_dispatched"]
                if isinstance(s, tuple)]

    # -- one request ---------------------------------------------------
    def send(self, rec: Sent):
        import jax

        with jax.profiler.TraceAnnotation("bench.submit"):
            with self.lock:
                self.outstanding += 1
            rec.t_sent = time.perf_counter()
            fut = self.server.submit_generate(rec.prompt, self.tr["max_new"])

        def on_done(f, rec=rec):
            rec.t_done = time.perf_counter()
            rec.result = f.result(timeout=0)
            with self.done:
                self.outstanding -= 1
                self.done.notify_all()

        fut.add_done_callback(on_done)
        return fut

    def drain(self, timeout: float = 600.0):
        import jax

        with jax.profiler.TraceAnnotation("bench.await_result"):
            end = time.monotonic() + timeout
            with self.done:
                while self.outstanding:
                    left = end - time.monotonic()
                    if left <= 0:
                        raise TimeoutError(
                            f"{self.outstanding} request(s) unresolved "
                            f"after {timeout}s")
                    self.done.wait(left)

    # -- after the window ----------------------------------------------
    def finish(self, sent: list, t_first: float) -> dict:
        ctx, tr, checks = self.ctx, self.tr, self.ctx.checks
        new_sigs = sorted(set(self.signatures()) - self.sigs_before)
        compiled = ctx.compiles.programs() - ctx.compiles_before
        m = self.server.metrics
        batches = m.batches - self.batches_before
        padded = m.padded_rows - self.padded_before
        peak = ctx.peak_bytes()
        self.server.stop(60)

        ok = [s for s in sent if s.result is not None and s.result.ok]
        failed = len(sent) - len(ok)
        t_last = max((s.t_done for s in ok), default=t_first)
        elapsed = t_last - t_first
        lat = np.array([s.t_done - s.t_due for s in ok])
        tokens = sum(len(s.prompt) + len(np.asarray(s.result.output))
                     for s in ok)
        e2e = {"setup_s": t_first - ctx.t_process}
        if len(ok):
            e2e.update(serve_tokens_per_s=tokens / elapsed,
                       serve_latency_p50_s=float(np.percentile(lat, 50)),
                       serve_latency_p95_s=float(np.percentile(lat, 95)))
        late = np.array([s.t_sent - s.t_due for s in sent])
        spans = {"latency_s": lat.tolist(),
                 "server_latency_s": [s.result.latency_s for s in ok],
                 "queued_s": [s.result.queued_s for s in ok],
                 "late_s": late.tolist()}
        counters = {"batches": batches, "padded_rows": padded,
                    "real_rows": len(ok), "tokens": tokens,
                    "requests_ok": len(ok)}
        ctx.say(f"[window] {len(sent)} requests sent, {len(ok)} OK, "
                f"{failed} failed; {tokens} tokens in {elapsed:.3f}s "
                f"(first dispatch -> last completion); {batches} batches, "
                f"{padded} padded rows; generator late p50/max "
                f"{np.percentile(late, 50) * 1e3:.2f}/"
                f"{late.max() * 1e3:.2f} ms")
        checks.le("compiled_in_window",
                  "programs compiled or loaded inside the window",
                  compiled + len(new_sigs), 0,
                  f"new signatures {new_sigs}")
        checks.le("requests_failed",
                  "requests sent in the window that did not resolve OK",
                  failed, 0, f"of {len(sent)}")
        n_new = tr["max_new"]
        checks.true("reply_length_wrong",
                    f"every reply holds {n_new} tokens",
                    all(np.asarray(s.result.output).shape == (n_new,)
                        for s in ok))
        self._check_tokens(ok)
        out = {"attempted": len(sent), "failed": failed, "end_to_end": e2e,
               "spans": spans, "counters": counters,
               "memory_peak_bytes": peak,
               "shapes": {"prompt_len": tr["prompt_len"],
                          "max_new": n_new, "max_batch": tr["max_batch"]}}
        if ctx.trace:
            out["trace_path"] = ctx.trace_dir
        return out

    def open_window(self):
        """Start the profiler in a traced run, and note the counters as
        they stand when the window opens."""
        ctx, m = self.ctx, self.server.metrics
        if ctx.trace:
            ctx.start_trace()
            ctx.clock.mark("profiler start")
        ctx.compiles_before = ctx.compiles.programs()
        self.sigs_before = set(self.signatures())
        self.batches_before, self.padded_before = m.batches, m.padded_rows

    def _check_tokens(self, ok: list):
        """A seeded sample of finished requests, teacher-forced through
        the plain reference once the program's state is freed."""
        from benchmark.reference import serve_check

        ctx, tr = self.ctx, self.tr
        if not ok:
            ctx.checks.true("none_finished", "some request finished", False)
            return
        pick = np.random.RandomState(ctx.seed % (2 ** 32)).choice(
            len(ok), size=min(tr["check_requests"], len(ok)), replace=False)
        prompts0 = np.stack([ok[i].prompt for i in pick]) - 1
        served0 = np.stack([np.asarray(ok[i].result.output)
                            for i in pick]).astype(np.int64) - 1
        # free the program's state before the reference makes its own
        # weights: the generator cache keeps the model alive, so the
        # arrays are deleted, not just dropped.  Gradient buffers: those
        # that exist (``grad_tree()`` would MAKE a model's worth of
        # zeros to hand over — a served model owns none until asked)
        import jax

        held = [self.model.param_tree()] + [
            getattr(m, "grads", {}) for m in self.model.modules_iter()]
        for leaf in jax.tree_util.tree_leaves(held):
            leaf.delete()
        del held
        self.model = self.server = None
        gc.collect()
        t0 = time.perf_counter()
        out = serve_check.teacher_forced(
            self.ref, ctx.config, ctx.seed, prompts0, served0,
            rows=tr.get("reference_rows", 4), control=ctx.control)
        gap, spread = out["gap"], out["spread"]
        rel = gap / spread
        ctx.say(f"[reference] {gap.size} served tokens of {len(pick)} "
                f"requests teacher-forced in {time.perf_counter() - t0:.1f}s "
                f"after the window; reference's best token served at "
                f"{100.0 * out['agree'].mean():.1f}% of positions; logit "
                f"spread {spread.mean():.3f}; mean gap {gap.mean():.5f}")
        if ctx.control:
            # the control: the fp8 reference's tokens stand in the served
            # tokens' place and go through the same comparison, so the
            # run has to end NOT correct; the program's own readings are
            # printed beside it
            ctx.say(f"[program] served_gap_widest: {float(rel.max()):.6g}; "
                    f"served_gap_mean: {float(rel.mean()):.6g}")
            rel = out["control_gap"] / spread
            ctx.say("[control] fp8 reference in the program's place; it "
                    "agrees with the reference's best at "
                    f"{100.0 * (out['control_gap'] == 0).mean():.1f}% of "
                    "positions")
        ctx.checks.le("served_gap_widest",
                      "widest gap of a served token's logit below the "
                      "reference's best, over the logit spread",
                      float(rel.max()),
                      tr["limits"]["served_gap_over_spread"])
        ctx.checks.le("served_gap_mean", "mean gap over the logit spread",
                      float(rel.mean()),
                      tr["limits"]["served_mean_gap_over_spread"])
