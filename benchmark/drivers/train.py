"""Training driver: the product's ``LocalOptimizer`` / ``DistriOptimizer``
on a learnable token stream, timed in whole optimizer steps.

ONE optimizer object, with its compiled step (``reuse_compiled_engine``),
goes through three entries of ``optimize()``:

  A  one step        -> Adam's first moment is 0.1 x the gradient the
                        optimizer was given: its leaf norms are read
  B  two more steps  -> the parameters after three steps are kept (host)
  C  warm-up steps, then the window: steps are timed from the first
     timed step's dispatch until ``--seconds`` have passed and the step
     in flight has finished (its loss fetched, which waits for the
     device).  The rate is tokens of those whole steps over the time
     those steps took.

The end trigger is the benchmark's clock: the optimizer calls it at the
top of every iteration, after the previous step's loss fetch, so every
call is a whole-step boundary.  After the window the program's state is
dropped and the plain reference follows the same three batches from the
same seed; losses, first-gradient norms and parameter-change norms are
compared leaf by leaf.
"""
from __future__ import annotations

import gc
import time

import numpy as np


class TokenStream:
    """A learnable stream from the seed, shaped like the program's
    datasets (``data(train)``, ``size``, ``shuffle``): every row walks a
    cycle of ``cycle`` tokens (a seeded choice from the whole
    vocabulary) from its own start, so the next token is determined by
    the current one; the rows of a batch all start at different points.
    Batch ``i`` is a pure function of (seed, i), and ``cursor`` says
    where the next entry of ``optimize()`` starts reading."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int,
                 cycle: int):
        from bigdl_tpu.dataset.sample import MiniBatch

        self._mb = MiniBatch
        self.seed, self.batch, self.seq = int(seed), batch, seq
        r = np.random.RandomState(self.seed % (2 ** 32))
        self.tokens = r.choice(vocab, size=cycle, replace=False)
        self.cycle = cycle
        self.cursor = 0

    def ids(self, i: int):
        """Batch ``i`` as 0-based int ids: (inputs, targets) [B, T]."""
        r = np.random.RandomState((self.seed * 1000003 + i) % (2 ** 32))
        starts = r.choice(self.cycle, size=self.batch, replace=False)
        pos = (starts[:, None] + np.arange(self.seq + 1)[None]) % self.cycle
        seq = self.tokens[pos]
        return seq[:, :-1], seq[:, 1:]

    def data(self, train: bool):
        def gen(i):
            while True:
                x, y = self.ids(i)
                # the program's ids are 1-based floats (Torch convention)
                yield self._mb((x + 1).astype(np.float32),
                               (y + 1).astype(np.float32))
                i += 1
                if not train:
                    return
        return gen(self.cursor if train else 0)

    def size(self) -> int:
        return 1 << 40  # records per "epoch": never reached

    def shuffle(self):
        return self

    def state_dict(self):
        return {}

    def load_state_dict(self, state):
        return self


class StepClock:
    """The end trigger.  Called with the optimizer's state table at the
    top of every iteration; records (time, loss of the step just done)
    and ends the entry after ``steps`` steps, or — for the window — once
    ``seconds`` have passed since the first timed step's dispatch."""

    def __init__(self, warm: int = 0, steps: int | None = None,
                 seconds: float | None = None, on_window=None, on_end=None):
        self.warm, self.steps, self.seconds = warm, steps, seconds
        self.on_window, self.on_end = on_window, on_end
        self.calls, self.times, self.losses = 0, [], []
        self.t_start = None

    def __call__(self, state) -> bool:
        now = time.perf_counter()
        if self.calls:
            self.losses.append(float(state.get("loss", float("nan"))))
        self.times.append(now)
        self.calls += 1
        done = self.calls - 1
        if self.steps is not None:
            return done >= self.steps
        if done < self.warm:
            return False
        if self.t_start is None:
            if self.on_window:
                self.on_window()
                now = self.times[-1] = time.perf_counter()
            self.t_start = now
            return False
        if now - self.t_start < self.seconds:
            return False
        if self.on_end:
            self.on_end()
        return True


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from bigdl_tpu import nn
    from bigdl_tpu.optim import Adam, DistriOptimizer, LocalOptimizer

    from benchmark import program
    from benchmark.reference import train as ref_train

    cfg, tr, clock, checks = ctx.config, ctx.traffic, ctx.clock, ctx.checks
    chips = ctx.chips
    batch, seq = tr["batch_per_chip"] * chips, tr["seq_len"]
    vocab = cfg["vocab_size"]
    ref = program.reference_for(cfg, ctx.overlay)

    model = program.build_model(cfg, ctx.seed, clock, ref)
    if tr.get("drop_eager_grad_buffers"):
        # every module keeps a zero gradient buffer per parameter for the
        # Torch-style eager API (|theta| f32 on the device); the plan
        # driver never reads them, and with them this job does not fit:
        # the compiled step fails to load by 0.24 GB (PERF.md)
        model.set_grad_tree(jax.tree_util.tree_map(
            lambda a: jnp.zeros((0,), a.dtype), model.grad_tree()))

    stream = TokenStream(ctx.seed, batch, seq, vocab, tr["cycle"])
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(), True)
    if tr["optimizer"] == "LocalOptimizer":
        opt = LocalOptimizer(model, stream, crit, batch_size=batch)
    else:
        mesh = Mesh(np.array(ctx.devices), ("data",))
        opt = DistriOptimizer(model, stream, crit, batch_size=batch,
                              mesh=mesh)
    adam = tr["adam"]
    opt.set_optim_method(Adam(adam["lr"], beta1=adam["beta1"],
                              beta2=adam["beta2"]))
    opt.set_compute_dtype(jnp.dtype(tr["compute_dtype"]))
    opt.reuse_compiled_engine = True  # one compiled step for A, B and C

    def entry(trigger, cursor):
        stream.cursor = cursor
        opt.set_end_when(trigger)
        opt.optimize()
        return trigger

    # -- A: one step; the gradient the optimizer was given -------------
    a = entry(StepClock(steps=1), 0)
    m1 = program.from_tree(cfg, opt.optim_method._slots["m"])
    grad_norms = {k: float(np.linalg.norm(np.asarray(v, np.float32).ravel()))
                  / (1.0 - adam["beta1"]) for k, v in m1.items()}
    del m1
    clock.mark("first step (compiles) and its optimizer state")
    # -- B: steps two and three; the parameters after three ------------
    b = entry(StepClock(steps=tr["check_steps"] - 1), 1)
    theta = {k: np.array(v, np.float32) for k, v in
             program.from_tree(cfg, model.param_tree()).items()}
    first_losses = a.losses + b.losses
    clock.mark("steps two and three and their parameters")

    # -- C: warm-up, then the window ------------------------------------
    def on_window():
        ctx.compiles_before = ctx.compiles.programs()
        if ctx.trace:
            ctx.start_trace()
        clock.mark("re-entry and warm-up steps")

    def on_end():
        if ctx.trace:  # the trace ends with the last timed step
            ctx.stop_trace()

    c = entry(StepClock(warm=tr["warmup_steps"],
                        seconds=ctx.window_seconds(),
                        on_window=on_window, on_end=on_end),
              tr["check_steps"])
    compiled_in_window = ctx.compiles.programs() - ctx.compiles_before
    peak = ctx.peak_bytes()

    i0 = c.times.index(c.t_start)
    step_times = np.diff(np.array(c.times[i0:]))
    steps = len(step_times)
    elapsed = c.times[-1] - c.t_start
    window_losses = c.losses[i0:]
    tokens = steps * batch * seq
    out = {
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens / chips / elapsed,
            "setup_s": c.t_start - ctx.t_process},
        "spans": {"step_s": step_times.tolist()},
        "counters": {"steps": steps},
        "memory_peak_bytes": peak,
        "shapes": {"batch": batch, "seq": seq, "chips": chips},
    }
    if ctx.trace:
        out["trace_path"] = ctx.trace_dir
    ctx.say(f"[window] {steps} whole steps in {elapsed:.3f}s "
            f"({tokens} tokens, {chips} chip(s)); loss "
            f"{window_losses[0]:.4f} -> {window_losses[-1]:.4f}")

    checks.le("compiled_in_window",
              "programs compiled or loaded inside the window",
              compiled_in_window, 0)
    checks.true("window_loss_not_finite", "window loss finite",
                bool(np.all(np.isfinite(window_losses))))
    checks.true("loss_did_not_fall", "loss falls on the learnable stream",
                window_losses[-1] < first_losses[0],
                f"{first_losses[0]:.4f} at step 1 -> "
                f"{window_losses[-1]:.4f} at the end")

    # -- the plain reference follows the same three batches ------------
    del opt, model, crit
    gc.collect()
    t_ref = time.perf_counter()
    batches = [stream.ids(i) for i in range(tr["check_steps"])]
    res = ref_train.run_steps(ref, cfg, ctx.seed, batches, adam["lr"],
                              adam["beta1"], adam["beta2"],
                              rows=tr["reference_rows"], devices=ctx.devices)
    ref_change = ref_train.change_norms_stacked(ref, res)
    del res["params"]
    got_change = ref_train.change_norms_flat(ref, cfg, ctx.seed, theta)
    lim = tr["limits"]
    # a leaf whose gradient is zero in exact arithmetic (GPT-2's key
    # bias: the softmax does not see it) moves under Adam by rounding
    # noise alone, in any precision — it says nothing about the step
    g_ref = res["first_grad_norms"]
    g_med = float(np.median(list(g_ref.values())))
    live = [k for k in ref_change if g_ref[k] >= 1e-3 * g_med]

    def compare(le, losses, grads, change):
        for i, (got, want) in enumerate(zip(losses, res["losses"]), 1):
            le(f"loss_step{i}_rel",
               f"loss of step {i}: |program - reference| / reference",
               abs(got - want) / abs(want), lim["loss_rel"],
               f"program {got:.6f}, reference {want:.6f}")
        gap, which = _worst_gap(grads, g_ref)
        le("first_grad_worst_leaf",
           "first gradient, worst leaf: |norm gap| / max(leaf, median)",
           gap, lim["grad_norm_rel"], which)
        gap, which = _worst_gap({k: change[k] for k in live},
                                {k: ref_change[k] for k in live})
        le("param_change_worst_leaf",
           "parameter change after three steps, worst leaf: "
           "|norm gap| / max(leaf, median)", gap, lim["change_norm_rel"],
           f"{which}; {len(ref_change) - len(live)} leaves with no "
           "gradient to speak of left out")

    ctx.say(f"[reference] {time.perf_counter() - t_ref:.1f}s after the "
            "window (not set-up, not timed)")
    if not ctx.control:
        compare(checks.le, first_losses, grad_norms, got_change)
        return out
    # the control: the reference's own three steps in fp8 stand in the
    # program's place and go through the same comparison, so the run has
    # to end NOT correct; the program's readings are printed beside it
    compare(lambda key, name, value, limit, why="": ctx.say(
        f"[program] {key}: {value:.6g} (limit <= {limit:.6g})"),
        first_losses, grad_norms, got_change)
    low = ref_train.run_steps(ref, cfg, ctx.seed, batches, adam["lr"],
                              adam["beta1"], adam["beta2"],
                              rows=tr["reference_rows"], mode="fp8",
                              devices=ctx.devices)
    low_change = ref_train.change_norms_stacked(ref, low)
    ctx.say("[control] fp8 reference in the program's place:")
    compare(checks.le, low["losses"], low["first_grad_norms"], low_change)
    return out


def _worst_gap(got: dict, want: dict):
    """Worst leaf of |got - want| over max(want of that leaf, the median
    leaf's want) — some gradients are all but zero."""
    if set(got) != set(want):
        raise ValueError(f"leaf names differ: {set(got) ^ set(want)}")
    med = float(np.median(list(want.values())))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in want}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], (f"worst leaf {worst}: program {got[worst]:.6g}, "
                         f"reference {want[worst]:.6g}, median leaf "
                         f"{med:.6g}")
