"""The order of a training step, as scenarios (ISSUE 35): the driver
stages step n+1 while step n runs and reports step n once n+1 is
enqueued — nothing a run computes or a trigger sees may move.

``scenarios(kind)`` drives ``LocalOptimizer`` (``"local"``) or
``DistriOptimizer`` (``"distri"``, the suite's 8 virtual devices)
through every case below with a tap on ``engine.step`` that counts the
dispatches and keeps each one's batch, key and learning rate, and
returns plain data: floats as hex, so equality is bitwise.  The same
function, run on the commit BEFORE the change, wrote
``tests/fixtures/driver_order_pr35.json``::

    PYTHONPATH=<parent checkout> JAX_PLATFORMS=cpu \\
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python tests/driver_order_scenarios.py <out.json>

``tests/test_local_optimizer.py`` / ``tests/test_distri_optimizer.py``
hold the change to that file and to the properties that need no
fixture.  An epoch is three batches of 32, and the model has a Dropout,
so the order of the shuffle and of every step's key shows in the loss.
"""
import json
import os
import sys
import zlib

import numpy as np

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "driver_order_pr35.json")
BATCH, EPOCH_BATCHES = 32, 3


def _hex(v) -> str:
    return float(v).hex()


def _tree_crc(tree) -> int:
    import jax

    out = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        out = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(),
                         out)
    return out & 0xFFFFFFFF


def _samples(seed, n=BATCH * EPOCH_BATCHES):
    from bigdl_tpu.dataset import Sample

    r = np.random.RandomState(seed)
    x = r.rand(n, 4).astype(np.float32)
    y = x @ np.array([[1.5], [-2.0], [0.5], [3.0]], np.float32) + 0.7
    return [Sample(x[i], y[i].astype(np.float32)) for i in range(n)]


class Tap:
    """Counts ``engine.step``: one record a dispatch, and the loss it
    returned (an array: read after the run)."""

    def __init__(self, opt):
        self.calls, self.outs = [], []
        build = opt._build_plan_engine

        def tapped(mesh, n_seq):
            engine = build(mesh, n_seq)
            inner = engine.step

            def step(params, slots, buffers, lr, x, y, **kw):
                import jax

                key = kw["rng"]
                if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
                    key = jax.random.key_data(key)
                self.calls.append({
                    "batch": _tree_crc(x), "lr": _hex(lr),
                    "key": [int(k) for k in np.asarray(key).ravel()]})
                out = inner(params, slots, buffers, lr, x, y, **kw)
                self.outs.append(out[0])
                return out

            engine.step = step
            return engine

        opt._build_plan_engine = tapped

    @property
    def n(self) -> int:
        return len(self.calls)

    def losses(self):
        return [_hex(np.asarray(o)) for o in self.outs]


class Watch:
    """A closure trigger: what the state table held at every call, and
    how many steps had been dispatched by then."""

    def __init__(self, tap, stop):
        self.tap, self.stop, self.seen = tap, stop, []

    def __call__(self, state):
        self.seen.append({"neval": int(state["neval"]),
                          "epoch": int(state["epoch"]),
                          "loss": (_hex(state["loss"]) if "loss" in state
                                   else None),
                          "dispatched": self.tap.n})
        return self.stop(state)


def build(kind, steps=None, end=None):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import SGD, max_iteration
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.utils.rng import set_global_seed

    set_global_seed(35)
    model = nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Dropout(0.25),
                          nn.Linear(16, 1))
    cls = LocalOptimizer if kind == "local" else DistriOptimizer
    opt = cls(model, array(_samples(0)), nn.MSECriterion(),
              batch_size=BATCH)
    opt.set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
    tap = Tap(opt)
    watch = Watch(tap, end or max_iteration(steps))
    opt.set_end_when(watch)
    return opt, tap, watch


def _validated(opt, trigger):
    """Validation at ``trigger``; returns the list its results land in:
    (iteration just done, [result as hex])."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.optim import Loss

    got, fired = [], []

    def counted(state):
        hit = trigger(state)
        fired.append((int(state["neval"]) - 1, bool(hit)))
        return hit

    opt.set_validation(counted, array(_samples(1, 64)),
                       [Loss(nn.MSECriterion())], batch_size=BATCH)
    opt._report_validation = lambda state, results: got.append(
        [int(state["neval"]) - 1,
         [_hex(r.result()[0]) for r in results]])
    return got, fired


def _account(tap, watch):
    return {"calls": tap.calls, "losses": tap.losses(), "seen": watch.seen}


def run_epochs(kind):
    """Twelve steps = four epochs of three batches; ``every_epoch``
    validates."""
    from bigdl_tpu.optim import every_epoch

    opt, tap, watch = build(kind, steps=12)
    validations, fired = _validated(opt, every_epoch())
    opt.optimize()
    out = _account(tap, watch)
    out.update(validations=validations,
               trigger_calls=[n for n, _ in fired],
               epoch_ends=[n for n, hit in fired if hit],
               params=_tree_crc(opt.model.param_tree()))
    return out


def run_min_loss(kind, below):
    from bigdl_tpu.optim import min_loss

    opt, tap, watch = build(kind, end=min_loss(below))
    opt.optimize()
    return _account(tap, watch)


def run_checkpointed(kind, directory, steps=6, every=2):
    """``several_iteration(every)`` checkpoints and validates: what each
    read, by the iteration it read it at."""
    from bigdl_tpu.optim import several_iteration
    from bigdl_tpu.utils.file_io import load

    opt, tap, watch = build(kind, steps=steps)
    validations, _ = _validated(opt, several_iteration(every))
    opt.set_checkpoint(directory, several_iteration(every))
    opt.optimize()
    out = _account(tap, watch)
    out["validations"] = validations
    out["checkpoints"] = {
        str(n): _tree_crc(load(os.path.join(
            directory, f"model.{n}")).param_tree())
        for n in range(every, steps + 1, every)}
    return out


def run_to(kind, steps, every=2):
    """A run that ENDS at ``steps``: its parameters and its last
    validation are what a checkpoint and a validation at that iteration
    of a longer run must have read."""
    from bigdl_tpu.optim import several_iteration

    opt, tap, watch = build(kind, steps=steps)
    validations, _ = _validated(opt, several_iteration(every))
    opt.optimize()
    return {"params": _tree_crc(opt.model.param_tree()),
            "validation": validations[-1]}


def run_reentry(kind):
    """Five steps, then ``train_more(3)`` on the compiled engine."""
    opt, tap, watch = build(kind, steps=5)
    opt.reuse_compiled_engine = True
    opt.optimize()
    first = tap.n
    opt.train_more(3)
    out = _account(tap, watch)
    out["first_entry"] = first
    return out


def run_resumed(kind, directory, stop=6, back_to=4, steps=8):
    """Checkpoints every two steps up to ``stop``; a fresh optimizer
    restores iteration ``back_to`` in total and trains on to ``steps``."""
    from bigdl_tpu.optim import max_iteration, several_iteration
    from bigdl_tpu.utils.rng import set_global_seed

    opt, _, _ = build(kind, steps=stop)
    opt.set_checkpoint(directory, several_iteration(2))
    opt.optimize()
    opt2, tap, watch = build(kind, steps=steps)
    set_global_seed(999)  # the checkpoint's stream must overwrite it
    opt2.set_checkpoint(directory, several_iteration(1000))
    assert opt2.resume_from_checkpoint(step=back_to) is True
    opt2.set_end_when(Watch(tap, max_iteration(steps)))
    opt2.optimize()
    return {"calls": tap.calls, "losses": tap.losses()}


def scenarios(kind, tmp):
    epochs = run_epochs(kind)
    # a limit the run crosses in its second epoch: between the sixth
    # smallest and the seventh smallest of the twelve losses
    by_size = sorted(float.fromhex(v) for v in epochs["losses"])
    below = (by_size[5] + by_size[6]) / 2
    return {
        "epochs": epochs,
        "min_loss": dict(run_min_loss(kind, below), below=_hex(below)),
        "checkpointed": run_checkpointed(kind, os.path.join(tmp, "a")),
        "to": {str(n): run_to(kind, n) for n in (2, 4)},
        "reentry": run_reentry(kind),
        "resumed": run_resumed(kind, os.path.join(tmp, "b")),
    }


# ---------------------------------------------------------------------------
# what the scenarios must show: ``got`` from this tree, ``want`` from the
# fixture.  One function a case, so each counts as a test of its own.
# ---------------------------------------------------------------------------

CASES = ("min_loss_dispatches_nothing_after_the_crossing",
         "a_trigger_sees_the_step_just_done_once_an_iteration",
         "checkpoint_and_validation_read_step_n",
         "an_epoch_rolls_over_in_the_parents_order",
         "reentry_and_resume_train_the_next_batch",
         "losses_are_bitwise_the_parents")


def _seen_is_the_step_just_done(run):
    """Call k of the end trigger (k = 0 before any step) sees exactly k
    steps dispatched, ``neval`` = k + 1 and the loss of step k."""
    seen, losses = run["seen"], run["losses"]
    assert [s["dispatched"] for s in seen] == list(range(len(seen)))
    assert [s["neval"] for s in seen] == list(range(1, len(seen) + 1))
    assert seen[0]["loss"] is None
    assert [s["loss"] for s in seen[1:]] == losses[:len(seen) - 1]
    # the last call ended the run: nothing was dispatched after it
    assert len(run["calls"]) == len(losses) == len(seen) - 1


def check_min_loss_dispatches_nothing_after_the_crossing(got, want):
    run, below = got["min_loss"], float.fromhex(got["min_loss"]["below"])
    losses = [float.fromhex(v) for v in run["losses"]]
    assert len(losses) > 1 and losses[-1] < below
    assert all(v >= below for v in losses[:-1])
    _seen_is_the_step_just_done(run)
    assert run == {k: want["min_loss"][k] for k in run}


def check_a_trigger_sees_the_step_just_done_once_an_iteration(got, want):
    for name in ("epochs", "min_loss", "checkpointed"):
        _seen_is_the_step_just_done(got[name])
    run = got["epochs"]
    # the epoch in the table is the one the NEXT step belongs to: it
    # turns with the third, sixth, ninth loss
    assert [s["epoch"] for s in run["seen"]] == [
        1 + k // EPOCH_BATCHES for k in range(13)]
    # and the validation trigger was asked once an iteration too
    assert run["trigger_calls"] == list(range(1, 13))
    assert run["seen"] == want["epochs"]["seen"]


def check_checkpoint_and_validation_read_step_n(got, want):
    run = got["checkpointed"]
    assert [n for n, _ in run["validations"]] == [2, 4, 6]
    for n in (2, 4):
        serial = got["to"][str(n)]
        assert run["checkpoints"][str(n)] == serial["params"]
        assert run["validations"][n // 2 - 1] == serial["validation"]
    assert run["checkpoints"] == want["checkpointed"]["checkpoints"]
    assert run["validations"] == want["checkpointed"]["validations"]
    assert got["to"] == want["to"]


def check_an_epoch_rolls_over_in_the_parents_order(got, want):
    run = got["epochs"]
    assert run["epoch_ends"] == [3, 6, 9, 12]
    assert [n for n, _ in run["validations"]] == [3, 6, 9, 12]
    # the shuffle deals the records anew: twelve different batches, and
    # the parent's twelve
    batches = [c["batch"] for c in run["calls"]]
    assert len(set(batches)) == 12
    assert batches == [c["batch"] for c in want["epochs"]["calls"]]
    assert run["validations"] == want["epochs"]["validations"]


def check_reentry_and_resume_train_the_next_batch(got, want):
    re_, whole = got["reentry"], got["epochs"]
    assert re_["first_entry"] == 5 and len(re_["calls"]) == 8
    # the key stream goes on where the first entry's last step left it:
    # the step staged beside that one drew nothing (the re-entry begins
    # its epoch again, so the next shuffle, and the keys after it, come
    # later than in the whole run)
    assert [c["key"] for c in re_["calls"][:6]] == [
        c["key"] for c in whole["calls"][:6]]
    assert re_["calls"] == want["reentry"]["calls"]
    assert re_["losses"] == want["reentry"]["losses"]
    # a total-state resume at iteration 4 trains steps 5..8 of the
    # uninterrupted run: batches, keys, losses
    res = got["resumed"]
    assert res["calls"] == whole["calls"][4:8]
    assert res["losses"] == whole["losses"][4:8]
    assert res == want["resumed"]


def check_losses_are_bitwise_the_parents(got, want):
    assert got["epochs"]["losses"] == want["epochs"]["losses"]
    assert got["epochs"]["calls"] == want["epochs"]["calls"]
    assert got["epochs"]["params"] == want["epochs"]["params"]
    assert len(got["epochs"]["losses"]) == 12


def fixture():
    with open(FIXTURE) as f:
        return json.load(f)


if __name__ == "__main__":
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        data = {kind: scenarios(kind, os.path.join(tmp, kind))
                for kind in ("local", "distri")}
    with open(sys.argv[1], "w") as f:
        json.dump(data, f, sort_keys=True, separators=(",", ":"))
