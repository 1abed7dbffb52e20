"""Child for the two-process DistriOptimizer lifecycle test
(test_multihost.py): each simulated host joins the jax.distributed
runtime, builds the SAME dataset+model under the same seed, and runs the
full data-parallel driver over the GLOBAL mesh — batches are
device_put with global semantics (every process offers the identical
host batch; JAX transfers only the addressable shards), gradients cross
the process boundary through the step's psum_scatter, and the trained
parameters (replicated specs) are fetched back host-side.

Prints PARAMS_SUM / FINAL_LOSS lines the parent compares across
processes AND against a single-process run of the same global mesh —
process topology must not change the math.
"""
import sys

import jax

# simulated hosts are CPU processes whatever the launcher's environment
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main():
    coordinator, n_proc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

    from bigdl_tpu.utils.engine import Engine

    if n_proc > 1:
        Engine.init_distributed(coordinator_address=coordinator,
                                num_processes=n_proc, process_id=pid)
    assert jax.process_count() == n_proc

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.optim import SGD, max_epoch
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.utils.rng import set_global_seed

    set_global_seed(7)
    model = nn.Sequential(nn.Linear(6, 16), nn.Tanh(),
                          nn.Linear(16, 4), nn.LogSoftMax())

    rng = np.random.RandomState(0)
    feats = rng.randn(40, 6).astype(np.float32)
    labels = (rng.randint(0, 4, 40) + 1).astype(np.float32)
    samples = [Sample(feats[i], labels[i]) for i in range(40)]

    crit = nn.ClassNLLCriterion()

    def dataset_nll(m):
        out = np.asarray(m.forward(feats))
        return float(np.mean([crit.forward(out[i:i + 1], labels[i:i + 1])
                              for i in range(len(feats))]))

    loss0 = dataset_nll(model)

    opt = DistriOptimizer(model, array(samples), crit,
                          batch_size=16)  # 40 % 16 = 8: masked tail batch
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_epoch(3))
    trained = opt.optimize()

    loss1 = dataset_nll(trained)
    psum = float(sum(np.abs(np.asarray(a)).sum()
                     for a in jax.tree_util.tree_leaves(
                         trained.param_tree())))
    assert loss1 < loss0, (loss0, loss1)
    print(f"TRAIN_OK pid={pid} processes={jax.process_count()} "
          f"devices={jax.device_count()}", flush=True)
    print(f"PARAMS_SUM pid={pid} {psum:.6f}", flush=True)
    print(f"FINAL_LOSS pid={pid} {loss1:.6f} from {loss0:.6f}", flush=True)


if __name__ == "__main__":
    main()
