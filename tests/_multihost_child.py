"""Child process for the two-process jax.distributed test (run by
test_multihost.py, one invocation per simulated host)."""
import sys

import jax

# simulated hosts are CPU processes whatever the launcher's environment
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402


def main():
    coordinator, n_proc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

    from bigdl_tpu.utils.engine import Engine

    Engine.init_distributed(coordinator_address=coordinator,
                            num_processes=n_proc, process_id=pid)

    assert jax.process_count() == n_proc, jax.process_count()
    local = jax.local_device_count()
    assert jax.device_count() == n_proc * local, (jax.device_count(), local)

    # a real cross-process (DCN) collective: all-gather each process's
    # contribution and check every process sees the same global result
    from jax.experimental import multihost_utils

    vals = multihost_utils.process_allgather(jnp.float32(pid + 1))
    total = float(jnp.sum(vals))
    expected = n_proc * (n_proc + 1) / 2
    assert total == expected, (total, expected)

    # re-entrancy: a second init_distributed must be a no-op
    Engine.init_distributed()

    print(f"MULTIHOST_OK pid={pid} processes={jax.process_count()} "
          f"devices={jax.device_count()} sum={total}", flush=True)


if __name__ == "__main__":
    main()
