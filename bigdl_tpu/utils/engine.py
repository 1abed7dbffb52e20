"""TpuEngine — device-mesh topology in place of the reference's Engine.

The reference ``Engine`` (utils/Engine.scala:93) derives a cluster
topology (node count × core count) from the Spark conf and owns two
thread pools for intra-node parallelism (Engine.scala:229-258).  On TPU
none of that survives: batch parallelism comes from XLA vectorisation,
node parallelism from a ``jax.sharding.Mesh``.  What this Engine keeps
is the *contract*: ``Engine.init``, ``node_number``/``core_number``,
config via ``bigdl.*``-style flags, and a singleton check — plus the new
mesh factory that everything distributed hangs off.

Mesh axes (forward-looking, reference has only data parallelism —
SURVEY §2.2):
  - ``data``  : data parallelism (reference P1/P2)
  - ``model`` : tensor parallelism
  - ``seq``   : sequence/context parallelism (ring attention)
  - ``pipe``  : pipeline parallelism
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def get_property(name: str, default=None):
    """``bigdl.*`` system properties become env vars: bigdl.foo → BIGDL_FOO."""
    env = name.replace(".", "_").upper()
    return os.environ.get(env, os.environ.get(name, default))


class Engine:
    """Process-wide topology singleton (reference utils/Engine.scala)."""

    _initialized = False
    _node_number = 1
    _core_number = 1
    _mesh: Optional[Mesh] = None
    engine_type = "xla"  # reference: MklBlas (Engine.scala:132)

    @classmethod
    def init(cls, node_number: Optional[int] = None,
             core_number: Optional[int] = None, on_spark: bool = False):
        """Discover devices.  node = host, core = local device (1 core : 1 chip).

        Reference: Engine.init (Engine.scala:93) parses the Spark conf;
        here topology comes from the jax runtime.  Explicit arguments are
        honoured for tests that simulate a topology (SURVEY §4.3).
        """
        if node_number is None:
            node_number = int(get_property("bigdl.node.number", jax.process_count()))
        if core_number is None:
            core_number = int(get_property("bigdl.core.number",
                                           jax.local_device_count()))
        cls._node_number = node_number
        cls._core_number = core_number
        cls._initialized = True
        cls._mesh = None
        return cls

    _distributed = False

    @classmethod
    def init_distributed(cls, coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None):
        """Join the multi-host jax runtime then discover topology.

        The DCN analogue of the reference's Spark-cluster bring-up
        (Engine.createSparkConf + init, Engine.scala:74-93): one process
        per host, devices global after initialize().  Arguments fall back
        to the ``bigdl.coordinator.*`` properties / jax env autodetection.
        Re-entrant like ``init``: the one-shot jax.distributed.initialize
        only runs on the first call.
        """
        if not cls._distributed:
            kwargs = {}
            addr = (coordinator_address
                    or get_property("bigdl.coordinator.address"))
            if addr is not None:
                kwargs["coordinator_address"] = addr
            n = (num_processes
                 if num_processes is not None
                 else get_property("bigdl.coordinator.num.processes"))
            if n is not None:
                kwargs["num_processes"] = int(n)
            pid = (process_id if process_id is not None
                   else get_property("bigdl.coordinator.process.id"))
            if pid is not None:
                kwargs["process_id"] = int(pid)
            jax.distributed.initialize(**kwargs)
            cls._distributed = True
        return cls.init()

    @classmethod
    def node_number(cls) -> int:
        cls._ensure()
        return cls._node_number

    @classmethod
    def core_number(cls) -> int:
        cls._ensure()
        return cls._core_number

    @classmethod
    def device_count(cls) -> int:
        cls._ensure()
        return cls._node_number * cls._core_number

    @classmethod
    def _ensure(cls):
        if not cls._initialized:
            cls.init()

    @classmethod
    def check_singleton(cls) -> bool:
        """Reference Engine.checkSingleton (Engine.scala:165) guards one
        BigDL instance per executor; here one Engine per process."""
        return cls._initialized

    # ------------------------------------------------------------------
    # Mesh factory — the TPU-native replacement for parseExecutorAndCore
    # ------------------------------------------------------------------
    @classmethod
    def create_mesh(cls, data: Optional[int] = None, model: int = 1,
                    seq: int = 1, pipe: int = 1,
                    devices: Optional[Sequence] = None) -> Mesh:
        """Build a 4-axis mesh ``(data, model, seq, pipe)`` over all devices.

        Unspecified ``data`` soaks up the remaining devices.  Collectives
        ride ICI when a contiguous axis maps to a physical ring; XLA picks
        the decomposition.
        """
        cls._ensure()
        devs = list(devices if devices is not None else jax.devices())
        n = len(devs)
        rest = model * seq * pipe
        if data is None:
            if n % rest != 0:
                raise ValueError(f"{n} devices not divisible by model*seq*pipe={rest}")
            data = n // rest
        if data * rest != n:
            raise ValueError(f"mesh {data}x{model}x{seq}x{pipe} != {n} devices")
        arr = np.array(devs).reshape(data, model, seq, pipe)
        return Mesh(arr, ("data", "model", "seq", "pipe"))

    @classmethod
    def default_mesh(cls) -> Mesh:
        cls._ensure()
        if cls._mesh is None:
            cls._mesh = cls.create_mesh()
        return cls._mesh

    @classmethod
    def set_default_mesh(cls, mesh: Mesh):
        cls._mesh = mesh

    @classmethod
    def reset(cls):
        cls._initialized = False
        cls._mesh = None
        cls._node_number = 1
        cls._core_number = 1


def init_engine(*args, **kwargs):
    """pyspark parity: ``init_engine()`` (pyspark/bigdl/util/engine.py)."""
    return Engine.init(*args, **kwargs)
