"""Elastic multi-host training: heartbeats, membership, shrink-to-survivors.

The reference framework ships a straggler-drop knob
(``Optimizer.setDropModuleProperty``, Optimizer.scala:229-243) because
its synchronous parameter manager stalls the whole gang on one slow or
dead worker (BigDL, arXiv:1804.05839 §4; SparkNet, arXiv:1511.06051
makes tolerating slow/failed workers the key to practical cluster
training).  Spark gave it task re-execution for free; a TPU-native
trainer has no such substrate, so this module owns the cluster-level
story end to end:

* **Heartbeats + membership** — every host publishes liveness and its
  recent step time through a pluggable :class:`KVTransport`
  (:class:`InMemoryKV` for tests/benches, :class:`FileKV` over a shared
  directory so CPU CI exercises the real read/write paths;
  ``jax.distributed``'s KV store carries the same protocol on a real
  pod).  Membership is versioned by a monotonically increasing
  **incarnation** number: incarnation *n* names an exact member set,
  and every reconfiguration — shrink, eviction, regrow — is a bump to
  *n+1* that all survivors rendezvous on.
* **Shrink-to-survivors** — on a membership change every survivor
  restores the last verified checkpoint
  (:func:`~bigdl_tpu.resilience.checkpoint.verified_load` walk-back),
  rebuilds the mesh at the **largest valid shard count** for the new
  member set (:func:`largest_valid_shards`), re-shards, and resumes.
  A departed host that comes back publishes a ``rejoin`` beat and is
  re-admitted at the next incarnation boundary (**regrow**).
* **Straggler policy** — per-host step-time skew (vs the cluster
  median) is tracked from the heartbeats; chronic stragglers are warned
  about and, within the reference drop knobs' budget, voted out at an
  incarnation boundary (:class:`StragglerPolicy`).
* **Hung-collective watchdog** — :mod:`.watchdog` bounds each step so a
  dead peer mid-collective surfaces as a retryable
  ``HungCollectiveError`` instead of an eternal block.

:class:`ElasticContext` packages all of it behind the three hooks the
training drivers call (``begin_attempt`` / ``on_step_start`` /
``run_step``); ``Optimizer.set_elastic`` wires it into every mesh path.
"""
from __future__ import annotations

import json
import logging
import os
import statistics
import threading
import time
import urllib.parse
from typing import (Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

from ..telemetry.events import record_change as _record_change
from ..telemetry.registry import default_registry
from .watchdog import CollectiveWatchdog, HungCollectiveError

log = logging.getLogger("bigdl_tpu")


def _count(name: str, help: str, n: float = 1.0):
    """Bump a process-wide counter (the telemetry default registry) —
    cluster events must land in the one scrapeable snapshot whether or
    not a Telemetry bundle is attached."""
    default_registry().counter(name, help).inc(n)

__all__ = [
    "ElasticContext", "ElasticCoordinator", "FileKV", "InMemoryKV",
    "KVTransport", "MembershipChangedError", "SimulatedHost",
    "StragglerPolicy", "largest_valid_shards",
]


class MembershipChangedError(RuntimeError):
    """The cluster reconfigured (host death, eviction, or rejoin) — the
    current attempt's mesh no longer matches the membership.  Retryable
    (``code`` ``"UNAVAILABLE"``): the driver restores the last verified
    checkpoint and re-enters with the new incarnation's mesh."""

    code = "UNAVAILABLE"

    def __init__(self, message: str, incarnation: Optional[int] = None,
                 members: Sequence[str] = ()):
        super().__init__(message)
        self.incarnation = incarnation
        self.members = tuple(members)


# ---------------------------------------------------------------------------
# KV transports
# ---------------------------------------------------------------------------

class KVTransport:
    """Minimal shared-KV contract the membership protocol needs.  Real
    deployments back this with ``jax.distributed``'s coordination
    service; CI uses the two implementations below."""

    def put(self, key: str, value: str) -> None:
        raise NotImplementedError

    def get(self, key: str) -> Optional[str]:
        raise NotImplementedError

    def keys(self, prefix: str = "") -> List[str]:
        raise NotImplementedError

    def delete(self, key: str) -> None:
        raise NotImplementedError


class InMemoryKV(KVTransport):
    """Dict-backed transport for single-process simulations (tests)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._data: Dict[str, str] = {}

    def put(self, key, value):
        with self._lock:
            self._data[str(key)] = str(value)

    def get(self, key):
        with self._lock:
            return self._data.get(str(key))

    def keys(self, prefix=""):
        with self._lock:
            return sorted(k for k in self._data if k.startswith(prefix))

    def delete(self, key):
        with self._lock:
            self._data.pop(str(key), None)


class FileKV(KVTransport):
    """Directory-backed transport: one file per key (name = the
    URL-quoted key), writes atomic via tmp + rename — the same
    discipline as the checkpoint layer, so a reader never sees a torn
    value.  Works over any shared filesystem, which is exactly what a
    multi-process CPU CI (or an NFS-backed dev pod) has."""

    def __init__(self, directory: str):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.directory,
                            urllib.parse.quote(str(key), safe=""))

    def put(self, key, value):
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            f.write(str(value))
        os.replace(tmp, path)

    def get(self, key):
        try:
            with open(self._path(key)) as f:
                return f.read()
        except OSError:
            return None

    def keys(self, prefix=""):
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            if ".tmp." in name:
                continue
            key = urllib.parse.unquote(name)
            if key.startswith(prefix):
                out.append(key)
        return sorted(out)

    def delete(self, key):
        try:
            os.remove(self._path(key))
        except OSError:
            pass


# ---------------------------------------------------------------------------
# coordinator: heartbeats + incarnation-numbered membership
# ---------------------------------------------------------------------------

_HB = "hb/"
_INC = "inc"
_ACK = "ack/"
_EVICTED = "evicted/"
_SDC = "sdc/"


class BoundedLog(list):
    """A list that keeps only its newest ``maxlen`` items — the
    bounded-memory event log (keeps plain-list semantics: slicing,
    equality with lists, `json`-serializable) for accumulators that
    would otherwise grow for the life of a long run."""

    def __init__(self, maxlen: int, iterable=()):
        super().__init__(iterable)
        self.maxlen = int(maxlen)
        self._trim()

    def _trim(self):
        if len(self) > self.maxlen:
            del self[:len(self) - self.maxlen]

    def append(self, item):
        super().append(item)
        self._trim()

    def extend(self, items):
        super().extend(items)
        self._trim()


class ElasticCoordinator:
    """One host's handle on the cluster membership protocol.

    Keys (all JSON strings through the transport):

    * ``hb/<host>``      — ``{step, step_time, ts, rejoin}`` liveness beat
    * ``inc``            — ``{n, members, reason, by}`` current incarnation
    * ``ack/<n>/<host>`` — host has adopted incarnation ``n``
    * ``evicted/<host>`` — straggler eviction marker (cleared on readmit)

    ``ts`` uses this coordinator's ``clock`` — injectable so liveness
    tests need no real waiting.
    """

    def __init__(self, host: str, transport: KVTransport,
                 heartbeat_timeout: float = 2.0,
                 clock: Callable[[], float] = time.monotonic):
        self.host = str(host)
        self.transport = transport
        self.heartbeat_timeout = float(heartbeat_timeout)
        self._clock = clock

    # -- liveness -------------------------------------------------------
    def heartbeat(self, step: int = 0, step_time: Optional[float] = None,
                  rejoin: bool = False):
        self.transport.put(_HB + self.host, json.dumps({
            "host": self.host, "step": int(step),
            "step_time": step_time, "ts": self._clock(),
            "rejoin": bool(rejoin)}))

    def beats(self) -> Dict[str, dict]:
        out = {}
        for key in self.transport.keys(_HB):
            raw = self.transport.get(key)
            if raw is None:
                continue
            try:
                b = json.loads(raw)
            except ValueError:
                continue
            out[key[len(_HB):]] = b
        return out

    def alive(self, beats: Optional[Dict[str, dict]] = None) -> Set[str]:
        now = self._clock()
        beats = self.beats() if beats is None else beats
        return {h for h, b in beats.items()
                if now - float(b.get("ts", -1e18)) <= self.heartbeat_timeout}

    def leader_step(self, leader: str) -> int:
        """Published step counter of ``leader`` (0 when absent) — the
        shared clock the deterministic fault schedules key off."""
        raw = self.transport.get(_HB + leader)
        if raw is None:
            return 0
        try:
            return int(json.loads(raw).get("step", 0))
        except ValueError:
            return 0

    # -- membership -----------------------------------------------------
    def bootstrap(self, members: Sequence[str]):
        """Write incarnation 0 with the initial gang (idempotent: a
        pre-existing incarnation wins)."""
        if self.transport.get(_INC) is None:
            self.transport.put(_INC, json.dumps({
                "n": 0, "members": sorted(members),
                "reason": "bootstrap", "by": self.host}))

    def membership(self) -> Tuple[int, Tuple[str, ...]]:
        raw = self.transport.get(_INC)
        if raw is None:
            return 0, (self.host,)
        rec = json.loads(raw)
        return int(rec["n"]), tuple(rec["members"])

    def propose(self, members: Sequence[str], reason: str,
                expect: Optional[int] = None) -> Optional[int]:
        """Publish incarnation ``current+1`` with ``members``.  With
        ``expect``, only when the current incarnation still matches
        (losing a race means someone else reconfigured first — adopt
        theirs instead).  Returns the new incarnation, or None."""
        cur, _ = self.membership()
        if expect is not None and cur != expect:
            return None
        n = cur + 1
        self.transport.put(_INC, json.dumps({
            "n": n, "members": sorted(set(members)), "reason": str(reason),
            "by": self.host}))
        log.warning("elastic: proposed incarnation %d (%s) members=%s",
                    n, reason, sorted(set(members)))
        _record_change("membership_change",
                       f"incarnation={n} reason={reason} "
                       f"members={len(set(members))}",
                       source="resilience.elastic", host=self.host)
        self.ack(n)
        return n

    def ack(self, n: int):
        self.transport.put(f"{_ACK}{int(n)}/{self.host}", "1")

    def acked(self, n: int) -> Set[str]:
        prefix = f"{_ACK}{int(n)}/"
        return {k[len(prefix):] for k in self.transport.keys(prefix)}

    def rendezvous(self, n: int, members: Sequence[str],
                   timeout: float = 5.0, poll: float = 0.01,
                   sleep: Callable[[float], None] = time.sleep) -> Set[str]:
        """Wait (bounded) until every member has acked incarnation
        ``n``; returns the acked set — callers drop the laggards and
        re-propose rather than blocking forever."""
        deadline = self._clock() + float(timeout)
        want = set(members)
        while True:
            got = self.acked(n)
            if want <= got or self._clock() >= deadline:
                return got
            sleep(poll)

    # -- eviction markers ----------------------------------------------
    def evict(self, host: str, reason: str):
        self.transport.put(_EVICTED + str(host), json.dumps(
            {"reason": str(reason), "by": self.host}))
        _record_change("membership_evict", str(reason),
                       source="resilience.elastic", host=host)

    def evicted(self) -> Set[str]:
        return {k[len(_EVICTED):] for k in self.transport.keys(_EVICTED)}

    def readmit(self, host: str):
        self.transport.delete(_EVICTED + str(host))
        _record_change("membership_readmit",
                       source="resilience.elastic", host=host)


# ---------------------------------------------------------------------------
# straggler policy
# ---------------------------------------------------------------------------

class StragglerPolicy:
    """Step-time skew tracking + bounded eviction votes.

    A host is *warned* about when its published step time exceeds
    ``skew_threshold`` × the cluster median, and becomes an eviction
    *victim* after ``patience`` consecutive over-threshold observations
    — provided the ``eviction_budget`` (total evictions allowed for the
    run) is not spent.  The reference drop knobs map onto this via
    :meth:`from_drop_knobs`.

    ``relax_before_evict`` interposes the relaxed-synchrony escape
    hatch (docs/elastic.md): the first ``max_relax_rounds`` times a
    host qualifies for eviction, the policy instead WIDENS the
    effective local-SGD averaging period (:attr:`period_factor`
    multiplies each ``periodic(k)`` rule's cadence — local steps keep
    landing while the straggler lags, and the averaging collective
    that would stall on it fires less often) and gives the host a
    fresh patience window.  Only when the skew sustains past every
    relax round does :meth:`victim` fall through to the eviction vote
    — eviction becomes the last resort, not the first response.  A
    round where every relaxed host is back under threshold resets the
    factor to 1 (the schedule tightens back once the straggler
    recovers).
    """

    def __init__(self, skew_threshold: float = 3.0, patience: int = 3,
                 eviction_budget: int = 1, sustain: float = 0.0,
                 relax_before_evict: bool = False,
                 relax_factor: float = 2.0, max_relax_rounds: int = 2,
                 clock: Callable[[], float] = time.monotonic):
        if skew_threshold <= 1.0:
            raise ValueError("skew_threshold must be > 1")
        self.skew_threshold = float(skew_threshold)
        self.patience = max(1, int(patience))
        self.eviction_budget = max(0, int(eviction_budget))
        # sustain: seconds a host must STAY over threshold before it can
        # be voted out.  Observation cadence is the driver's step rate,
        # which can be far faster than peers refresh their beats — a
        # count alone would let one stale spike read as a chronic
        # straggler within milliseconds.
        self.sustain = float(sustain)
        self.relax_before_evict = bool(relax_before_evict)
        if relax_factor <= 1.0:
            raise ValueError("relax_factor must be > 1")
        self.relax_factor = float(relax_factor)
        self.max_relax_rounds = max(0, int(max_relax_rounds))
        self.relax_rounds = 0
        self.relaxed_hosts: Dict[str, int] = {}
        self._clock = clock
        self.evicted_count = 0
        self._streak: Dict[str, int] = {}
        self._since: Dict[str, float] = {}
        self.warnings: Dict[str, float] = {}

    @property
    def period_factor(self) -> float:
        """Multiplier for every ``periodic(k)`` rule's effective
        averaging period (1.0 = the configured cadence)."""
        if not self.relax_before_evict or self.relax_rounds <= 0:
            return 1.0
        return self.relax_factor ** self.relax_rounds

    @classmethod
    def from_drop_knobs(cls, drop_percentage: float,
                        max_drop_percentage: float, n_hosts: int,
                        warmup_iteration: int = 200,
                        sustain: float = 0.0
                        ) -> Optional["StragglerPolicy"]:
        """Map the reference ``setDropModuleProperty`` knobs
        (Optimizer.scala:229-243) onto the policy: ``drop_percentage``
        sets the sensitivity (skew threshold ``max(1.5,
        1/drop_percentage)`` — the larger the fraction you were willing
        to drop per sync, the lower the skew a host may sustain),
        ``max_drop_percentage`` caps the eviction budget as a fraction
        of the gang, and ``warmup_iteration`` scales the patience
        (observations before a vote, ``warmup/100``).  ``0`` disables
        (returns None), matching the reference default."""
        drop = float(drop_percentage)
        if drop <= 0:
            return None
        budget = max(1, int(round(float(max_drop_percentage or drop)
                                  * max(1, int(n_hosts)))))
        return cls(
            skew_threshold=max(1.5, 1.0 / max(drop, 0.1)),
            patience=max(1, int(warmup_iteration) // 100),
            eviction_budget=budget, sustain=sustain)

    def observe(self, step_times: Dict[str, float]) -> Dict[str, float]:
        """Feed one round of per-host step times; returns the hosts
        currently over threshold with their skew."""
        times = {h: float(t) for h, t in step_times.items()
                 if t is not None and t > 0}
        if len(times) < 2:
            return {}
        med = statistics.median(times.values())
        if med <= 0:
            return {}
        warn = {}
        now = self._clock()
        for h, t in times.items():
            skew = t / med
            if skew >= self.skew_threshold:
                if self._streak.get(h, 0) == 0:
                    self._since[h] = now
                self._streak[h] = self._streak.get(h, 0) + 1
                warn[h] = skew
            else:
                self._streak[h] = 0
                self._since.pop(h, None)
        self.warnings = warn
        if self.relaxed_hosts and not any(h in warn
                                          for h in self.relaxed_hosts):
            # every relaxed host is back under threshold: tighten the
            # averaging schedule back to its configured cadence
            self.relax_rounds = 0
            self.relaxed_hosts.clear()
        return warn

    def victim(self, exclude: Sequence[str] = ()) -> Optional[str]:
        """The host to vote out at the next incarnation boundary, or
        None (nobody chronic, budget spent, or — under
        ``relax_before_evict`` — a relax round absorbed the skew
        instead).  Chronic = over threshold for ``patience``
        consecutive observations AND ``sustain`` seconds of wall
        clock."""
        if self.evicted_count >= self.eviction_budget:
            return None
        now = self._clock()
        over = sorted(
            ((s, h) for h, s in self._streak.items()
             if s >= self.patience and h not in exclude
             and now - self._since.get(h, now) >= self.sustain),
            reverse=True)
        if not over:
            return None
        host = over[0][1]
        if self.relax_before_evict \
                and self.relax_rounds < self.max_relax_rounds:
            # widen the effective averaging period instead of voting:
            # the straggler gets a fresh patience window to catch up
            # under the cheaper schedule
            self.relax_rounds += 1
            self.relaxed_hosts[host] = self.relaxed_hosts.get(host,
                                                              0) + 1
            self._streak[host] = 0
            self._since.pop(host, None)
            return None
        return host

    def record_eviction(self, host: str):
        self.evicted_count += 1
        self._streak.pop(host, None)
        self._since.pop(host, None)


# ---------------------------------------------------------------------------
# shard-count math
# ---------------------------------------------------------------------------

def largest_valid_shards(n_hosts: int, batch_size: Optional[int] = None,
                         n_devices: Optional[int] = None) -> int:
    """Largest data-shard count a surviving gang can run: at most one
    shard per member (and per device), shrunk until it divides the
    global batch — the shrink-to-survivors mesh is always valid for the
    existing batch pipeline, never a remainder-shard special case."""
    k = max(1, int(n_hosts))
    if n_devices is not None:
        k = min(k, max(1, int(n_devices)))
    if batch_size is not None:
        while k > 1 and int(batch_size) % k != 0:
            k -= 1
    return k


# ---------------------------------------------------------------------------
# the driver-facing context
# ---------------------------------------------------------------------------

class ElasticContext:
    """Everything ``Optimizer.set_elastic`` needs, behind three hooks:

    * :meth:`begin_attempt` — start of every optimize attempt: adopt the
      current incarnation (rendezvousing with the other members when it
      changed), reset the step-time estimator, rebuild the straggler
      policy for the member set.
    * :meth:`on_step_start` — once per iteration before the batch:
      heartbeat, detect dead members / a newer incarnation / chronic
      stragglers / rejoiners, and raise
      :class:`MembershipChangedError` when the gang must reconfigure.
    * :meth:`run_step` — run the compiled step under the watchdog
      deadline (blocking on the loss so hangs are covered), feed the
      estimator, and close out recovery timing.

    Counters (`incarnation_changes`, `evictions`, watchdog ``trips``,
    ``recoveries`` wall-clock) are exported to
    :class:`~bigdl_tpu.visualization.ElasticSummary` when one is
    attached.
    """

    def __init__(self, coordinator: ElasticCoordinator, *,
                 watchdog: Optional[CollectiveWatchdog] = None,
                 straggler: Optional[StragglerPolicy] = None,
                 summary=None, mesh_factory: Optional[Callable] = None,
                 batch_size: Optional[int] = None,
                 rendezvous_timeout: float = 5.0,
                 regrow_after_steps: int = 3,
                 integrity_cadence: int = 0,
                 integrity_timeout: float = 2.0,
                 integrity_summary=None,
                 telemetry=None, telemetry_cadence: int = 10,
                 sleep: Callable[[float], None] = time.sleep):
        self.coordinator = coordinator
        self.watchdog = watchdog or CollectiveWatchdog()
        self.straggler = straggler
        self.summary = summary
        self.batch_size = batch_size
        self.rendezvous_timeout = float(rendezvous_timeout)
        self.regrow_after_steps = max(1, int(regrow_after_steps))
        # cross-host SDC vote knobs (resilience/integrity.py): every
        # ``integrity_cadence`` steps each member publishes a param
        # checksum through the transport and the strict majority defines
        # truth; 0 disables.  ``integrity_timeout`` bounds the wait for
        # peers' checksums (a silent peer counts against quorum).
        self.integrity_cadence = max(0, int(integrity_cadence))
        self.integrity_timeout = float(integrity_timeout)
        self.integrity_summary = integrity_summary
        # cross-host telemetry (bigdl_tpu/telemetry): every
        # ``telemetry_cadence`` steps this host publishes its metric/
        # goodput snapshot under ``tm/<incarnation>/<host>`` (keyed
        # like the SDC votes, so a reconfigured cluster never reads a
        # departed membership's numbers); the leader merges the gang's
        # payloads via cluster_snapshot().  Attached by
        # Optimizer.set_telemetry; 0 disables publishing.
        self.telemetry = telemetry
        self.telemetry_cadence = max(0, int(telemetry_cadence))
        self._sleep = sleep
        self._mesh_factory = mesh_factory
        self._mesh_template = None
        self._n_devices: Optional[int] = None
        self._drop_knobs: Optional[Tuple[float, float, int]] = None
        # background publisher (telemetry/publish.py): KV-transport
        # puts for telemetry snapshots and vote checksums run off the
        # step critical path, with incarnation-keyed staleness discard.
        # Built lazily; close() joins it.
        self._publisher = None
        # parameter-server embedding legs (nn/embedding_store.py):
        # every adopted membership change re-partitions each attached
        # table over the survivors before training resumes
        self._embedding_stores: List = []
        # -- state ------------------------------------------------------
        self.incarnation: Optional[int] = None
        self.members: Tuple[str, ...] = ()
        self.current_shards: Optional[int] = None
        self._last_dt: Optional[float] = None
        self._last_step = 0
        self._steps_since_change = 0
        self._fault_at: Optional[float] = None
        # -- counters ---------------------------------------------------
        # event logs are BOUNDED (keep-newest window): a week-long run
        # retains the recent window instead of growing RSS without
        # limit (the LONGRUN leak audit — a 150-min run appended ~141k
        # step_log tuples here)
        self.incarnation_changes = 0
        self.evictions = 0
        self.evicted_hosts: List[str] = BoundedLog(1024)
        self.recoveries: List[float] = BoundedLog(1024)
        self.step_log: List[Tuple[int, int, float, float]] = \
            BoundedLog(2048)
        self.shard_history: List[int] = BoundedLog(1024)
        self.sdc_votes = 0
        self.sdc_disagreements = 0
        self.sdc_evictions = 0
        self.sdc_detected_steps: List[int] = BoundedLog(1024)
        # (step, vote wall s)
        self.vote_log: List[Tuple[int, float]] = BoundedLog(2048)

    # -- configuration --------------------------------------------------
    @property
    def host(self) -> str:
        return self.coordinator.host

    def attach(self, n_devices: Optional[int] = None,
               batch_size: Optional[int] = None, mesh_template=None):
        """Driver hook: record the local device pool, the batch size
        the shrink math must respect, and the mesh TEMPLATE whose
        non-data axes a shrink must keep (ISSUE 8: a shrink on a
        data x model [x pipe] mesh re-derives a mesh that still
        tensor/pipeline-parallelizes instead of silently degrading to
        data-only)."""
        if n_devices is not None:
            self._n_devices = int(n_devices)
        if batch_size is not None:
            self.batch_size = int(batch_size)
        if mesh_template is not None:
            self._mesh_template = mesh_template
        return self

    def configure_straggler_from_knobs(self, drop_percentage: float,
                                       max_drop_percentage: float,
                                       warmup_iteration: int = 200):
        """Install the reference drop knobs; the concrete policy is
        (re)built per incarnation so the budget scales with the live
        member count."""
        self._drop_knobs = (float(drop_percentage),
                            float(max_drop_percentage),
                            int(warmup_iteration))
        return self

    # -- parameter-server embedding legs ---------------------------------
    def attach_embedding_store(self, store):
        """Register this host's
        :class:`~bigdl_tpu.nn.embedding_store.EmbeddingStore` leg: on
        every adopted membership change the context re-partitions the
        table over the survivors (sealed, crc32c-verified shards over
        the SAME KV transport the membership protocol rides — the
        store inherits the coordinator's transport if it has none), so
        the optimize retry that follows
        :class:`MembershipChangedError` resumes against re-owned,
        verified rows — no step trains on a torn table."""
        if store.kv is None:
            store.kv = self.coordinator.transport
        self._embedding_stores.append(store)
        return self

    def _repartition_stores(self):
        for store in self._embedding_stores:
            if store.members == self.members:
                continue
            dead = set(store.members) - set(self.members)
            stats = store.repartition(self.members, dead=dead,
                                      sleep=self._sleep)
            log.warning(
                "elastic: embedding table %r re-partitioned to "
                "version %d over %d member(s) — %d block(s) in, "
                "%d out, %d row(s) moved (%d from checkpointed legs)",
                store.table, stats["version"], len(self.members),
                stats["imported_blocks"], stats["exported_blocks"],
                stats["moved_rows"], stats["recovered_from_checkpoint"])

    def counters(self) -> dict:
        return {
            "incarnation": self.incarnation,
            "members": list(self.members),
            "incarnation_changes": self.incarnation_changes,
            "evictions": self.evictions,
            "evicted_hosts": list(self.evicted_hosts),
            "watchdog_trips": self.watchdog.trips,
            "recoveries_s": list(self.recoveries),
            "shard_history": list(self.shard_history),
            "sdc_votes": self.sdc_votes,
            "sdc_disagreements": self.sdc_disagreements,
            "sdc_evictions": self.sdc_evictions,
            "sdc_detected_steps": list(self.sdc_detected_steps),
        }

    # -- mesh -----------------------------------------------------------
    def current_mesh(self):
        """The mesh this incarnation trains on: largest valid DATA
        shard count for the member set over the local device pool,
        with the attached template's non-data axes (model/seq/pipe)
        kept at full size — shrink/regrow is one mesh(+plan)
        re-derivation for ANY mesh shape (the factory defaults to
        :func:`parallel.spmd.survivor_mesh`)."""
        import jax

        n_dev = self._n_devices or len(jax.devices())
        template = self._mesh_template
        rest = 1
        if template is not None:
            for a in template.axis_names:
                if a != "data":
                    rest *= int(template.shape[a])
        k = largest_valid_shards(len(self.members) or 1,
                                 self.batch_size,
                                 max(1, n_dev // rest))
        self.current_shards = k
        self.shard_history.append(k)
        if self._mesh_factory is not None:
            return self._mesh_factory(k)
        from ..parallel.spmd import survivor_mesh

        return survivor_mesh(k, template=template)

    # -- lifecycle hooks -------------------------------------------------
    def begin_attempt(self):
        c = self.coordinator
        c.heartbeat(step=self._last_step, step_time=self._last_dt)
        n, members = c.membership()
        if self.incarnation is None:
            # first attach: adopt the bootstrap incarnation quietly
            c.ack(n)
            self._adopt(n, members, count=False)
        elif n != self.incarnation:
            c.ack(n)
            for _ in range(3):
                got = c.rendezvous(n, members,
                                   timeout=self.rendezvous_timeout,
                                   sleep=self._sleep)
                missing = set(members) - got
                if not missing:
                    break
                # laggards are suspects too: shrink past them rather
                # than blocking the survivors
                log.warning("elastic: rendezvous %d timed out waiting "
                            "for %s — proposing without them",
                            n, sorted(missing))
                survivors = [m for m in members if m not in missing]
                n2 = c.propose(survivors, "rendezvous timeout", expect=n)
                if n2 is None:
                    n, members = c.membership()
                    c.ack(n)
                else:
                    n, members = n2, tuple(sorted(survivors))
            self._adopt(n, members, count=True)
        # membership settled for this attempt
        if self._drop_knobs is not None:
            # rebuilt per incarnation so the budget scales with the live
            # gang; a vote needs skew sustained past two heartbeat
            # timeouts — one stale spike must never read as chronic
            self.straggler = StragglerPolicy.from_drop_knobs(
                self._drop_knobs[0], self._drop_knobs[1],
                n_hosts=len(self.members),
                warmup_iteration=self._drop_knobs[2],
                sustain=2.0 * self.coordinator.heartbeat_timeout)
            if self.straggler is not None:
                # the eviction budget is a RUN budget, not a
                # per-incarnation allowance — carry the spend forward
                self.straggler.evicted_count = self.evictions
        self.watchdog.estimator.reset()
        self._steps_since_change = 0

    def _adopt(self, n: int, members: Sequence[str], count: bool):
        self.incarnation = int(n)
        self.members = tuple(sorted(members))
        if count:
            self.incarnation_changes += 1
            _count("bigdl_elastic_incarnation_changes_total",
                   "cluster membership reconfigurations adopted")
        log.warning("elastic: running incarnation %d with %d member(s) %s",
                    self.incarnation, len(self.members), self.members)
        self._repartition_stores()
        self._scalar("Incarnation", self.incarnation)
        self._scalar("ClusterSize", len(self.members))

    def publisher(self):
        """The lazily-built background publisher (one per context);
        staleness is judged against this context's live incarnation."""
        from ..telemetry.publish import BackgroundPublisher

        if self._publisher is None:
            self._publisher = BackgroundPublisher(
                incarnation_of=lambda: self.incarnation or 0)
        return self._publisher

    def publish_telemetry(self, step: int):
        """Publish this host's telemetry payload for the current
        incarnation (no-op without an attached Telemetry).  The
        payload snapshot AND the transport put both run on the
        background publisher — KV I/O never blocks a step; a payload
        queued under an incarnation that has since died is discarded
        instead of published (stale snapshots must not haunt the new
        membership's view)."""
        if self.telemetry is None:
            return
        from ..telemetry.aggregate import publish_snapshot

        tm, transport, host = (self.telemetry,
                               self.coordinator.transport, self.host)
        inc = self.incarnation or 0
        tm.incarnation = inc

        def publish():
            publish_snapshot(transport, host, tm.payload(step),
                             incarnation=inc)

        if not self.publisher().submit(publish, incarnation=inc,
                                       key="tm"):
            publish()  # publisher closed: degrade to synchronous

    def cluster_snapshot(self) -> dict:
        """The leader's merged cluster telemetry view: newest payload
        per CURRENT member for the current incarnation, folded by
        :func:`~bigdl_tpu.telemetry.merge_cluster` (counters sum,
        histogram buckets add, goodput ledgers sum host-seconds)."""
        from ..telemetry.aggregate import collect_snapshots, merge_cluster

        self.publish_telemetry(self._last_step)
        if self._publisher is not None:
            # the reader's barrier: our own freshest payload must be
            # visible before the collect
            self._publisher.drain()
        payloads = collect_snapshots(
            self.coordinator.transport, self.incarnation or 0,
            members=self.members or None)
        return merge_cluster(payloads)

    def close(self):
        """Join the background publisher (flushing queued payloads).
        The context stays usable — publishing after close degrades to
        synchronous puts."""
        if self._publisher is not None:
            self._publisher.close()

    def on_step_start(self, step: int):
        c = self.coordinator
        self._last_step = int(step)
        c.heartbeat(step=step, step_time=self._last_dt)
        if self.telemetry is not None and self.telemetry_cadence > 0 \
                and step % self.telemetry_cadence == 0:
            self.publish_telemetry(step)
        n, members = c.membership()
        if self.incarnation is None:
            c.ack(n)
            self._adopt(n, members, count=False)
        elif n != self.incarnation:
            # someone else reconfigured: fall back to the retry loop,
            # which restores and re-enters through begin_attempt
            self._mark_fault()
            raise MembershipChangedError(
                f"incarnation moved {self.incarnation} -> {n}",
                incarnation=n, members=members)
        beats = c.beats()
        alive = c.alive(beats)
        dead = [m for m in self.members if m != c.host and m not in alive]
        if dead:
            survivors = [m for m in self.members if m not in dead]
            n2 = c.propose(survivors, f"hosts presumed dead: {dead}",
                           expect=n)
            self._mark_fault()
            raise MembershipChangedError(
                f"host(s) {dead} stopped heartbeating — shrinking to "
                f"{survivors}", incarnation=n2, members=survivors)
        # let the incarnation's compile transient settle before judging
        # skew — the first step of a fresh program runs seconds of XLA
        # compilation that would read as the leader straggling
        if self._steps_since_change >= 2:
            self._check_stragglers(beats, alive, n)
        self._steps_since_change += 1
        if self._steps_since_change >= self.regrow_after_steps:
            barred = c.evicted()
            rejoiners = sorted(
                h for h, b in beats.items()
                if h not in self.members and h in alive
                and b.get("rejoin") and h not in barred)
            if rejoiners:
                # an evicted straggler stays barred until something
                # clears its marker (coordinator.readmit — the host
                # itself once it has recovered, or an operator)
                grown = sorted(set(self.members) | set(rejoiners))
                n2 = c.propose(grown, f"rejoin: {rejoiners}", expect=n)
                # regrow is planned, not a fault: no recovery clock
                raise MembershipChangedError(
                    f"host(s) {rejoiners} rejoined — regrowing to {grown}",
                    incarnation=n2, members=grown)

    def _check_stragglers(self, beats: Dict[str, dict], alive: Set[str],
                          n: int):
        if self.straggler is None:
            return
        # only LIVE members are judged for skew: a freshly dead host's
        # frozen last beat is the death path's business, not a
        # straggler vote's
        times = {h: beats[h].get("step_time") for h in self.members
                 if h in beats and h in alive}
        warn = self.straggler.observe(times)
        for h, skew in warn.items():
            log.warning("elastic: straggler %s at %.1fx the cluster "
                        "median step time (threshold %.1fx)", h, skew,
                        self.straggler.skew_threshold)
            self._scalar("StragglerSkew", skew)
        before = self.sync_relax_factor()
        victim = self.straggler.victim(exclude=(self.coordinator.host,))
        if victim is None:
            after = self.sync_relax_factor()
            if after != before:
                log.warning(
                    "elastic: relax-before-evict widened the effective "
                    "sync averaging period x%.1f (round %d/%d) instead "
                    "of voting out the straggler — eviction is the "
                    "last resort", after,
                    self.straggler.relax_rounds,
                    self.straggler.max_relax_rounds)
                self._scalar("SyncRelaxFactor", after)
            return
        c = self.coordinator
        self.straggler.record_eviction(victim)
        self.evictions += 1
        _count("bigdl_elastic_evictions_total",
               "hosts voted out (stragglers + SDC minorities)")
        self.evicted_hosts.append(victim)
        c.evict(victim, "chronic straggler")
        survivors = [m for m in self.members if m != victim]
        n2 = c.propose(survivors, f"evicted straggler {victim}", expect=n)
        self._scalar("Evictions", self.evictions)
        self._mark_fault()
        raise MembershipChangedError(
            f"straggler {victim} voted out — shrinking to {survivors}",
            incarnation=n2, members=survivors)

    def sync_relax_factor(self) -> float:
        """The live relaxed-synchrony period multiplier the driver
        consults every iteration: 1.0 normally; >1 while the straggler
        policy's ``relax_before_evict`` rounds are widening the
        effective ``periodic(k)`` averaging cadence (docs/elastic.md)."""
        s = self.straggler
        return float(getattr(s, "period_factor", 1.0)) \
            if s is not None else 1.0

    def run_step(self, dispatch: Callable, step: int):
        """Run one compiled step under the watchdog.  ``dispatch`` is
        the driver's zero-arg jitted call; the worker blocks on the
        returned loss so a hang between dispatch and the value fetch is
        inside the deadline."""
        host = self.coordinator.host

        def body(cancel):
            from . import faults

            faults.check_elastic_fault(host, step, cancel)
            out = dispatch()
            import jax

            jax.block_until_ready(out[0])
            return out

        t0 = time.monotonic()
        try:
            out = self.watchdog.run(body)
        except HungCollectiveError:
            self._mark_fault()
            self._scalar("WatchdogTrips", self.watchdog.trips)
            raise
        dt = time.monotonic() - t0
        self._last_dt = dt
        self.step_log.append((self.incarnation or 0, int(step),
                              time.monotonic(), dt))
        if self._fault_at is not None:
            rec = time.monotonic() - self._fault_at
            self._fault_at = None
            self.recoveries.append(rec)
            log.warning("elastic: recovered in %.2fs (incarnation %d, "
                        "step %d)", rec, self.incarnation or 0, step)
            self._scalar("RecoverySeconds", rec)
        return out

    # -- cross-host integrity votes (resilience/integrity.py) -----------
    def integrity_vote(self, step: int, checksum: str):
        """One SDC vote round: publish this host's param checksum under
        ``sdc/<step>/<host>``, bounded-wait for the other members',
        and let the strict majority define truth
        (:func:`~bigdl_tpu.resilience.integrity.majority_vote`).

        * a corrupt PEER → evicted + membership proposal without it →
          retryable :class:`MembershipChangedError` (the survivors
          restore the verified checkpoint and shrink — the same path
          a dead host takes, because a silently-wrong host is worse
          than a dead one);
        * a corrupt SELF → retryable
          :class:`~bigdl_tpu.resilience.integrity
          .SilentDataCorruptionError` (restore replaces our bad state
          with known-good bytes);
        * no strict majority → fatal
          :class:`~bigdl_tpu.resilience.integrity.IntegrityError`.
        """
        from .integrity import SilentDataCorruptionError, majority_vote

        c = self.coordinator
        # rounds are keyed by incarnation AND step: a post-restore replay
        # of the same step is a FRESH round — peers' answers from before
        # the membership change must never count against it (the restore
        # legitimately changes the bits: fewer shards, different
        # reduction order)
        prefix = f"{_SDC}{self.incarnation}/{int(step)}/"
        # our own vote publishes through the background publisher too
        # (urgent: this round's bounded wait below is watching for it),
        # so a slow KV transport never stalls the step loop beyond the
        # vote round itself
        vote_key, vote_value = prefix + c.host, str(checksum)
        if not self.publisher().submit(
                lambda: c.transport.put(vote_key, vote_value),
                incarnation=self.incarnation, urgent=True):
            c.transport.put(vote_key, vote_value)
        want = set(self.members) or {c.host}
        t0 = time.monotonic()
        deadline = t0 + self.integrity_timeout
        while True:
            votes = {}
            for key in c.transport.keys(prefix):
                host = key[len(prefix):]
                if host in want:
                    votes[host] = c.transport.get(key)
            if want <= set(votes) or time.monotonic() >= deadline:
                break
            self._sleep(0.005)
        self.sdc_votes += 1
        _count("bigdl_integrity_votes_total",
               "cross-host SDC checksum vote rounds")
        self.vote_log.append((int(step), time.monotonic() - t0))
        self._iscalar("IntegrityVotes", self.sdc_votes, step)
        truth, corrupt = majority_vote(votes, sorted(want))
        if not corrupt:
            return
        self.sdc_disagreements += 1
        _count("bigdl_integrity_disagreements_total",
               "SDC vote rounds that flagged a minority checksum")
        self.sdc_detected_steps.append(int(step))
        self._iscalar("IntegrityDisagreements", self.sdc_disagreements,
                      step)
        log.warning("elastic: integrity vote at step %d flagged %s "
                    "(majority checksum %s, votes %s)", step, corrupt,
                    truth, votes)
        if c.host in corrupt:
            self._mark_fault()
            raise SilentDataCorruptionError(
                f"this host's parameter checksum {votes.get(c.host)} "
                f"was flagged against the {truth} majority at step "
                f"{step} — restoring the last verified checkpoint")
        for h in corrupt:
            c.evict(h, "silent data corruption")
        self.sdc_evictions += len(corrupt)
        self.evictions += len(corrupt)
        _count("bigdl_elastic_evictions_total",
               "hosts voted out (stragglers + SDC minorities)",
               len(corrupt))
        self.evicted_hosts.extend(corrupt)
        survivors = [m for m in self.members if m not in corrupt]
        n2 = c.propose(survivors, f"sdc eviction: {corrupt}",
                       expect=self.incarnation)
        self._iscalar("IntegrityEvictions", self.sdc_evictions, step)
        self._mark_fault()
        raise MembershipChangedError(
            f"host(s) {corrupt} failed the step-{step} integrity vote "
            f"(checksum minority vs {truth}) — shrinking to {survivors}",
            incarnation=n2, members=survivors)

    # -- internals -------------------------------------------------------
    def _iscalar(self, tag: str, value, step: int):
        summary = self.integrity_summary or self.summary
        if summary is not None:
            try:
                summary.add_scalar(tag, float(value), int(step))
            except Exception:
                log.exception("elastic: integrity summary write failed "
                              "for %s", tag)

    def _mark_fault(self):
        if self._fault_at is None:
            self._fault_at = time.monotonic()

    def _scalar(self, tag: str, value):
        if self.summary is not None:
            try:
                self.summary.add_scalar(tag, float(value), self._last_step)
            except Exception:
                log.exception("elastic: summary write failed for %s", tag)


# ---------------------------------------------------------------------------
# simulated cluster member (tests + bench)
# ---------------------------------------------------------------------------

class SimulatedHost:
    """A fake gang member for single-process simulations: pumps
    heartbeats, acks every incarnation that includes it, honors the
    elastic fault injectors (keyed off the *leader's* published step,
    so schedules are deterministic against the training timeline), and
    can die / rejoin / recover its speed on that schedule.

    This is what lets CPU CI drive a 4-"host" cluster through death →
    shrink → rejoin → regrow in one process: the real driver is one
    member; the rest are these.
    """

    def __init__(self, host: str, transport: KVTransport, *,
                 leader: str = "host0", interval: float = 0.02,
                 heartbeat_timeout: float = 2.0,
                 step_time: Optional[float] = None,
                 die_at_leader_step: Optional[int] = None,
                 rejoin_at_leader_step: Optional[int] = None,
                 readmit_at_leader_step: Optional[int] = None):
        self.coordinator = ElasticCoordinator(
            host, transport, heartbeat_timeout=heartbeat_timeout)
        self.host = str(host)
        self.leader = str(leader)
        self.interval = float(interval)
        # step_time=None mirrors the leader's published step time ("the
        # host keeps up with the gang"); a number simulates a fixed-rate
        # host; either is inflated by an armed delay_host fault
        self.step_time = step_time
        self.die_at_leader_step = die_at_leader_step
        self.rejoin_at_leader_step = rejoin_at_leader_step
        # a straggler that got evicted stays barred until it clears its
        # own marker; at this leader step it recovers its speed and
        # readmits itself (regrow picks it up at the next boundary)
        self.readmit_at_leader_step = readmit_at_leader_step
        self.dead = False
        self.deaths = 0
        self._acked = -1
        # every fake member carries its own telemetry bundle (private
        # registry and tracer — fake hosts stand for other processes
        # and must not pollute this one's defaults) and publishes
        # payloads like a real host would, so a single-process
        # simulation exercises the leader's merge path
        from ..telemetry import MetricsRegistry, Telemetry, Tracer

        self.telemetry = Telemetry(registry=MetricsRegistry(),
                                   tracer=Tracer(), host=str(host))
        self._tm_publish_every = 5
        self._tm_last: Optional[float] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"elastic-sim-{host}")

    def start(self):
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0):
        self._stop.set()
        self._thread.join(timeout=timeout)

    # ------------------------------------------------------------------
    def _run(self):
        from . import faults

        c = self.coordinator
        step = 0
        while not self._stop.is_set():
            leader_step = c.leader_step(self.leader)
            if self.dead:
                self._tm_last = None  # dead wall is not productive
                if (self.rejoin_at_leader_step is not None
                        and leader_step >= self.rejoin_at_leader_step):
                    self.dead = False
                    self.die_at_leader_step = None
                self._stop.wait(self.interval)
                continue
            if (self.die_at_leader_step is not None
                    and leader_step >= self.die_at_leader_step):
                self.dead = True
                self.deaths += 1
                continue
            if (self.readmit_at_leader_step is not None
                    and leader_step >= self.readmit_at_leader_step):
                self.step_time = None  # recovered: keep pace again
                self.readmit_at_leader_step = None
                c.readmit(self.host)
            step += 1
            t0 = time.monotonic()
            try:
                faults.check_elastic_fault(self.host, leader_step, None)
            except faults.HostKilledError:
                self.dead = True
                self.deaths += 1
                continue
            except HungCollectiveError:
                pass  # an uncanceled hang just delayed this fake host
            fault_dt = time.monotonic() - t0
            base = self.step_time
            if base is None:
                # keep pace with the leader's published step time, so a
                # healthy fake host never reads as a straggler relative
                # to the one member doing real compute
                raw = c.transport.get(_HB + self.leader)
                try:
                    base = json.loads(raw).get("step_time") if raw else None
                except ValueError:
                    base = None
                base = base or self.interval
            dt = max(float(base), fault_dt)
            n, members = c.membership()
            member = self.host in members
            c.heartbeat(step=step, step_time=dt, rejoin=not member)
            if member and n > self._acked:
                c.ack(n)
                self._acked = n
            if member:
                self._answer_integrity_votes(leader_step)
                self._pump_telemetry(n, step, dt)
            self._stop.wait(self.interval)

    def _pump_telemetry(self, incarnation: int, step: int, dt: float):
        """Keep the fake host's telemetry honest and published: its
        published step time feeds the step histogram (the skew view),
        while the goodput ledger is attributed real elapsed wall — a
        fake host is 'keeping pace', so its wall is productive."""
        from ..telemetry.aggregate import publish_snapshot

        tm = self.telemetry
        tm.ledger.start()
        now = time.monotonic()
        if self._tm_last is not None:
            tm.ledger.add("productive", now - self._tm_last)
        self._tm_last = now
        tm.steps.inc()
        tm.step_seconds.observe(dt)
        if step % self._tm_publish_every == 0:
            tm.incarnation = incarnation
            publish_snapshot(self.coordinator.transport, self.host,
                             tm.payload(step), incarnation=incarnation)

    def _answer_integrity_votes(self, leader_step: int):
        """Echo the leader's published integrity checksum for any open
        vote round this host has not answered — in real synchronous
        SPMD every healthy host computes the bit-identical post-gather
        parameters, so "agrees with the leader" is the faithful
        simulation of a healthy host.  An armed ``corrupt_gradient`` /
        ``flip_param_bits`` fault perturbs the answer instead,
        simulating the silently-corrupting host the vote must flag."""
        from . import faults

        t = self.coordinator.transport
        for key in t.keys(_SDC):
            parts = key[len(_SDC):].split("/")  # <inc>/<step>/<host>
            if len(parts) != 3 or parts[2] != self.leader:
                continue
            inc_s, step_s, _ = parts
            if not step_s.isdigit():
                continue
            mine = f"{_SDC}{inc_s}/{step_s}/{self.host}"
            if t.get(mine) is not None:
                continue
            value = t.get(key)
            if value is None:
                continue
            t.put(mine, faults.corrupt_checksum(self.host, int(step_s),
                                                value))
