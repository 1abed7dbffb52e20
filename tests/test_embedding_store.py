"""Parameter-server-scale embedding store specs (ISSUE 18).

The contract under test, end to end:

* **Consistent ownership** — rendezvous-hashed block assignment agrees
  across hosts and a 1-host membership delta moves ~1/N of the rows
  (never a full reshuffle).
* **Lazy capacity** — a 1e7-row table costs memory proportional to its
  touched hot set, not its vocabulary.
* **Verified migration** — shrink/regrow moves rows as crc32c-sealed
  shards; a corrupted shard is detected on import and re-requested
  from the owner's checkpointed leg; the table is bitwise identical
  across the membership boundary (``table_checksum`` proof).
* **Chaos e2e** — a training loop survives a host death mid-repartition
  PLUS a corrupted migration shard: loss keeps descending, the final
  table is bitwise equal to a fault-free control run, and a serving
  fetch hammering throughout serves ``bad_rows_served == 0``.
"""
import os
import threading

import numpy as np
import pytest

from bigdl_tpu.nn import (EmbeddingStore, HotRowCache, MigrationCorrupt,
                          ShardedEmbedding, StoreMigrating, table_checksum)
from bigdl_tpu.nn.embedding_store import assign_blocks, block_owner
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.elastic import (ElasticContext,
                                          ElasticCoordinator, InMemoryKV)
from bigdl_tpu.resilience.faults import HostKilledError
from bigdl_tpu.serving import SparseFetchClient, Status

TABLE = "ads_emb"
HOSTS = ["host-0", "host-1", "host-2"]


def _cluster(tmp_path, hosts=HOSTS, n_rows=512, dim=8, block_rows=32,
             seed=7):
    kv = InMemoryKV()
    stores = {h: EmbeddingStore(TABLE, n_rows, dim, h, hosts, kv=kv,
                                block_rows=block_rows, seed=seed,
                                checkpoint_dir=str(tmp_path))
              for h in hosts}
    return kv, stores


def _route(stores, row):
    """Any live leg's view of who owns ``row`` (they all agree)."""
    return next(iter(stores.values())).owner_of_row(row)


def _train(stores, rng, target, n_steps, batch=32, lr=4.0):
    """PS-style sparse SGD on loss = |emb[rows] - target[rows]|^2.

    Row deltas are elementwise per row, so the final table bytes are
    independent of how rows group over legs — which is exactly what
    lets the chaos run (different membership mid-stream) be compared
    bitwise against the static control run.
    """
    losses = []
    n_rows = next(iter(stores.values())).n_rows
    for _ in range(n_steps):
        rows = rng.randint(0, n_rows, size=batch)
        by_owner = {}
        for r in rows:
            by_owner.setdefault(_route(stores, int(r)), []).append(int(r))
        loss = 0.0
        for owner, rs in by_owner.items():
            leg = stores[owner]
            vals, _version = leg.read_rows(rs)
            err = vals - target[rs]
            loss += float((err ** 2).sum())
            leg.apply_updates(rs, -lr * 2.0 * err / batch)
        losses.append(loss / (batch * target.shape[1]))
    return losses


# ---------------------------------------------------------------------------
# consistent ownership
# ---------------------------------------------------------------------------

def test_ownership_agrees_across_hosts_and_is_total():
    n_blocks = 120
    maps = [assign_blocks(TABLE, n_blocks, perm)
            for perm in (HOSTS, list(reversed(HOSTS)))]
    assert maps[0] == maps[1]            # member-list order irrelevant
    assert set(maps[0]) == set(range(n_blocks))
    assert set(maps[0].values()) <= set(HOSTS)
    for b in (0, 57, n_blocks - 1):
        assert maps[0][b] == block_owner(TABLE, b, HOSTS)


def test_one_host_delta_moves_about_one_nth():
    """The acceptance bar: a 1-host shrink moves <= 1.5/N of the
    blocks, and ONLY the departed host's blocks move; a 1-host regrow
    steals <= 1.5/(N+1) and only to the joiner."""
    n_blocks = 120
    full = assign_blocks(TABLE, n_blocks, HOSTS)
    survivors = assign_blocks(TABLE, n_blocks, HOSTS[:-1])
    moved = [b for b in range(n_blocks) if full[b] != survivors[b]]
    assert all(full[b] == HOSTS[-1] for b in moved)
    assert len(moved) / n_blocks <= 1.5 / len(HOSTS)
    assert moved                          # the dead host owned SOMETHING

    grown = assign_blocks(TABLE, n_blocks, HOSTS + ["host-3"])
    stolen = [b for b in range(n_blocks) if full[b] != grown[b]]
    assert all(grown[b] == "host-3" for b in stolen)
    assert len(stolen) / n_blocks <= 1.5 / (len(HOSTS) + 1)


def test_lazy_blocks_give_1e7_row_capacity(tmp_path):
    """10M rows construct instantly and cost only the touched blocks —
    the 1e8-capable-by-construction property, exercised at 1e7."""
    store = EmbeddingStore(TABLE, 10_000_000, 16, HOSTS[0], HOSTS,
                           block_rows=4096, seed=3,
                           checkpoint_dir=str(tmp_path))
    assert store.n_blocks == -(-10_000_000 // 4096)
    mine = [r for r in range(0, 10_000_000, 999_983)
            if store.owns_row(r)][:3]
    assert mine
    vals, version = store.read_rows(mine)
    assert vals.shape == (len(mine), 16) and version == 0
    store.apply_updates(mine[:1], np.ones((1, 16), np.float32))
    snap = store.snapshot()
    assert snap["materialized_blocks"] <= len(mine)
    assert snap["owned_blocks"] > store.n_blocks // 4
    # untouched blocks re-derive identical bytes on every leg
    other = EmbeddingStore(TABLE, 10_000_000, 16, HOSTS[1], HOSTS,
                           block_rows=4096, seed=3)
    np.testing.assert_array_equal(store._init_block(5),
                                  other._init_block(5))


# ---------------------------------------------------------------------------
# verified migration
# ---------------------------------------------------------------------------

def test_clean_shrink_is_bitwise_identical(tmp_path):
    kv, stores = _cluster(tmp_path)
    rng = np.random.RandomState(0)
    target = rng.standard_normal((512, 8)).astype(np.float32)
    _train(stores, rng, target, n_steps=6)
    for s in stores.values():
        s.checkpoint()
    before = table_checksum(list(stores.values()))

    survivors = {h: stores[h] for h in HOSTS[:-1]}
    dead = HOSTS[-1]
    for leg in survivors.values():
        stats = leg.repartition(HOSTS[:-1], dead=[dead])
        assert stats["version"] == 1
        assert stats["exported_blocks"] == 0   # HRW: survivors keep theirs
    assert table_checksum(list(survivors.values())) == before
    moved = sum(len(s.owned_blocks()) for s in survivors.values())
    assert moved == next(iter(survivors.values())).n_blocks
    # every import came off the dead host's checkpointed leg
    assert all(s.recovered_from_checkpoint == len(
        [b for b in s.owned_blocks()
         if assign_blocks(TABLE, s.n_blocks, HOSTS)[b] == dead])
        for s in survivors.values())


@pytest.mark.parametrize("n_rows,block_rows", [(512, 32), (8192, 256)])
def test_regrow_corrupt_shard_recovers_from_checkpointed_leg(
        tmp_path, n_rows, block_rows):
    kv, stores = _cluster(tmp_path, n_rows=n_rows, block_rows=block_rows)
    rng = np.random.RandomState(1)
    target = rng.standard_normal((n_rows, 8)).astype(np.float32)
    _train(stores, rng, target, n_steps=6)
    for s in stores.values():
        s.checkpoint()
    before = table_checksum(list(stores.values()))

    joiner = EmbeddingStore(TABLE, n_rows, 8, "host-3", HOSTS, kv=kv,
                            block_rows=block_rows, seed=7,
                            checkpoint_dir=str(tmp_path))
    grown = HOSTS + ["host-3"]
    with faults.corrupt_migration_shard(TABLE, times=1) as f:
        for h in HOSTS:                      # exporters seal first...
            stores[h].repartition(grown)
        stats = joiner.repartition(grown)    # ...the joiner imports
        assert f["fired"] == 1
    assert stats["imported_blocks"] > 0
    assert joiner.migration_corrupt_detected >= 1
    assert joiner.recovered_from_checkpoint >= 1
    legs = list(stores.values()) + [joiner]
    assert table_checksum(legs) == before
    assert all(s.version == 1 and s.members == tuple(sorted(grown))
               for s in legs)
    client = SparseFetchClient({s.host: s for s in legs})   # Zipf reads
    for _ in range(60):
        assert client.fetch([int(r) for r in np.minimum(
            rng.zipf(1.3, size=32) - 1, n_rows - 1)]).ok
    snap = client.health_snapshot()
    assert snap["bad_rows_served"] == 0 and snap["rows_served"] > 0


def test_corrupt_shard_without_checkpoint_leg_raises_typed():
    """No silent zero-fill: corruption with no verified fallback is a
    loud, typed DATA_LOSS stop."""
    kv = InMemoryKV()
    stores = {h: EmbeddingStore(TABLE, 512, 8, h, HOSTS, kv=kv,
                                block_rows=32, seed=7)  # no ckpt dir
              for h in HOSTS}
    joiner = EmbeddingStore(TABLE, 512, 8, "host-3", HOSTS, kv=kv,
                            block_rows=32, seed=7)
    grown = HOSTS + ["host-3"]
    with faults.corrupt_migration_shard(TABLE, times=1):
        for h in HOSTS:
            stores[h].repartition(grown)
        with pytest.raises(MigrationCorrupt) as ei:
            joiner.repartition(grown)
    assert ei.value.code == "DATA_LOSS"
    assert ei.value.table == TABLE and ei.value.block >= 0


def test_reads_shed_typed_while_migrating(tmp_path):
    _kv, stores = _cluster(tmp_path)
    leg = stores[HOSTS[0]]
    leg._migrating = True
    with pytest.raises(StoreMigrating) as ei:
        leg.read_rows(leg.owned_blocks()[:1])
    assert ei.value.code == "UNAVAILABLE"
    with pytest.raises(StoreMigrating):
        leg.apply_updates([0], np.zeros((1, 8), np.float32))
    leg._migrating = False


# ---------------------------------------------------------------------------
# the chaos e2e
# ---------------------------------------------------------------------------

def test_chaos_death_plus_corruption_bitwise_equal_and_loss_descends(
        tmp_path):
    """The acceptance bar in one run: host-2 dies INSIDE its
    repartition (between ownership re-derivation and import-ack) while
    host-3 is joining, AND one migration shard is corrupted in flight.
    Survivors re-derive 3 -> 3 (swap host-2 for host-3), source the
    dead leg from its checkpoints and the torn shard from its owner's
    checkpointed leg, training resumes on the exact next batch, loss
    keeps descending, the final table is bitwise equal to a fault-free
    control run, and a serving client hammering throughout never
    serves a retired row.
    """
    rng_c = np.random.RandomState(42)
    target = rng_c.standard_normal((512, 8)).astype(np.float32)

    # -- control: static membership, no faults, same update stream ----
    _kvc, control = _cluster(tmp_path / "control")
    losses_c = _train(control, np.random.RandomState(9), target, 30)
    want = table_checksum(list(control.values()))

    # -- chaos run ----------------------------------------------------
    kv, stores = _cluster(tmp_path / "chaos")
    rng = np.random.RandomState(9)           # identical update stream
    losses = _train(stores, rng, target, 12)

    fetch_stop = threading.Event()
    client = SparseFetchClient(dict(stores), default_deadline_s=0.05,
                               retry_backoff_s=0.001)

    def hammer():
        zipf = np.random.RandomState(5)
        while not fetch_stop.is_set():
            rows = np.minimum(zipf.zipf(1.5, size=8) - 1, 511)
            client.fetch([int(r) for r in rows])

    t = threading.Thread(target=hammer)
    t.start()
    try:
        # the repartition-barrier checkpoint every leg writes before a
        # planned membership change (docs/embeddings.md)
        for s in stores.values():
            s.checkpoint()

        joiner = EmbeddingStore(TABLE, 512, 8, "host-3", HOSTS, kv=kv,
                                block_rows=32, seed=7,
                                checkpoint_dir=str(tmp_path / "chaos"))
        grown = sorted(HOSTS + ["host-3"])
        with faults.kill_host_mid_repartition("host-2") as kill:
            with pytest.raises(HostKilledError):
                stores["host-2"].repartition(grown)
        assert kill["fired"] == 1

        # survivors re-derive WITHOUT the dead host; the corrupt shard
        # lands on one of their live exports to the joiner
        final_members = sorted(["host-0", "host-1", "host-3"])
        with faults.corrupt_migration_shard(TABLE, times=1) as f:
            for h in ("host-0", "host-1"):
                stores[h].repartition(final_members, dead=["host-2"])
            jstats = joiner.repartition(final_members, dead=["host-2"])
            assert f["fired"] == 1
        assert jstats["imported_blocks"] > 0
        assert joiner.migration_corrupt_detected >= 1

        live = {"host-0": stores["host-0"], "host-1": stores["host-1"],
                "host-3": joiner}
        # resume on the exact next batch of the SAME stream
        losses += _train(live, rng, target, 18)
    finally:
        fetch_stop.set()
        t.join(timeout=30)
        assert not t.is_alive()

    assert table_checksum(list(live.values())) == want
    assert losses[-1] < losses[0]
    assert min(losses[-5:]) < min(losses_c[:5])
    np.testing.assert_allclose(losses[:12], losses_c[:12], rtol=1e-5)
    # the serving audit: sheds are typed and allowed, bad rows are not
    snap = client.health_snapshot()
    assert snap["bad_rows_served"] == 0
    assert client.rows_served > 0


# ---------------------------------------------------------------------------
# serving: sparse fetch
# ---------------------------------------------------------------------------

def test_sparse_fetch_zipf_cache_hit_rate(tmp_path):
    _kv, stores = _cluster(tmp_path)
    client = SparseFetchClient(dict(stores), cache_capacity=256)
    zipf = np.random.RandomState(3)
    for _ in range(60):
        rows = np.minimum(zipf.zipf(1.5, size=16) - 1, 511)
        res = client.fetch([int(r) for r in rows])
        assert res.ok
    snap = client.health_snapshot()
    assert snap["cache"]["hit_rate"] > 0.4     # Zipf skew pays
    assert snap["bad_rows_served"] == 0
    assert snap["table_version"] == 0


def test_sparse_fetch_sheds_typed_on_migrating_leg(tmp_path):
    """Uncached rows on a mid-repartition leg shed DEADLINE_EXCEEDED /
    UNAVAILABLE within the budget — never a late or unverified row."""
    _kv, stores = _cluster(tmp_path)
    now = [0.0]
    client = SparseFetchClient(
        dict(stores), default_deadline_s=0.05, retry_backoff_s=0.01,
        breaker_kw={"failure_threshold": 5, "reset_timeout": 0.25,
                    "clock": lambda: now[0]},
        clock=lambda: now[0],
        sleep=lambda s: now.__setitem__(0, now[0] + s))
    leg = stores[HOSTS[0]]
    rows = [r * leg.block_rows for r in range(leg.n_blocks)
            if leg.owns_row(r * leg.block_rows)][:4]
    leg._migrating = True
    try:
        res = client.fetch(rows)
        assert res.status in (Status.DEADLINE_EXCEEDED,
                              Status.UNAVAILABLE)
        assert set(res.shed_rows) == set(rows)
        assert client.rows_shed == len(rows)
        assert client.retries > 0
    finally:
        leg._migrating = False
    assert client.bad_rows_served == 0
    now[0] += 10.0                 # past reset_timeout: half-open probe
    res = client.fetch(rows)
    assert res.ok and res.version == 0


def test_sparse_fetch_version_bump_retires_cache(tmp_path):
    _kv, stores = _cluster(tmp_path)
    client = SparseFetchClient(dict(stores))
    rows = [0, 1, 2, 3]
    assert client.fetch(rows).ok
    assert client.fetch(rows).cache_hits == len(rows)
    for s in stores.values():                  # a repartition's bump
        s.version += 1
    res = client.fetch(rows)
    assert res.ok and res.cache_hits == 0      # all retired, re-read
    assert res.version == 1
    assert client.cache.snapshot()["version"] == 1
    assert client.bad_rows_served == 0


# ---------------------------------------------------------------------------
# integration: ElasticContext + ShardedEmbedding bridge
# ---------------------------------------------------------------------------

def test_elastic_context_repartitions_attached_stores(tmp_path):
    kv, stores = _cluster(tmp_path)
    rng = np.random.RandomState(2)
    target = rng.standard_normal((512, 8)).astype(np.float32)
    _train(stores, rng, target, n_steps=4)
    for s in stores.values():
        s.checkpoint()
    before = table_checksum(list(stores.values()))

    ctxs = {}
    for h in HOSTS:
        coord = ElasticCoordinator(h, kv, heartbeat_timeout=100.0)
        coord.bootstrap(HOSTS)
        ctx = ElasticContext(coord)
        ctx.attach_embedding_store(stores[h])
        ctxs[h] = ctx
        ctx.begin_attempt()                    # bootstrap adopt: no move
    assert all(s.version == 0 for s in stores.values())

    survivors = HOSTS[:-1]
    ctxs[HOSTS[0]].coordinator.propose(survivors, "host-2 died",
                                       expect=0)
    for h in survivors:                        # both acked: rendezvous
        ctxs[h].coordinator.ack(1)             # passes single-threaded
    for h in survivors:
        ctxs[h].begin_attempt()
    legs = [stores[h] for h in survivors]
    assert all(s.version == 1 and s.members == tuple(sorted(survivors))
               for s in legs)
    assert table_checksum(legs) == before
    # the store inherited the coordinator's transport
    assert stores[HOSTS[0]].kv is kv


def test_sharded_embedding_store_bridge(tmp_path):
    _kv, stores = _cluster(tmp_path)
    leg = stores[HOSTS[0]]
    with pytest.raises(ValueError):
        ShardedEmbedding(100, 8, axis_name=None).attach_store(leg)
    emb = ShardedEmbedding(512, 8, axis_name=None).attach_store(leg)
    emb.refresh_from_store()
    np.testing.assert_array_equal(np.asarray(emb.params["weight"]),
                                  leg.dense())

    mine = [r for r in range(512) if leg.owns_row(r)][:3]
    theirs = [r for r in range(512) if not leg.owns_row(r)][:2]
    before, _ = leg.read_rows(mine)
    n = emb.flush_to_store(mine + theirs,
                           np.ones((len(mine) + len(theirs), 8),
                                   np.float32), lr=0.5)
    assert n == len(mine)                      # peers' rows not applied
    after, _ = leg.read_rows(mine)
    np.testing.assert_allclose(after, before - 0.5, atol=1e-6)

    unbacked = ShardedEmbedding(512, 8, axis_name=None)
    with pytest.raises(ValueError):
        unbacked.refresh_from_store()
