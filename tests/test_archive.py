"""Archive subsystem: sealed-segment log archival + in-memory truncation
(splice-cursor transparency for recovery, analysis and shipping), fuzzy
logical snapshots, point-in-time restore, standby re-seeding
(SnapshotRequired / auto-reseed / promote survivors), and ranged replica
scans with min-over-spanned-shards staleness tokens.

Every test that builds an archive runs twice — once on ``MemoryBackend``
(the PR-3 in-process semantics, unchanged) and once on
``DirectoryBackend`` (encoded blobs on disk) — via the ``make_backend``
fixture: the media layer's contract is that the backend choice is
invisible to everything above it."""
import itertools
import random

import pytest

from repro.archive import (Archiver, LogArchive, SnapshotRequired,
                           SnapshotStore)
from repro.core import (Database, LogManager, Strategy, TruncatedLogError,
                        committed_state_oracle, make_key, recover)
from repro.core.records import BeginCkptRec
from repro.media import DirectoryBackend, MemoryBackend
from repro.replication import (LogShipper, Replica, ReplicaSet,
                               ShardedApplier, range_partitioner)

from repl_workload import drive, make_primary

N_ROWS, VAL = 400, 24


def _mix(rng, db, n_txns):
    drive(db, rng, n_txns, n_rows=N_ROWS, val=VAL)


@pytest.fixture(params=["memory", "directory"])
def make_backend(request, tmp_path):
    """Factory for fresh backends of the parametrized kind (a test may
    need several — e.g. one per LSN space after a failover)."""
    if request.param == "memory":
        return MemoryBackend
    counter = itertools.count()
    return lambda: DirectoryBackend(tmp_path / f"backend{next(counter)}")


@pytest.fixture
def primary():
    rng = random.Random(1234)
    db, rows, base = make_primary(rng, n_rows=N_ROWS, val=VAL,
                                  page_size=4096)
    _mix(rng, db, 60)
    return rng, db, rows, base


# ------------------------------------------------------------ archive/splice
def test_seal_truncate_and_splice(primary, make_backend):
    rng, db, rows, base = primary
    full = [r.lsn for r in db.log.scan(1)]
    arch = LogArchive(segment_records=64, backend=make_backend())
    db.log.attach_archive(arch)
    sealed = arch.seal(db.log)
    assert sealed == db.log.stable_lsn
    dropped = db.log.truncate(db.log.stable_lsn)
    assert dropped == sealed
    assert db.log.in_memory_records == db.log.end_lsn - db.log.stable_lsn
    # the splice yields the identical dense sequence
    assert [r.lsn for r in db.log.scan(1)] == full
    # record() reaches into segments transparently
    assert db.log.record(1).lsn == 1
    assert db.log.record(sealed).lsn == sealed
    # appends continue in the same LSN space; incremental seal resumes
    _mix(rng, db, 10)
    assert [r.lsn for r in db.log.scan(1)] == \
        list(range(1, db.log.stable_lsn + 1))
    arch.seal(db.log)
    assert arch.archived_upto == db.log.stable_lsn


def test_truncate_guards(primary, make_backend):
    _, db, _, _ = primary
    with pytest.raises(ValueError, match="no archive"):
        db.log.truncate(10)
    arch = LogArchive(backend=make_backend())
    db.log.attach_archive(arch)
    arch.seal(db.log, upto=20)
    with pytest.raises(ValueError, match="sealed only through"):
        db.log.truncate(30)
    assert db.log.truncate(20) == 20
    assert db.log.truncate(20) == 0          # idempotent


def test_discard_guards(primary, make_backend):
    """Discard deletes below the last checkpoint's bCkpt record only, with
    no transaction active and no archive attached; recovery from the cut
    log gives the committed state, and reads below the cut are loud."""
    rng, db, _, base = primary
    db.checkpoint()
    _mix(rng, db, 20)
    bckpt = db.checkpoint()
    _mix(rng, db, 10)
    want = committed_state_oracle(db, base)
    for upto in (bckpt, bckpt + 1, db.log.stable_lsn):
        with pytest.raises(ValueError, match="bCkpt"):
            db.tc.discard_log(upto)
    txn = db.tc.begin()
    db.tc.update(txn, "t", b"k00001", b"INFLIGHT")
    with pytest.raises(ValueError, match="active"):
        db.tc.discard_log(bckpt - 1)
    db.tc.abort(txn)
    assert db.log.retained_lsn == 1
    assert db.tc.discard_log(bckpt - 1) == bckpt - 1
    assert db.tc.discard_log(bckpt - 1) == 0         # idempotent
    assert db.log.retained_lsn == bckpt
    with pytest.raises(TruncatedLogError):
        db.log.record(bckpt - 1)
    with pytest.raises(TruncatedLogError):
        list(db.log.scan(1))
    image = db.crash()
    for strategy in (Strategy.LOG1, Strategy.SQL1):
        rec_db, stats = recover(image, strategy, page_size=4096)
        assert stats.scan_from == bckpt
        assert dict(rec_db.scan_all()) == want
    db.log.attach_archive(LogArchive(backend=make_backend()))
    with pytest.raises(ValueError, match="archive is attached"):
        db.log.discard(bckpt - 1)


def test_discard_refuses_the_unstable_tail():
    log = LogManager()
    for _ in range(10):
        log.append(BeginCkptRec())
    log.flush(upto=5)
    log.set_master(bckpt=9, end_ckpt=10)
    with pytest.raises(ValueError, match="stable only through LSN 5"):
        log.discard(7)
    assert log.discard(5) == 5 and log.retained_lsn == 6


def test_prune_loses_history_loudly(primary, make_backend):
    _, db, _, _ = primary
    arch = LogArchive(segment_records=16, backend=make_backend())
    db.log.attach_archive(arch)
    arch.seal(db.log, upto=50)
    db.log.truncate(50)
    arch.prune(30)
    assert db.log.retained_lsn == arch.retained_from > 1
    with pytest.raises(TruncatedLogError):
        list(db.log.scan(1))
    with pytest.raises(TruncatedLogError):
        db.log.record(1)
    # scans above the prune floor still splice fine
    assert [r.lsn for r in db.log.scan(db.log.retained_lsn)] == \
        list(range(db.log.retained_lsn, db.log.stable_lsn + 1))


def test_recovery_starts_below_truncation(primary, make_backend):
    """Crash after truncation: analysis/redo start at the checkpoint,
    which lives in the archive — recovery must be oblivious."""
    rng, db, rows, base = primary
    db.checkpoint()
    _mix(rng, db, 40)
    arch = LogArchive(segment_records=32, backend=make_backend())
    db.log.attach_archive(arch)
    arch.seal(db.log)
    db.log.truncate(db.log.stable_lsn)       # checkpoint now below the base
    _mix(rng, db, 25)
    loser = db.tc.begin()
    db.tc.update(loser, "t", b"k00001", b"LOSER")
    db.log.flush()
    image = db.crash()
    assert image.log.master.bckpt_lsn <= image.log._base
    for strategy in (Strategy.LOG1, Strategy.LOG2):
        rec_db, stats = recover(image, strategy, page_size=4096)
        assert dict(rec_db.scan_all()) == committed_state_oracle(image, base)
        assert stats.scan_from <= image.log._base


def test_shipping_through_splice(primary, make_backend):
    """A subscriber below the truncation base (but above the prune floor)
    is served from archive segments — truncation is invisible to it."""
    rng, db, rows, base = primary
    arch = LogArchive(segment_records=50, backend=make_backend())
    db.log.attach_archive(arch)
    arch.seal(db.log)
    db.log.truncate(db.log.stable_lsn)
    replica = Replica("r1", page_size=8192, cache_pages=256,
                      seed_tables={"t": rows})
    rs = ReplicaSet(db, [replica])           # subscribes from LSN 1
    _mix(rng, db, 20)
    rs.sync()
    assert replica.user_state() == committed_state_oracle(db.crash(), base)


# ------------------------------------------------------------------ snapshot
def test_fuzzy_snapshot_restore_is_oracle_exact(primary):
    rng, db, rows, base = primary
    store = SnapshotStore()
    snap = store.take(db, chunk_keys=32,
                      on_chunk=lambda: _mix(rng, db, 2))
    assert snap.chunks > 1                   # genuinely chunked
    assert snap.end_lsn > snap.begin_lsn     # writers ran inside the window
    _mix(rng, db, 30)
    target = db.log.stable_lsn
    restored, stats = store.restore(target, db, page_size=16384)
    assert dict(restored.scan_all()) == \
        committed_state_oracle(db.crash(), base, upto_lsn=target)
    assert stats.snapshot_id == snap.snapshot_id
    assert stats.redo_from == snap.redo_lsn
    # restored database is writable and keeps working
    restored.run_txn([("insert", "t", b"post-restore", b"v")])
    assert restored.dc.read("t", b"post-restore") == b"v"


def test_snapshot_excludes_inflight_work(primary):
    """Open transactions at scan time contribute their committed
    before-images, not their in-flight values; in-flight inserts are
    absent, in-flight deletes present."""
    rng, db, rows, base = primary
    from repro.core import split_key
    committed = committed_state_oracle(db.crash(), base)
    k_upd, k_del = sorted(committed)[0], sorted(committed)[1]
    txn = db.tc.begin()
    db.tc.update(txn, *split_key(k_upd), b"UNCOMMITTED")
    db.tc.insert(txn, "t", b"zz-new", b"PHANTOM")
    db.tc.delete(txn, *split_key(k_del))
    store = SnapshotStore()
    snap = store.take(db, chunk_keys=64)
    rows_d = dict(snap.rows)
    assert rows_d[k_upd] == committed[k_upd]
    assert make_key("t", b"zz-new") not in rows_d
    assert rows_d[k_del] == committed[k_del]
    db.tc.abort(txn)
    # a long-running transaction straddling the begin point sets redo_lsn
    # below the window
    txn2 = db.tc.begin()
    db.tc.update(txn2, "t", rows[0][0], b"STRADDLER")
    snap2 = store.take(db)
    assert snap2.redo_lsn < snap2.begin_lsn
    db.tc.commit(txn2)
    target = db.log.stable_lsn
    restored, stats = store.restore(target, db)
    assert stats.snapshot_id == snap2.snapshot_id
    assert dict(restored.scan_all()) == \
        committed_state_oracle(db.crash(), base, upto_lsn=target)


def test_restore_targets_before_and_between_snapshots(primary):
    rng, db, rows, base = primary
    store = SnapshotStore()
    marks = []
    for _ in range(3):
        store.take(db, chunk_keys=64, on_chunk=lambda: _mix(rng, db, 1))
        _mix(rng, db, 25)
        marks.append(db.log.stable_lsn)
    image = db.crash()
    for target in (marks[0], marks[1] - 3, marks[2]):
        restored, _ = store.restore(target, image)
        assert dict(restored.scan_all()) == \
            committed_state_oracle(image, base, upto_lsn=target)
    # before the first snapshot window closes: full replay over base_rows
    early = store.snapshots[0].begin_lsn - 2
    restored, stats = store.restore(early, image, base_rows=base)
    assert stats.snapshot_id is None
    assert dict(restored.scan_all()) == \
        committed_state_oracle(image, base, upto_lsn=early)


def test_restore_from_archive_alone(primary, make_backend):
    """Dead-primary story: sealed segments + snapshots restore with no
    live log at all."""
    rng, db, rows, base = primary
    store = SnapshotStore()
    arch = Archiver(db, archive=LogArchive(backend=make_backend()),
                    snapshots=store)
    store.take(db, chunk_keys=64, on_chunk=lambda: _mix(rng, db, 2))
    _mix(rng, db, 20)
    arch.run_once()                          # seal through stable
    target = arch.archive.archived_upto
    oracle = committed_state_oracle(db.crash(), base, upto_lsn=target)
    restored, _ = store.restore(target)      # no source: archive only
    assert dict(restored.scan_all()) == oracle
    with pytest.raises(ValueError, match="archive alone"):
        store.restore(target + 1)


def test_restore_rejects_unstable_target(primary):
    rng, db, rows, base = primary
    store = SnapshotStore()
    store.take(db)
    txn = db.tc.begin()
    db.tc.update(txn, "t", rows[0][0], b"TAIL")     # unforced tail
    with pytest.raises(ValueError, match="stable"):
        store.restore(db.log.end_lsn, db)


# ------------------------------------------------- truncation watermark/bound
def test_archiver_watermark_and_bounded_memory(primary, make_backend):
    """min(snapshot horizon, slowest subscriber): the live record count
    stays bounded by the snapshot cadence instead of growing with
    history."""
    rng, db, rows, base = primary
    store = SnapshotStore()
    rs = ReplicaSet(db, snapshots=store)
    arch = Archiver(db, archive=LogArchive(backend=make_backend()),
                    snapshots=store, shippers=[rs.shipper])
    assert arch.watermark() == 0             # no snapshot yet: all hot
    store.take(db)
    replica = store.restore_replica("r1", page_size=8192, cache_pages=256)
    rs.add_replica(replica)

    peaks = []
    for _ in range(6):
        _mix(rng, db, 40)
        rs.sync()                            # subscriber keeps up
        store.take(db)
        out = arch.run_once()
        peaks.append(db.log.in_memory_records)
        assert db.log.retained_lsn == 1      # nothing pruned
    assert replica.user_state() == committed_state_oracle(db.crash(), base)
    # memory is bounded by the inter-snapshot distance, not total history
    assert max(peaks) < db.log.end_lsn / 2
    assert db.log._base > 0
    # slowest-subscriber bound: a lagging cursor pins the tail in memory
    lag_cursor = db.log._base + 5
    rs.shipper.subscribe("laggard", lag_cursor)
    _mix(rng, db, 20)
    store.take(db)
    arch.run_once()
    assert db.log._base < lag_cursor         # never truncated past it


# ------------------------------------------- SnapshotRequired / auto-reseed
def _pruned_set(rng, db, make_backend):
    store = SnapshotStore()
    rs = ReplicaSet(db, snapshots=store)
    arch = Archiver(db, archive=LogArchive(segment_records=16,
                                           backend=make_backend()),
                    snapshots=store, shippers=[rs.shipper])
    store.take(db)
    _mix(rng, db, 40)
    store.take(db)
    arch.run_once()
    arch.prune(keep_snapshots=1)
    assert db.log.retained_lsn > 1
    return store, rs, arch


def test_subscribe_below_horizon_raises(primary, make_backend):
    rng, db, rows, base = primary
    store, rs, arch = _pruned_set(rng, db, make_backend)
    with pytest.raises(SnapshotRequired) as exc:
        rs.shipper.subscribe("stale", 1)
    assert exc.value.requested_lsn == 1
    assert exc.value.retained_lsn == db.log.retained_lsn
    assert "re-seed" in str(exc.value)
    # a cursor pruned underneath a stalled subscriber surfaces it at poll:
    # shipper2 is NOT registered with the archiver, so retention advances
    # past its cursor (register it to get the slowest-subscriber bound)
    shipper2 = LogShipper(db.log)
    shipper2.subscribe("ok", db.log.retained_lsn)
    _mix(rng, db, 30)                        # the world moves on ...
    store.take(db)
    arch.run_once()
    arch.prune(keep_snapshots=1)             # ... and prunes past it
    assert db.log.retained_lsn > shipper2.cursors["ok"]
    with pytest.raises(SnapshotRequired):
        shipper2.poll("ok")


def test_add_replica_below_horizon_autoreseeds(primary, make_backend):
    rng, db, rows, base = primary
    store, rs, arch = _pruned_set(rng, db, make_backend)
    stale = Replica("stale", page_size=2048, cache_pages=256)
    assert stale.resume_lsn == 1             # fresh standby: below horizon
    rs.add_replica(stale)                    # SnapshotRequired -> reseed
    assert rs.reseeds == 1
    rs.sync()
    assert stale.user_state() == dict(db.scan_all())
    # without a SnapshotStore the error reaches the caller instead
    rs2 = ReplicaSet(db)
    with pytest.raises(SnapshotRequired):
        rs2.add_replica(Replica("nope", cache_pages=128))


def test_reseeded_replica_survives_local_crash(primary):
    """The reseed watermark is durable: local crash recovery lands on the
    snapshot window and re-subscribes cleanly."""
    rng, db, rows, base = primary
    store = SnapshotStore()
    rs = ReplicaSet(db, snapshots=store)
    store.take(db)
    replica = store.restore_replica("r1", page_size=8192, cache_pages=512)
    rs.add_replica(replica)
    rs.sync()
    _mix(rng, db, 15)
    rs.sync()
    replica.recover_local(Strategy.LOG1)
    replica.resubscribe(rs.shipper)
    _mix(rng, db, 10)
    rs.sync()
    assert replica.user_state() == committed_state_oracle(db.crash(), base)


# ------------------------------------------------------------- promote/reseed
@pytest.mark.parametrize("crash_primary", [False, True])
def test_promote_reseeds_survivors(primary, crash_primary):
    rng, db, rows, base = primary
    store = SnapshotStore()
    rs = ReplicaSet(db, snapshots=store)
    store.take(db)
    all_ids = {"r1", "r2", "r3"}
    for rid, ps in (("r1", 8192), ("r2", 2048), ("r3", 4096)):
        rs.add_replica(store.restore_replica(rid, page_size=ps,
                                             cache_pages=512))
    rs.sync()
    _mix(rng, db, 25)
    rs.sync()
    _mix(rng, db, 10)                        # the set lags the tail
    loser = db.tc.begin()
    db.tc.update(loser, "t", rows[3][0], b"LOSER")
    db.log.flush()
    image = db.crash() if crash_primary else None
    oracle = committed_state_oracle(db.crash(), base)
    new_primary = rs.promote(image=image)
    assert dict(new_primary.scan_all()) == oracle
    # zero permanently-detached survivors: re-seeded AND re-subscribed
    assert len(rs.replicas) == 2
    assert set(rs.replicas) < all_ids
    assert all(rs.shipper.is_subscribed(rid) for rid in rs.replicas)
    # new writes reach every survivor through ordinary shipping
    token = rs.write([("update", "t", rows[4][0], b"AFTER-FAILOVER")])
    rs.sync()
    for r in rs.replicas.values():
        assert r.applied_lsn >= token
        assert r.read("t", rows[4][0]) == b"AFTER-FAILOVER"
        assert r.user_state() == dict(new_primary.scan_all())
    # read routing serves from survivors again
    res = rs.read("t", rows[4][0], min_lsn=token)
    assert res.source in rs.replicas


def test_promote_without_store_still_detaches(primary):
    rng, db, rows, base = primary
    rs = ReplicaSet(db)
    rs.add_replica(Replica("r1", cache_pages=512, seed_tables={"t": rows}))
    rs.add_replica(Replica("r2", cache_pages=512, seed_tables={"t": rows}))
    rs.sync()
    new_primary = rs.promote("r1")
    assert rs.replicas == {}                 # pre-archive behavior intact
    assert dict(new_primary.scan_all()) == \
        committed_state_oracle(db.crash(), base)


# ----------------------------------------------------------- ranged routing
def test_read_range_serial_and_primary_fallback(primary):
    rng, db, rows, base = primary
    store = SnapshotStore()
    rs = ReplicaSet(db, snapshots=store)
    store.take(db)
    replica = store.restore_replica("r1", page_size=8192, cache_pages=512)
    rs.add_replica(replica)
    rs.sync()
    lo, hi = b"k00100", b"k00140"
    res = rs.read_range("t", lo, hi)
    assert res.source == "r1"
    expect = {k: v for k, v in db.scan_all()
              if make_key("t", lo) <= k < make_key("t", hi)}
    assert {make_key("t", k): v for k, v in res.rows} == expect
    # unreachable token -> primary fallback with committed-only visibility
    txn = db.tc.begin()
    db.tc.update(txn, "t", b"k00120", b"DIRTY")
    res2 = rs.read_range("t", lo, hi, min_lsn=db.log.stable_lsn + 10_000)
    assert res2.source == "primary"
    assert dict(res2.rows).get(b"k00120") != b"DIRTY"
    db.tc.abort(txn)


def test_read_range_sharded_min_over_spanned_shards(primary):
    """The ROADMAP rule: a ranged scan over a sharded standby takes the
    min volatile watermark across the shards the range spans — a behind
    shard outside the range must not block, one inside must."""
    rng, db, rows, base = primary
    store = SnapshotStore()
    store.take(db)
    part = range_partitioner([("t", b"k00150"), ("t", b"k00300")])
    sh = store.restore_replica("s1", replica_cls=ShardedApplier,
                               n_shards=3, partitioner=part,
                               epoch_txns=10_000, auto_pump=False,
                               page_size=8192, cache_pages=512)
    rs = ReplicaSet(db, snapshots=store)
    rs.add_replica(sh)
    token = rs.write([("update", "t", b"k00010", b"S0"),   # shard 0
                      ("update", "t", b"k00200", b"S1")])  # shard 1
    rs.sync(max_records=10_000)              # ingest + dispatch, no pump
    sh.pump(shard=1)
    sh.pump(shard=2)
    # shard 0 is behind the token; shards 1 and 2 are current
    assert sh.watermark_for_range("t", b"k00200", b"k00250") >= token
    assert sh.watermark_for_range("t", b"k00000", b"k00100") < token
    r_in = rs.read_range("t", b"k00200", b"k00250", min_lsn=token)
    assert r_in.source == "s1" and r_in.watermark >= token
    r_cross = rs.read_range("t", b"k00100", b"k00200", min_lsn=token)
    assert r_cross.source == "primary"       # spans the behind shard
    sh.pump()
    r_now = rs.read_range("t", b"k00100", b"k00200", min_lsn=token)
    assert r_now.source == "s1"
    # hash partitioner cannot enumerate spans: any range uses the global min
    sh2 = ShardedApplier("s2", n_shards=4, epoch_txns=4, cache_pages=256)
    assert sh2.watermark_for_range("t", b"a", b"b") == sh2.catchup_lsn()


def test_scan_range_matches_point_reads(primary):
    rng, db, rows, base = primary
    store = SnapshotStore()
    store.take(db)
    replica = store.restore_replica("r1", page_size=2048, cache_pages=512)
    rs = ReplicaSet(db, snapshots=store)
    rs.add_replica(replica)
    rs.sync()
    scanned = replica.scan_range("t", b"k00050", b"k00060")
    for k, v in scanned:
        assert replica.read("t", k) == v
    assert [k for k, _ in scanned] == sorted(k for k, _ in scanned)
    # open-ended scans cover the whole table
    all_rows = replica.scan_range("t")
    assert {make_key("t", k): v for k, v in all_rows} == replica.user_state()
