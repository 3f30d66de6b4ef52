"""Transactional Component (TC): logical locking surface, logical logging,
checkpointing (RSSP), and the recovery driver's transaction table.

The TC never sees a PID: it logs (table, key, before, after).  In the
side-by-side prototype the DC stamps the touched PID back onto the shared log
record *after* applying — exactly how the paper's SQL-Server-derived prototype
keeps one log serving both recovery families (Section 5.1); logical recovery
ignores that field.

``Database`` is the harness: normal execution, checkpoints, trackers,
background flushing, and crash-image capture.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..obs import metrics as _metrics
from ..obs.flightrec import FLIGHT as _FLIGHT
from ..obs.flightrec import auto_dump as _flight_dump
from .dc import DataComponent, make_key, rec_key, table_range
from .log import LogManager
from .records import (LSN, NULL_LSN, AbortRec, BeginCkptRec, CLRRec,
                      CommitRec, EndCkptRec, RecKind, SnapshotRec, TxnId,
                      UpdateRec)
from .storage import PageStore

#: before- plus after-image bytes of every update record the TC logs
_C_LOG_BYTES = _metrics.counter("log.bytes_appended")


class TransactionalComponent:
    def __init__(self, log: LogManager, dc: DataComponent):
        self.log = log
        self.dc = dc
        self.active: dict[TxnId, LSN] = {}       # txn -> last LSN of its chain
        self._next_txn: TxnId = 1
        # commit hooks: called as f(txn, commit_lsn) after the group-commit
        # force, i.e. once the txn's records are stable and thus shippable.
        self.on_commit: list = []
        # per-txn first write of each (table, key): (lsn, before-image) —
        # the committed value at the time the in-flight txn first touched it
        self._first_writes: dict[TxnId, dict] = {}

    # ------------------------------------------------------------------ txns
    def begin(self) -> TxnId:
        txn = self._next_txn
        self._next_txn += 1
        self.active[txn] = NULL_LSN
        return txn

    def _log_op(self, txn: TxnId, table: str, key: bytes,
                before: Optional[bytes], after: Optional[bytes],
                op: RecKind) -> UpdateRec:
        rec = UpdateRec(txn=txn, table=table, key=key, before=before,
                        after=after, prev_lsn=self.active[txn], op=op)
        self.log.append(rec)
        _C_LOG_BYTES.inc(len(before or b"") + len(after or b""))
        self.active[txn] = rec.lsn
        self._first_writes.setdefault(txn, {}).setdefault(
            (table, key), (rec.lsn, before))
        self.dc.apply(rec)       # DC stamps rec.pid (prototype common log)
        return rec

    def update(self, txn: TxnId, table: str, key: bytes, value: bytes) -> None:
        before = self.dc.read(table, key)
        self._log_op(txn, table, key, before, value, RecKind.UPDATE)

    def insert(self, txn: TxnId, table: str, key: bytes, value: bytes) -> None:
        self._log_op(txn, table, key, None, value, RecKind.INSERT)

    def delete(self, txn: TxnId, table: str, key: bytes) -> None:
        before = self.dc.read(table, key)
        self._log_op(txn, table, key, before, None, RecKind.DELETE)

    def committed_read(self, table: str, key: bytes) -> Optional[bytes]:
        """Read (table, key) as of the last commit.  The DC executes updates
        at log time — before commit — so a plain ``dc.read`` sees in-flight
        work.  The first in-flight writer of a key captured the committed
        value as its before-image; ``_first_writes`` keeps that per active
        transaction, making this O(active txns) per read."""
        best: Optional[tuple] = None
        for txn in self.active:
            hit = self._first_writes.get(txn, {}).get((table, key))
            if hit is not None and (best is None or hit[0] < best[0]):
                best = hit
        if best is not None:
            return best[1]
        return self.dc.read(table, key)

    def _committed_overlay(self) -> dict:
        """Composite key -> (first-write LSN, committed before-image) for
        every key touched by an in-flight transaction.  The DC executes
        updates at log time, so the tree holds uncommitted values; the
        earliest first-writer's before-image is the committed value (same
        reasoning as ``committed_read``, materialized for a batch)."""
        overlay: dict[bytes, tuple[LSN, Optional[bytes]]] = {}
        for txn in self.active:
            for (table, key), (lsn, before) in \
                    self._first_writes.get(txn, {}).items():
                ck = make_key(table, key)
                if ck not in overlay or lsn < overlay[ck][0]:
                    overlay[ck] = (lsn, before)
        return overlay

    def committed_chunk(self, after: Optional[bytes], n: int
                        ) -> tuple[list[tuple[bytes, bytes]],
                                   Optional[bytes], bool]:
        """One chunk of a committed-only full scan in composite-key order:
        up to ``n`` raw tree records with key > ``after``, patched to
        committed values.  Returns ``(items, cursor, more)`` — feed
        ``cursor`` back as the next ``after``.  This is the fuzzy-snapshot
        scan step: it never blocks writers (the patch is O(active txns'
        write sets), not a lock), so state observed by different chunks may
        come from different commit points — the snapshot's (begin, end)
        window plus committed redo replay absorbs exactly that.

        Patching handles all three in-flight shapes: an uncommitted UPDATE
        reverts to the before-image, an uncommitted INSERT (before None) is
        dropped, and an uncommitted DELETE — whose key is *absent* from the
        raw chunk — is re-added at its before-image."""
        lo = after + b"\x00" if after is not None else None   # key > after
        raw = self.dc.btree.range_items(lo, None, n)
        more = len(raw) == n
        # the chunk covers (after, upper]; overlay keys past upper belong
        # to a later chunk, keys inside it merge in sorted position
        upper = raw[-1][0] if more else None
        overlay = self._committed_overlay()
        patched: dict[bytes, Optional[bytes]] = dict(raw)
        for ck, (_, before) in overlay.items():
            if (after is None or ck > after) and (upper is None or ck <= upper):
                patched[ck] = before                 # None = drop (insert)
        items = [(k, v) for k, v in sorted(patched.items()) if v is not None]
        return items, upper, more

    def committed_scan_range(self, table: str, lo: Optional[bytes] = None,
                             hi: Optional[bytes] = None
                             ) -> list[tuple[bytes, bytes]]:
        """Ranged ``committed_read``: ``table`` keys in [lo, hi) at their
        last-committed values.  The primary-fallback path of routed ranged
        scans must honor the same committed-only visibility the replica
        path enforces."""
        lo_c, hi_c = table_range(table, lo, hi)
        patched: dict[bytes, Optional[bytes]] = \
            dict(self.dc.btree.range_items(lo_c, hi_c))
        for ck, (_, before) in self._committed_overlay().items():
            if ck >= lo_c and (hi_c is None or ck < hi_c):
                patched[ck] = before
        from .dc import split_key
        return [(split_key(k)[1], v)
                for k, v in sorted(patched.items()) if v is not None]

    def apply_shipped(self, txn: TxnId, shipped: UpdateRec) -> None:
        """Re-log and re-execute a logical record shipped from another TC.

        The shipped record is read-only (it belongs to the source's log); a
        fresh record is appended to OUR log with OUR LSN space, reusing the
        shipped before-image so the undo chain works without a local read.
        This is the replica apply hook: logical identity (table, key) crosses
        the wire, PIDs never do."""
        self._log_op(txn, shipped.table, shipped.key, shipped.before,
                     shipped.after, shipped.op)

    def apply_shipped_batch(self, txn: TxnId, shipped_ops) -> int:
        """Batched ``apply_shipped``: re-log a run of shipped records in
        (key, source-LSN) order, then execute them through the DC's
        leaf-resident batched engine (``DataComponent.apply_batch``) in one
        walk — the replica/restore apply hot path.

        Reordering across keys is sound for the same reason the batched
        redo is: the ops are committed absolute after-images, per-key
        source order is preserved by the stable (key, lsn) sort, and the
        local undo chain (abort on a failed apply) restores before-images
        in reverse append order, which per key is reverse source order.
        Returns the number of ops applied."""
        order = sorted(shipped_ops, key=rec_key)   # stable: per-key source
        local: list[UpdateRec] = []                # order is kept
        log, active = self.log, self.active
        for s in order:
            rec = UpdateRec(txn=txn, table=s.table, key=s.key,
                            before=s.before, after=s.after,
                            prev_lsn=active[txn], op=s.op, ck=s.ck)
            log.append(rec)
            _C_LOG_BYTES.inc(len(s.before or b"") + len(s.after or b""))
            active[txn] = rec.lsn
            self._first_writes.setdefault(txn, {}).setdefault(
                (s.table, s.key), (rec.lsn, s.before))
            local.append(rec)
        # local LSNs were assigned in sorted-key order, so the batch is
        # presorted for the engine (its sort is then a linear verify)
        self.dc.apply_batch(local, mode="execute")
        return len(local)

    def commit(self, txn: TxnId) -> LSN:
        rec = CommitRec(txn=txn, prev_lsn=self.active[txn])
        self.log.append(rec)
        self.log.flush()                          # group-commit force
        self.dc.eosl(self.log.stable_lsn)         # EOSL push
        del self.active[txn]
        self._first_writes.pop(txn, None)
        for hook in self.on_commit:
            hook(txn, rec.lsn)
        return rec.lsn

    def abort(self, txn: TxnId) -> None:
        """Logical undo of the transaction's chain, writing CLRs."""
        lsn = self.active[txn]
        while lsn != NULL_LSN:
            rec = self.log.record(lsn)
            if isinstance(rec, UpdateRec):
                self._compensate(txn, rec)
                lsn = rec.prev_lsn
            elif isinstance(rec, CLRRec):
                lsn = rec.undo_next
            else:
                break
        arec = AbortRec(txn=txn, prev_lsn=self.active[txn])
        self.log.append(arec)
        self.log.flush()
        del self.active[txn]
        self._first_writes.pop(txn, None)

    def _compensate(self, txn: TxnId, rec: UpdateRec) -> None:
        """Undo one update logically; the CLR is redo-only."""
        if rec.op == RecKind.INSERT:
            clr = CLRRec(txn=txn, table=rec.table, key=rec.key, after=None,
                         op=RecKind.DELETE, undone_lsn=rec.lsn,
                         undo_next=rec.prev_lsn)
        else:   # UPDATE or DELETE: restore the before image
            clr = CLRRec(txn=txn, table=rec.table, key=rec.key,
                         after=rec.before, op=RecKind.UPDATE,
                         undone_lsn=rec.lsn, undo_next=rec.prev_lsn)
        self.log.append(clr)
        self.active[txn] = clr.lsn
        self.dc.apply_clr(clr)

    # ------------------------------------------------------------ checkpoint
    def checkpoint(self) -> LSN:
        """Penultimate-scheme checkpoint, coordinated with the DC via RSSP.
        Returns the bCkpt LSN (= redo scan start once complete)."""
        b = BeginCkptRec()
        self.log.append(b)
        self.log.flush()
        self.dc.rssp(b.lsn)                       # DC flushes + logs RSSP rec
        e = EndCkptRec(bckpt_lsn=b.lsn, active_txns=dict(self.active))
        self.log.append(e)
        self.log.flush()
        self.log.set_master(end_ckpt=e.lsn, bckpt=b.lsn)
        return b.lsn

    # -------------------------------------------------------------- snapshot
    def snapshot_begin(self, snapshot_id: int = 0) -> SnapshotRec:
        """Anchor a fuzzy logical snapshot: log (and force) a SnapshotRec
        carrying the oldest in-flight first-write LSN.  The record's own LSN
        is the snapshot's ``begin_lsn`` — every commit at or below it is
        fully visible to the scan that follows; redo at restore time starts
        at ``oldest_active_lsn`` (when set) so transactions straddling the
        begin point re-deliver completely."""
        oldest = min((lsn for fw in self._first_writes.values()
                      for lsn, _ in fw.values()), default=NULL_LSN)
        rec = SnapshotRec(snapshot_id=snapshot_id, oldest_active_lsn=oldest)
        self.log.append(rec)
        self.log.flush()
        return rec


@dataclass
class CrashImage:
    """What survives: the stable page store and the stable log prefix."""
    store: PageStore
    log: LogManager


class Database:
    """Side-by-side prototype harness (Section 5): one normal execution run
    produces a common log + crash image that every recovery strategy consumes."""

    def __init__(self, cache_pages: int = 4096, delta_mode: str = "paper",
                 side_by_side: bool = True, tracker_interval: int = 100,
                 bg_flush_per_txn: int = 0, page_size: int = None,
                 page_backend=None, media_retry=None):
        """``media_retry``: a ``faults.RetryPolicy`` threaded into the
        buffer pool so page reads/flushes against a flaky ``page_backend``
        absorb into bounded backoff (only ``BackendUnavailableError`` —
        corruption stays first-throw loud everywhere)."""
        if page_backend is not None:
            from ..media.backend import open_backend
            self.store = PageStore(open_backend(page_backend))
        else:
            self.store = PageStore()
        self.log = LogManager()
        self.dc = DataComponent(self.store, self.log, cache_pages,
                                delta_mode=delta_mode, side_by_side=side_by_side,
                                page_size=page_size, retry=media_retry)
        self.tc = TransactionalComponent(self.log, self.dc)
        self.tracker_interval = tracker_interval
        self.bg_flush_per_txn = bg_flush_per_txn
        self._updates_since_tracker = 0

    # ---------------------------------------------------------------- setup
    def bootstrap_empty(self) -> None:
        self.dc.bootstrap()
        self.tc.checkpoint()

    def load_table(self, table: str, rows: list[tuple[bytes, bytes]]) -> None:
        from .dc import make_key
        self.dc.bulk_build([(make_key(table, k), v) for k, v in rows])
        self.tc.checkpoint()

    # ------------------------------------------------------------- workload
    def note_update(self) -> None:
        """Tracker cadence: count one logical update; emit Delta/BW records
        every ``tracker_interval`` updates."""
        self._updates_since_tracker += 1
        if self._updates_since_tracker >= self.tracker_interval:
            self.dc.emit_trackers()
            self._updates_since_tracker = 0

    def note_updates(self, n: int) -> None:
        """Batch form of ``note_update``: same cadence, one call per
        applied batch instead of one per op."""
        self._updates_since_tracker += n
        while self._updates_since_tracker >= self.tracker_interval:
            self.dc.emit_trackers()
            self._updates_since_tracker -= self.tracker_interval

    def post_commit_flush(self) -> None:
        """Background page flushing budgeted per committed transaction."""
        if self.bg_flush_per_txn:
            self.dc.maybe_background_flush(self.bg_flush_per_txn)

    def run_txn(self, ops: list[tuple[str, str, bytes, Optional[bytes]]]) -> LSN:
        """ops: (verb, table, key, value) with verb in {update, insert, delete}.
        Returns the commit LSN — usable as a read-your-writes staleness token
        against a replica set."""
        txn = self.tc.begin()
        for verb, table, key, value in ops:
            if verb == "update":
                self.tc.update(txn, table, key, value)
            elif verb == "insert":
                self.tc.insert(txn, table, key, value)
            else:
                self.tc.delete(txn, table, key)
            self.note_update()
        commit_lsn = self.tc.commit(txn)
        self.post_commit_flush()
        return commit_lsn

    def checkpoint(self) -> LSN:
        return self.tc.checkpoint()

    # ----------------------------------------------------------------- crash
    def crash(self) -> CrashImage:
        """Simulate an unplanned crash: only stable state survives.  The
        flight recorder treats this as a black-box event — the dump is
        what a post-mortem of the dead process reads."""
        _FLIGHT.record("db.crash", self.log.stable_lsn, self.log.end_lsn)
        _flight_dump("db.crash")
        return CrashImage(store=self.store.clone(), log=self.log.crash())

    # ------------------------------------------------------------- inspection
    def scan_all(self) -> list[tuple[bytes, bytes]]:
        return self.dc.btree.items()
