"""Write-ahead log manager.

One LSN space; in-memory tail + "stable" prefix (what survives a crash).
``flush()`` advances the stable point (group commit forces it).  ``crash()``
returns the stable prefix — the unforced tail is lost, exactly the set of
records the paper's "tail of the log" analysis concerns itself with.

The master pointer (ARIES' master record) remembers the last complete
checkpoint and the DC's last RSSP record so recovery knows where to start
without scanning from the beginning of time.  Master LSNs may point *below*
the truncation base (an old checkpoint whose records have moved to the
archive): ``record``/``scan`` splice transparently, so the pointer stays
valid across truncation.

Truncation: once a stable prefix has been sealed into an attached
``LogArchive``, ``truncate(upto)`` drops it from memory and remembers only
the base LSN.  Every read path (``record``, ``scan``, ``scan_stable``)
splices archive segments and the live tail into one dense LSN sequence, so
recovery, analysis, DPT construction and log shipping are oblivious to
where a record physically lives.  Only records *pruned from the archive*
are gone for good — reading below ``retained_lsn`` raises
``TruncatedLogError`` (never a silent skip), which the shipper surfaces as
``SnapshotRequired``.

Truncation without an archive is ``discard(upto)``: a log whose one
reader is crash recovery may delete the stable prefix below the last
complete checkpoint's bCkpt record.  Recovery scans from that record
(``recover`` reads the master's ``bckpt_lsn``, and the RSSP and EndCkpt
records above it), the RSSP checkpoint has flushed every page dirtied
before it, and with no transaction active a loser's undo chain starts
above the cut too; so nothing a recovery reads is lost.  The records go
for good: a read below the base raises ``TruncatedLogError``.
``discard`` refuses when an archive is attached (then ``truncate``
through the ``Archiver`` is the path) and when the cut reaches the bCkpt
record or the unstable tail; the TC adds the last guard, no active
transaction (``TransactionalComponent.discard_log``).
"""
from __future__ import annotations

import bisect
import time
from dataclasses import dataclass, field
from typing import Iterator, List, Optional, Tuple

from .records import (LSN, NULL_LSN, BeginCkptRec, CommitRec, EndCkptRec,
                      LogRec, RSSPRec, UpdateRec)

# Purely for IO accounting: how many log records fit a "log page".
LOG_RECS_PER_PAGE = 64

#: commit-to-visible stamp retention; bounds memory on a primary whose
#: replicas never poll (stamps for drained commits are long gone anyway)
_MAX_COMMIT_STAMPS = 8192


def _image_bytes(rec: UpdateRec) -> int:
    return len(rec.before or b"") + len(rec.after or b"")


def _held_image_bytes(recs) -> int:
    """``_image_bytes`` summed over the update records of ``recs``."""
    return sum(_image_bytes(r) for r in recs if isinstance(r, UpdateRec))


class TruncatedLogError(LookupError):
    """A read touched LSNs that are neither in memory nor in the archive
    (truncated without an archive, or pruned from it).  Raised instead of
    silently skipping: a recovery or shipping pass that misses records
    would corrupt state, so the hole must be loud."""


@dataclass
class Master:
    """Stable master pointer (updated atomically, survives crash)."""
    end_ckpt_lsn: LSN = NULL_LSN      # last complete checkpoint's eCkpt LSN
    bckpt_lsn: LSN = NULL_LSN         # its matching bCkpt LSN
    rssp_rec_lsn: LSN = NULL_LSN      # DC's last RSSP record (carries DC meta)


class LogManager:
    def __init__(self):
        self._recs: List[LogRec] = []
        self._base: LSN = 0                # records [1, _base] truncated away
        self._stable_lsn: LSN = 0          # records [1, _stable_lsn] are stable
        self.archive = None                # LogArchive holding the sealed prefix
        self.master = Master()
        self.forced_flushes = 0
        self.max_txn: int = 0              # largest txn id ever logged
        self.last_commit_lsn: LSN = NULL_LSN   # newest CommitRec appended
        # Newest CommitRec at or below the stable point.  This — not
        # last_commit_lsn, which may sit in the unforced tail — is the
        # reference for commit-relative staleness: a committed-only consumer
        # can never have applied past it, so lag measured against anything
        # newer is phantom lag.
        self.last_stable_commit_lsn: LSN = NULL_LSN
        # Commit LSNs in the unforced tail, ascending by construction.
        # flush() bisects here for the newest commit <= the flush target
        # instead of rescanning the flushed range backwards — O(commits
        # since the last flush), amortized O(1) per commit.
        self._pending_commits: List[LSN] = []
        # Commit LSN -> perf_counter stamp taken the moment the commit
        # became stable (its flush) — the t0 of commit-to-visible.  The
        # shipper copies stamps into batches; appliers subtract at apply.
        # Bounded FIFO: insertion order is LSN order, so evicting the
        # oldest drops the commit least likely to still be in flight.
        self.commit_stamps: dict = {}
        # Before- plus after-image bytes of the update records held in
        # memory: raised on append, lowered when a prefix leaves memory.
        # A CLR's after-image is the undone update's before-image object,
        # so CLRs add nothing; summed over appends this is what the
        # ``log.bytes_appended`` counter adds for this log.
        self.live_image_bytes: int = 0

    # ---------------------------------------------------------------- append
    def append(self, rec: LogRec) -> LSN:
        rec.lsn = self._base + len(self._recs) + 1   # dense LSNs starting at 1
        self._recs.append(rec)
        if isinstance(rec, UpdateRec):
            self.live_image_bytes += _image_bytes(rec)
        txn = getattr(rec, "txn", None)
        if txn is not None and txn > self.max_txn:
            self.max_txn = txn
        if isinstance(rec, CommitRec):
            self.last_commit_lsn = rec.lsn
            self._pending_commits.append(rec.lsn)
        return rec.lsn

    def flush(self, upto: Optional[LSN] = None) -> LSN:
        """Force the log to stable storage up to ``upto`` (default: all)."""
        tgt = self.end_lsn if upto is None else min(upto, self.end_lsn)
        if tgt > self._stable_lsn:
            # newest pending commit at or below tgt; the full flush (the
            # common case) clears the whole pending list in one del
            idx = bisect.bisect_right(self._pending_commits, tgt)
            if idx:
                self.last_stable_commit_lsn = self._pending_commits[idx - 1]
                stamps = self.commit_stamps
                now = time.perf_counter()
                for lsn in self._pending_commits[:idx]:
                    if len(stamps) >= _MAX_COMMIT_STAMPS:
                        del stamps[next(iter(stamps))]
                    stamps[lsn] = now
                del self._pending_commits[:idx]
            self._stable_lsn = tgt
            self.forced_flushes += 1
        return self.stable_lsn

    @property
    def stable_lsn(self) -> LSN:
        return self._stable_lsn            # LSN of last stable record

    @property
    def end_lsn(self) -> LSN:
        return self._base + len(self._recs)

    # -------------------------------------------------------------- archive
    def attach_archive(self, archive) -> None:
        """Wire a ``LogArchive`` in as the home of the sealed prefix; the
        read paths below splice it with the live tail from then on."""
        self.archive = archive

    @property
    def retained_lsn(self) -> LSN:
        """First LSN still obtainable (from the archive or from memory).
        Everything below it has been truncated-without-archive or pruned."""
        mem_from = self._base + 1
        a = self.archive
        if a is not None and a.retained_from < mem_from \
                and a.archived_upto >= self._base:   # contiguous splice
            return a.retained_from
        return mem_from

    @property
    def in_memory_records(self) -> int:
        """Live tail size — what truncation bounds (``end_lsn`` keeps
        counting every record ever appended)."""
        return len(self._recs)

    def truncate(self, upto: LSN) -> int:
        """Drop the in-memory prefix [1, upto]; returns records dropped.

        Never loses information: the prefix must already be sealed in the
        attached archive (and be stable — the unforced tail cannot be
        archived, it can still be disowned by a crash).  Callers pick
        ``upto`` below the ``min(snapshot horizon, slowest subscriber)``
        watermark (see ``archive.Archiver``) so the *hot* paths — shipping
        to live subscribers, restore to recent targets — stay in memory and
        only cold readers ever touch archive segments."""
        upto = min(upto, self._stable_lsn)
        if upto <= self._base:
            return 0
        if self.archive is None or self.archive.archived_upto < upto:
            have = "no archive attached" if self.archive is None else \
                f"archive sealed only through LSN {self.archive.archived_upto}"
            raise ValueError(
                f"cannot truncate through LSN {upto}: {have} — seal the "
                "prefix into a LogArchive first (truncation moves records, "
                "it never deletes them)")
        return self._drop_prefix(upto)

    def discard(self, upto: LSN) -> int:
        """Delete the in-memory prefix [1, upto] for good; returns records
        dropped.  Only for a log whose one reader is crash recovery, and
        only below the last complete checkpoint's bCkpt record, where
        recovery's scan starts (module docstring).  Refuses an attached
        archive, a cut at or above the master's ``bckpt_lsn``, and a cut
        above the stable point."""
        if self.archive is not None:
            raise ValueError(
                f"cannot discard through LSN {upto}: an archive is attached "
                "— truncate through the Archiver, which keeps the records")
        if upto >= self.master.bckpt_lsn:
            raise ValueError(
                f"cannot discard through LSN {upto}: recovery scans from the "
                f"last complete checkpoint's bCkpt at LSN "
                f"{self.master.bckpt_lsn}")
        if upto > self._stable_lsn:
            raise ValueError(
                f"cannot discard through LSN {upto}: the log is stable only "
                f"through LSN {self._stable_lsn}")
        if upto <= self._base:
            return 0
        return self._drop_prefix(upto)

    def _drop_prefix(self, upto: LSN) -> int:
        dropped = upto - self._base
        self.live_image_bytes -= _held_image_bytes(self._recs[:dropped])
        del self._recs[:dropped]
        self._base = upto
        return dropped

    # ----------------------------------------------------------------- read
    def record(self, lsn: LSN) -> LogRec:
        if lsn > self._base:
            return self._recs[lsn - self._base - 1]
        if self.archive is not None:
            return self.archive.record(lsn)     # raises TruncatedLogError
        raise TruncatedLogError(
            f"LSN {lsn} was truncated (base={self._base}) and no archive "
            "is attached")

    def scan(self, from_lsn: LSN, to_lsn: Optional[LSN] = None) -> Iterator[LogRec]:
        """Yield stable records with lsn >= from_lsn (inclusive), splicing
        archive segments below the truncation base with the live tail."""
        hi = self._stable_lsn if to_lsn is None else min(to_lsn, self._stable_lsn)
        lo = max(from_lsn, 1)
        if lo > hi:
            return
        if lo <= self._base:
            if lo < self.retained_lsn:
                raise TruncatedLogError(
                    f"scan from LSN {lo} reaches below retained_lsn="
                    f"{self.retained_lsn}: those records were pruned")
            yield from self.archive.scan(lo, min(hi, self._base))
            lo = self._base + 1
        for i in range(lo - self._base - 1, hi - self._base):
            yield self._recs[i]

    def scan_stable(self, from_lsn: LSN,
                    max_records: Optional[int] = None
                    ) -> Tuple[List[LogRec], LSN]:
        """Shipping-cursor read: a batch of stable records starting at
        ``from_lsn``, plus the cursor for the next call.

        Returns ``(records, next_lsn)`` where ``next_lsn`` is the LSN the
        caller should resume from — callers keep no other state, which is
        what makes a log shipper restartable: the cursor can always be
        reconstructed from the consumer's durable resume point.  Only the
        stable prefix is visible; the unforced tail is never shipped (it can
        still be lost, and a replica must never apply work its primary could
        disown).  Truncation is invisible here too: a cursor below the base
        reads spliced archive segments.  Below ``retained_lsn`` there is
        nothing to splice and ``TruncatedLogError`` propagates (the shipper
        turns it into ``SnapshotRequired``)."""
        lo = max(from_lsn, 1)
        hi = self._stable_lsn
        if max_records is not None:
            hi = min(hi, lo - 1 + max_records)
        if lo > hi:
            return [], lo
        recs = list(self.scan(lo, hi))
        return recs, lo + len(recs)

    # ------------------------------------------------------------ checkpoint
    def set_master(self, *, end_ckpt: Optional[LSN] = None,
                   bckpt: Optional[LSN] = None,
                   rssp_rec: Optional[LSN] = None) -> None:
        if end_ckpt is not None:
            self.master.end_ckpt_lsn = end_ckpt
        if bckpt is not None:
            self.master.bckpt_lsn = bckpt
        if rssp_rec is not None:
            self.master.rssp_rec_lsn = rssp_rec

    def save_master(self, backend=None) -> None:
        """Persist the master pointer as an encoded blob on a
        ``MediaBackend`` (default: the attached archive's backend) — the
        ARIES master record made real bytes, so a fresh process knows
        where the last complete checkpoint and RSSP live without scanning
        (``Archiver.run_once`` calls this after every seal)."""
        from ..media.codec import encode_master   # keep core import-light
        if backend is None:
            backend = getattr(self.archive, "backend", None)
        if backend is None:
            raise ValueError("save_master needs a MediaBackend (none given "
                             "and no backend-backed archive is attached)")
        # reprolint: allow(wal-discipline) — the master pointer is the recovery bootstrap, not data: it only names LSNs that seal() already clamped to stable_lsn, and a stale master is always safe (recovery just scans further)
        backend.put("master", encode_master(self.master))

    @staticmethod
    def load_master(backend) -> Master:
        """Read a master pointer back from a backend; a fresh ``Master``
        (all NULL_LSN) when none was ever saved."""
        from ..media.codec import decode_master
        if not backend.exists("master"):
            return Master()
        return decode_master(backend.get("master"))

    # ---------------------------------------------------------------- crash
    def crash(self) -> "LogManager":
        """Return the stable image of this log (tail beyond stable point
        lost).  The archive is stable storage: the survivor keeps the same
        sealed segments, so a post-truncation crash image still reads the
        full history through the splice."""
        survivor = LogManager()
        kept = self._stable_lsn - self._base
        survivor._recs = self._recs[:kept]
        survivor.live_image_bytes = (self.live_image_bytes
                                     - _held_image_bytes(self._recs[kept:]))
        survivor._base = self._base
        survivor._stable_lsn = self._stable_lsn
        survivor.archive = self.archive
        survivor.master = Master(self.master.end_ckpt_lsn,
                                 self.master.bckpt_lsn,
                                 self.master.rssp_rec_lsn)
        # max_txn may over-approximate (tail txns lost in the crash), which is
        # safe: recovery only needs fresh txn ids to be strictly larger than
        # any id that can appear in the surviving log.
        survivor.max_txn = self.max_txn
        # last_stable_commit_lsn is maintained at every flush and is by
        # definition the newest commit that survives, so both notions
        # coincide on the survivor (a commit in the unforced tail is lost).
        survivor.last_commit_lsn = self.last_stable_commit_lsn
        survivor.last_stable_commit_lsn = self.last_stable_commit_lsn
        # Stamps belong to stable commits, all of which survive; keeping
        # them lets commit-to-visible span a failover (stamps are
        # perf_counter values, comparable within this process only).
        survivor.commit_stamps = dict(self.commit_stamps)
        return survivor

    def n_log_pages(self, from_lsn: LSN) -> int:
        n = max(0, self._stable_lsn - (from_lsn - 1))
        return (n + LOG_RECS_PER_PAGE - 1) // LOG_RECS_PER_PAGE

    def __len__(self) -> int:
        """Total records ever appended (dense LSN space, unaffected by
        truncation) — callers diff this across operations to count writes."""
        return self.end_lsn
