"""Bounded database cache (buffer pool): CLOCK eviction, frame pins, dirty
tracking, WAL enforcement, and the penultimate-checkpoint "generation bit"
scheme (Section 3.2).

The pool is the only path between decoded pages and the ``PageStore``
(whose bytes live as ``page/<pid>`` blobs on a ``MediaBackend``), so
bounded residency is real: at most ``capacity_pages`` frames are decoded
at once, pinned frames (a ``LeafCursor`` span mid-mutation, a split in
flight) are never victims, clean victims drop for free, and dirty victims
flush through the WAL clamp — the log is forced up to the buffer's
``wal_lsn`` before the page may reach stable storage.

Listeners let the DC's Delta accumulator and the SQL-Server BW tracker observe
page dirtying / flush completions without the pool knowing about logging.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional

from ..obs import metrics as _metrics
from ..obs.flightrec import FLIGHT as _FLIGHT
from ..obs.trace import TRACER as _TRACER
from .log import LogManager
from .pages import Page
from .records import LSN, NULL_LSN, PID
from .storage import IOSim, PageStore

_C_HITS = _metrics.counter("bufferpool.hits")
_C_MISSES = _metrics.counter("bufferpool.misses")
_C_EVICTIONS = _metrics.counter("bufferpool.evictions")
_C_FLUSHES = _metrics.counter("bufferpool.flushes")
_C_FLUSH_FAILURES = _metrics.counter("bufferpool.flush_failures")
_G_PINNED = _metrics.gauge("bufferpool.pinned")


@dataclass(slots=True)
class Buffer:
    page: Page
    dirty: bool = False
    rlsn: LSN = NULL_LSN          # LSN of op that first dirtied this buffer
    wal_lsn: LSN = NULL_LSN       # max LSN applied (incl. SMOs) — WAL horizon
    dirty_gen: int = -1           # checkpoint generation when first dirtied
    pins: int = 0                 # pinned frames are never eviction victims
    ref: bool = True              # CLOCK reference bit
    bg_flush_tick: int = -2       # flush_some round that last wrote this page


class BufferPool:
    def __init__(self, store: PageStore, log: LogManager,
                 capacity_pages: int = 1 << 30, retry=None):
        self.store = store
        self.log = log
        self.capacity = capacity_pages
        # a ``faults.RetryPolicy`` mediating transient page-write failures
        # (the store may sit on a remote MediaBackend).  Duck-typed and
        # optional: core must not import faults at module load.
        self.retry = retry
        self.buffers: Dict[PID, Buffer] = {}
        self._clock: list[PID] = []        # CLOCK ring (lazy compaction)
        self._hand = 0
        self._flush_tick = 0               # flush_some round counter
        self.gen = 0                               # checkpoint generation bit
        # listeners
        self.on_update: list[Callable[[PID, LSN], None]] = []   # every page update
        self.on_flush: list[Callable[[PID], None]] = []          # flush IO complete
        # stats
        self.hits = 0
        self.fetches = 0              # misses (store reads), historical name
        self.evictions = 0
        self.flushes = 0
        self.flush_failures = 0       # transient write failures (page stayed
        #                               dirty + resident; nothing was lost)
        self.pinned_count = 0
        self.peak_resident = 0        # max frames ever resident at once
        # recovery-time IO accounting hook
        self.iosim: Optional[IOSim] = None

    # ------------------------------------------------------------------ fetch
    def get(self, pid: PID, pin: bool = False) -> Optional[Page]:
        buf = self.buffers.get(pid)
        if buf is not None:
            buf.ref = True
            self.hits += 1
            _C_HITS.inc()
            if pin:
                self._pin(buf)
            return buf.page
        if self.retry is None:
            page = self.store.read_page(pid)
        else:
            # demand reads are as retryable as flushes: the backend, not
            # the bytes, failed — bounded backoff beats a dead read path
            page = self.retry.call(self.store.read_page, pid)
        if page is None:
            return None
        if self.iosim is not None:
            self.iosim.demand_read(pid)
        self.fetches += 1
        _C_MISSES.inc()
        buf = self._install(page, dirty=False)
        if pin:
            self._pin(buf)
        return page

    def contains(self, pid: PID) -> bool:
        return pid in self.buffers

    def install_new(self, page: Page, lsn: LSN) -> None:
        """Install a freshly allocated page (born dirty)."""
        self._install(page, dirty=True, rlsn=lsn)

    def _install(self, page: Page, dirty: bool,
                 rlsn: LSN = NULL_LSN) -> Buffer:
        self._evict_for_space()
        buf = Buffer(page=page, dirty=dirty, rlsn=rlsn, wal_lsn=rlsn,
                     dirty_gen=self.gen if dirty else -1)
        if page.pid not in self.buffers:
            self._clock.append(page.pid)
        self.buffers[page.pid] = buf
        if len(self.buffers) > self.peak_resident:
            self.peak_resident = len(self.buffers)
        return buf

    # ------------------------------------------------------------------- pins
    def _pin(self, buf: Buffer) -> None:
        buf.pins += 1
        self.pinned_count += 1
        _G_PINNED.inc()

    def pin(self, pid: PID) -> None:
        self._pin(self.buffers[pid])

    def unpin(self, pid: PID) -> None:
        buf = self.buffers[pid]
        assert buf.pins > 0, f"unpin of unpinned frame {pid}"
        buf.pins -= 1
        self.pinned_count -= 1
        _G_PINNED.inc(-1)

    # ------------------------------------------------------------------ dirty
    def mark_dirty(self, pid: PID, lsn: LSN) -> None:
        buf = self.buffers[pid]
        if not buf.dirty:
            buf.dirty = True
            buf.rlsn = lsn
            buf.dirty_gen = self.gen
        if lsn > buf.wal_lsn:
            buf.wal_lsn = lsn
        for cb in self.on_update:
            cb(pid, lsn)

    # ------------------------------------------------------------------ flush
    def flush_page(self, pid: PID) -> bool:
        buf = self.buffers.get(pid)
        if buf is None or not buf.dirty:
            return False
        # WAL: a page may not reach stable storage before the log records that
        # produced its state — including SMOs, hence wal_lsn not plsn.  (EOSL
        # gives the DC the TC stable point; here the integrated log is forced
        # directly.)
        if buf.wal_lsn > self.log.stable_lsn:
            self.log.flush(buf.wal_lsn)
        # call-time import: core loads before media (package layering)
        from ..media.errors import BackendUnavailableError
        try:
            if self.retry is None:
                self.store.write_page(buf.page)
            else:
                self.retry.call(self.store.write_page, buf.page)
        except BackendUnavailableError:
            # the write never happened: the buffer stays dirty (its state
            # was not touched above), stays resident, and keeps serving
            # reads — account the failure and let the caller decide
            # whether this flush was optional (background cadence) or not
            self.flush_failures += 1
            _C_FLUSH_FAILURES.inc()
            _FLIGHT.record("pool.flush_fail", pid, buf.wal_lsn)
            raise
        buf.dirty = False
        buf.rlsn = NULL_LSN
        buf.dirty_gen = -1
        self.flushes += 1
        _C_FLUSHES.inc()
        _FLIGHT.record("pool.flush", pid, buf.wal_lsn)
        for cb in self.on_flush:
            cb(pid)
        return True

    def flush_some(self, max_pages: int) -> int:
        """Background flusher: write the oldest-dirtied pages (rate-limited).
        This is the training-framework 'fuzzy incremental checkpoint' driver.

        Hot-page coalescing: a page this flusher wrote last round and that
        is dirty again already is hot — writing it every round is wasted
        serialization (it will be dirty again before any crash cares), so
        it sits out one round and flushes every other.  Cold pages are
        unaffected: with a large dirty set the rate limit never re-picks
        the same page on consecutive rounds anyway.  Correctness is
        untouched — any flush schedule is WAL-legal, a skipped page just
        stays in the DPT one round longer."""
        with _TRACER.span("pool.bg_flush") as sp:
            self._flush_tick += 1
            tick = self._flush_tick
            dirty = [(b.rlsn, pid) for pid, b in self.buffers.items()
                     if b.dirty and b.bg_flush_tick < tick - 1]
            dirty.sort()
            n = 0
            from ..media.errors import BackendUnavailableError
            for _, pid in dirty[:max_pages]:
                try:
                    flushed = self.flush_page(pid)
                except BackendUnavailableError:
                    # background flushing is optional by construction (any
                    # flush schedule is WAL-legal): the page stays dirty
                    # and the next round retries it.  flush_page accounted
                    # the failure; outage-wide pressure shows up as a
                    # flush_failures ramp, not a dead pool.
                    continue
                if flushed:
                    self.buffers[pid].bg_flush_tick = tick
                    n += 1
            sp.set(scanned=len(self.buffers), flushed=n)
        return n

    # ------------------------------------------------------------- checkpoint
    def begin_checkpoint_flush(self) -> int:
        """Penultimate scheme: flip the generation bit, then flush every page
        dirtied in an earlier generation.  Pages dirtied *during* the
        checkpoint keep the new generation and are left dirty."""
        self.gen += 1
        flushed = 0
        victims = [pid for pid, b in self.buffers.items()
                   if b.dirty and b.dirty_gen < self.gen]
        for pid in victims:
            if self.flush_page(pid):
                flushed += 1
        return flushed

    # --------------------------------------------------------------- eviction
    def _evict_for_space(self) -> None:
        from ..media.errors import BackendUnavailableError
        failing: set[PID] = set()      # dirty victims whose flush failed
        last_exc: Optional[Exception] = None
        while len(self.buffers) >= self.capacity:
            victim = self._clock_sweep(skip=failing)
            if victim is None:
                if last_exc is not None:
                    # every evictable frame is dirty and every flush
                    # failed: the pool genuinely cannot make space, and
                    # soft-overflowing would hide a full outage — raise
                    # the last transient error instead
                    raise last_exc
                # every frame is pinned: overflow softly rather than
                # deadlock — pins are short (one mutation window)
                break
            try:
                self._evict(victim)
            except BackendUnavailableError as exc:
                # the victim stayed resident and dirty (flush_page left
                # it intact); put it back in the ring, remember it as
                # failing, back off once, and sweep for a different
                # victim — a clean frame costs no IO and always works
                self._clock.append(victim)
                failing.add(victim)
                last_exc = exc
                if self.retry is not None:
                    self.retry.backoff(min(len(failing),
                                           self.retry.max_attempts))

    def _clock_sweep(self, skip: Optional[set] = None) -> Optional[PID]:
        """Advance the CLOCK hand to a victim: referenced frames get a
        second chance, pinned frames (and ``skip`` members — victims whose
        flush just failed) are never picked, clean frames are preferred (a
        dirty victim costs a flush IO); the first unreferenced dirty frame
        is remembered as the fallback."""
        clock = self._clock
        fallback: Optional[PID] = None
        steps = 0
        limit = 3 * len(clock) + 1
        while clock and steps < limit:
            steps += 1
            if self._hand >= len(clock):
                self._hand = 0
            pid = clock[self._hand]
            buf = self.buffers.get(pid)
            if buf is None:                       # lazily compact stale slot
                clock[self._hand] = clock[-1]
                clock.pop()
                continue
            if buf.pins or (skip is not None and pid in skip):
                self._hand += 1
                continue
            if buf.ref:
                buf.ref = False
                self._hand += 1
                continue
            if not buf.dirty:
                clock[self._hand] = clock[-1]
                clock.pop()
                return pid
            if fallback is None:
                fallback = pid
            self._hand += 1
        if fallback is not None:
            self._clock.remove(fallback)          # rare: all-victims-dirty
            return fallback
        return None

    def _evict(self, pid: PID) -> None:
        buf = self.buffers[pid]
        was_dirty = buf.dirty
        if was_dirty:
            self.flush_page(pid)                  # WAL-clamped inside
        del self.buffers[pid]
        self.evictions += 1
        _C_EVICTIONS.inc()
        _FLIGHT.record("pool.evict", pid, 1 if was_dirty else 0)

    # ------------------------------------------------------------------ misc
    def dirty_pids(self) -> list[PID]:
        return [pid for pid, b in self.buffers.items() if b.dirty]

    def reset_stats(self) -> None:
        self.hits = self.fetches = self.evictions = self.flushes = 0
        self.peak_resident = len(self.buffers)

    def __len__(self) -> int:
        return len(self.buffers)
