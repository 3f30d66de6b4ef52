"""Data Component (DC): owns placement (B-tree), the cache (buffer pool) and
stable storage.  Knows *nothing* about transactions; executes (re-)submitted
logical operations and runs its own recovery (SMO replay + DPT construction)
before the TC's redo pass (Section 1.2, 4.2).

The TC addresses records logically as (table, key); the DC maps that to a
composite byte key (length-prefixed table + key) so one tree serves many
tables, and then to a leaf PID.
"""
from __future__ import annotations

import bisect
import struct
from dataclasses import dataclass, field
from typing import Optional

from ..obs import metrics as _metrics
from ..obs.trace import TRACER as _TRACER
from .btree import BTree, LeafCursor
from .bufferpool import BufferPool
from .delta_log import BWAccumulator, DeltaAccumulator
from .dpt import DPT, LogicalDPTBuilder, build_dpt_logical
from .log import LogManager
from .records import (LSN, NULL_LSN, NULL_PID, PID, CLRRec, DeltaRec, LogRec,
                      RecKind, RSSPRec, SMORec, UpdateRec)
from .storage import PageStore

# batched-apply span walks: how well the leaf-resident cursor amortizes
# traversal (records/spans ~ ops per traversal)
_C_AB_CALLS = _metrics.counter("dc.apply_batch.calls")
_C_AB_RECORDS = _metrics.counter("dc.apply_batch.records")
_C_AB_SPANS = _metrics.counter("dc.apply_batch.leaf_spans")


# length-prefixed table headers, memoized: make_key is on every logical
# hot path (apply, redo, batch sort) and the prefix only depends on the
# table name (bounded set)
_TABLE_PREFIX: dict = {}


def make_key(table: str, key: bytes) -> bytes:
    p = _TABLE_PREFIX.get(table)
    if p is None:
        t = table.encode()
        p = _TABLE_PREFIX[table] = struct.pack("<H", len(t)) + t
    return p + key


def rec_key(rec) -> bytes:
    """Composite tree key of an Update/CLR record, memoized on the record
    (``rec.ck``) — the identity never changes after append and every
    redo / apply / batch-sort pass needs it."""
    ck = rec.ck
    if ck is None:
        ck = rec.ck = make_key(rec.table, rec.key)
    return ck


def split_key(composite: bytes) -> tuple[str, bytes]:
    """Inverse of make_key: (table, key) from a composite tree key."""
    (tlen,) = struct.unpack_from("<H", composite)
    return composite[2:2 + tlen].decode(), composite[2 + tlen:]


def table_bounds(table: str) -> tuple[bytes, Optional[bytes]]:
    """Composite-key interval [lo, hi) covering every key of ``table``
    (hi None = end of key space).  hi is the prefix incremented with
    carry: the smallest byte string sorting after every prefix extension."""
    prefix = make_key(table, b"")
    hi = bytearray(prefix)
    while hi and hi[-1] == 0xFF:
        hi.pop()
    if not hi:
        return prefix, None
    hi[-1] += 1
    return prefix, bytes(hi)


def table_range(table: str, lo: Optional[bytes] = None,
                hi: Optional[bytes] = None) -> tuple[bytes, Optional[bytes]]:
    """Composite-key interval [lo_c, hi_c) for ``table`` keys in [lo, hi),
    where None means the table edge on that side."""
    t_lo, t_hi = table_bounds(table)
    lo_c = make_key(table, lo) if lo is not None else t_lo
    hi_c = make_key(table, hi) if hi is not None else t_hi
    return lo_c, hi_c


@dataclass
class RedoStats:
    submitted: int = 0
    redone: int = 0
    skipped_dpt: int = 0       # pruned without fetching the page (DPT miss / rLSN)
    skipped_plsn: int = 0      # page fetched, pLSN said no
    tail_ops: int = 0          # ops past the last Delta record (basic fallback)


class DataComponent:
    def __init__(self, store: PageStore, log: LogManager, cache_pages: int = 1 << 30,
                 delta_mode: str = "paper", side_by_side: bool = True,
                 page_size: int = None, retry=None):
        """delta_mode: 'paper' | 'perfect' (D.1) | 'reduced' (D.2) | 'off'.
        side_by_side: also maintain SQL-Server BW records on the same log so
        physiological recovery can be compared on a common log (Section 5.1).
        page_size: stable-page byte size — replicas may differ (Section 1.1).
        retry: a ``faults.RetryPolicy`` the buffer pool uses to absorb
        transient page-IO failures (page blobs may live on a remote
        ``MediaBackend``); None keeps every backend error first-throw."""
        from .pages import PAGE_SIZE
        self.page_size = page_size or PAGE_SIZE
        self.store = store
        self.log = log
        self.pool = BufferPool(store, log, cache_pages, retry=retry)
        self.btree = BTree(self.pool, log, page_size=self.page_size)
        self.delta_mode = delta_mode
        self.delta: Optional[DeltaAccumulator] = None
        if delta_mode != "off":
            self.delta = DeltaAccumulator(log, perfect=(delta_mode == "perfect"),
                                          reduced=(delta_mode == "reduced"))
            self.pool.on_update.append(self.delta.note_update)
            self.pool.on_flush.append(self.delta.note_flush)
        self.bw: Optional[BWAccumulator] = None
        if side_by_side:
            self.bw = BWAccumulator(log)
            self.pool.on_flush.append(self.bw.note_flush)
        self.n_delta_recs = 0
        self.n_bw_recs = 0
        # recovery artifacts
        self.dpt: Optional[DPT] = None
        self.last_delta_tc_lsn: LSN = NULL_LSN
        self.pf_list: list[PID] = []
        self.redo_stats = RedoStats()
        # first PID allocated *during* recovery redo (set by ``recover``):
        # pages at or above it were (re-)born by redo-time splits and have
        # no DPT entry, so the DPT test must not prune ops that land there
        self.redo_pid_floor: PID = 1 << 62

    # ----------------------------------------------------------- bootstrap
    def bootstrap(self) -> None:
        self.btree.create()

    def _store_write(self, page) -> None:
        """Direct-to-store page write (bulk paths that bypass the pool),
        through the pool's retry policy when one is configured — a bulk
        load should survive the same transient blips a flush does."""
        if self.pool.retry is None:
            self.store.write_page(page)
        else:
            self.pool.retry.call(self.store.write_page, page)

    def bulk_build(self, items: list[tuple[bytes, bytes]]) -> None:
        """Offline index build (initial load / restore-from-backup): packs
        sorted records bottom-up straight into stable storage, no logging.
        Must be followed by a checkpoint before the workload starts."""
        from .pages import SLOT_OVERHEAD, empty_internal, empty_leaf
        # The build bypasses the pool and writes pages straight to stable
        # storage; WAL still demands that no page outrun the log, so force
        # the log to its end before the first write_page below.
        self.log.flush()
        assert self.log.stable_lsn >= self.log.end_lsn, \
            "bulk_build requires a fully stable log (WAL)"
        items = sorted(items)
        fill = int(self.page_size * 0.7)

        # ---- leaf level: (max_key, pid) per leaf, contiguous PIDs
        leaves: list[tuple[bytes, PID]] = []
        cur = empty_leaf(self.store.allocate_pid())
        size = 0
        for k, v in items:
            rec_sz = len(k) + len(v) + SLOT_OVERHEAD
            if size + rec_sz > fill and cur.records:
                leaves.append((max(cur.records), cur.pid))
                cur.invalidate_sorted()
                self._store_write(cur)
                cur = empty_leaf(self.store.allocate_pid())
                size = 0
            cur.records[k] = v
            size += rec_sz
        leaves.append((max(cur.records) if cur.records else b"", cur.pid))
        cur.invalidate_sorted()
        self._store_write(cur)

        # ---- internal levels: children[i] holds keys <= keys[i]
        level = leaves
        height = 1
        while len(level) > 1:
            height += 1
            nxt: list[tuple[bytes, PID]] = []
            node = empty_internal(self.store.allocate_pid())
            prev_mx: Optional[bytes] = None
            for mx, pid in level:
                if node.children and node.serialized_size() + len(mx) + 24 > fill:
                    nxt.append((prev_mx, node.pid))
                    self._store_write(node)
                    node = empty_internal(self.store.allocate_pid())
                if node.children:
                    node.keys.append(prev_mx)
                node.children.append(pid)
                node.invalidate_sorted()
                prev_mx = mx
            nxt.append((prev_mx, node.pid))
            self._store_write(node)
            level = nxt
        self.btree.root_pid = level[0][1]
        self.btree.height = height

    # ------------------------------------------------------- normal-mode ops
    def apply(self, rec: UpdateRec) -> None:
        """Execute a logical operation; stamps the touched PID back onto the
        (shared prototype) log record so the physiological path can use it."""
        k = rec_key(rec)
        if rec.op == RecKind.DELETE:
            rec.pid = self.btree.delete(k, rec.lsn)
        else:
            rec.pid = self.btree.put(k, rec.after, rec.lsn)
        if self.delta is not None and rec.lsn > self.delta.applied_lsn:
            self.delta.applied_lsn = rec.lsn

    def read_leaf(self, table: str, key: bytes
                  ) -> tuple[bytes, PID, Optional[bytes]]:
        """``read`` that also returns the composite key and the PID of the
        leaf that owns it, so that ``put_in_leaf`` applies the update
        without a second traversal."""
        ck = make_key(table, key)
        pid = self.btree.find_leaf(ck)
        return ck, pid, self.pool.get(pid).get(ck)

    def put_in_leaf(self, rec: UpdateRec, pid: PID) -> None:
        """``apply`` for an update whose key ``read_leaf`` found in leaf
        ``pid``, with no change to the tree since.  The page is fetched
        again, so an eviction in between is harmless; a put that would
        overflow the leaf splits through the ordinary ``btree.put``.
        Stamps ``rec.pid`` as ``apply`` does."""
        ck, after, lsn = rec.ck, rec.after, rec.lsn
        page = self.pool.get(pid)
        # a value no longer than the one it replaces always fits
        if (rec.before is not None and len(after) <= len(rec.before)) \
                or not page.would_overflow(ck, after, self.page_size):
            page.put(ck, after, lsn)
            self.pool.mark_dirty(pid, lsn)
            rec.pid = pid
        else:
            rec.pid = self.btree.put(ck, after, lsn)
        if self.delta is not None and lsn > self.delta.applied_lsn:
            self.delta.applied_lsn = lsn

    def apply_clr(self, rec: CLRRec) -> None:
        k = rec_key(rec)
        if rec.op == RecKind.DELETE or rec.after is None:
            rec.pid = self.btree.delete(k, rec.lsn)
        else:
            rec.pid = self.btree.put(k, rec.after, rec.lsn)

    def read(self, table: str, key: bytes) -> Optional[bytes]:
        return self.btree.get(make_key(table, key))

    def scan_range(self, table: str, lo: Optional[bytes] = None,
                   hi: Optional[bytes] = None,
                   limit: Optional[int] = None) -> list[tuple[bytes, bytes]]:
        """Ordered read of ``table`` keys in [lo, hi) (None = table edge)."""
        lo_c, hi_c = table_range(table, lo, hi)
        return [(split_key(k)[1], v)
                for k, v in self.btree.range_items(lo_c, hi_c, limit)]

    # --------------------------------------------------------- control ops
    def eosl(self, elsn: LSN) -> None:
        """EOSL: TC's end-of-stable-log.  With the integrated prototype log the
        pool reads stability directly; kept for interface fidelity."""
        # (Deuteronomy-mode DCs would store elsn and cap page flushes by it.)
        return None

    def emit_trackers(self) -> None:
        """Write a Delta-log record, then a BW record ('exactly before', 5.2)."""
        if self.delta is not None and self.delta.emit() is not None:
            self.n_delta_recs += 1
        if self.bw is not None and self.bw.emit() is not None:
            self.n_bw_recs += 1

    def rssp(self, rssp_lsn: LSN) -> LSN:
        """RSSP: flush every page dirtied by ops <= rssp_lsn (penultimate
        checkpoint scheme via the generation bit), record the DC's meta +
        rsspLSN on the log.  Returns the RSSP record's LSN."""
        self.pool.begin_checkpoint_flush()
        self.emit_trackers()
        rec = RSSPRec(rssp_lsn=rssp_lsn, root_pid=self.btree.root_pid,
                      next_pid=self.store.next_pid, height=self.btree.height)
        lsn = self.log.append(rec)
        self.log.set_master(rssp_rec=lsn)
        return lsn

    def maybe_background_flush(self, max_pages: int) -> int:
        return self.pool.flush_some(max_pages)

    # ------------------------------------------------------------ DC recovery
    def recover(self, scan_from: LSN, rssp_lsn: LSN = NULL_LSN,
                build_dpt: bool = True, preload_index: bool = False) -> None:
        """DC-side recovery, before any TC redo (Section 4.2):
          1. adopt meta from the master RSSP record,
          2. replay SMOs so the B-tree is well-formed,
          3. build the DPT + PF-list from Delta-log records,
          4. optionally bulk-preload all index pages (Appendix A.1)."""
        m = self.log.master
        if m.rssp_rec_lsn != NULL_LSN:
            rssp = self.log.record(m.rssp_rec_lsn)
            assert isinstance(rssp, RSSPRec)
            self.btree.root_pid = rssp.root_pid
            self.btree.height = rssp.height
            self.store.set_next_pid(rssp.next_pid)
        # one fused scan serves both DC recovery jobs: SMO replay (from
        # ``scan_from``) and DPT construction (Delta records above
        # ``rssp_lsn``) — this used to be two full passes over the log.
        dpt_builder = LogicalDPTBuilder(rssp_lsn) if build_dpt else None
        for rec in self.log.scan(min(scan_from, rssp_lsn + 1)):
            if isinstance(rec, SMORec):
                if rec.lsn >= scan_from:
                    self.btree.redo_smo(rec)
            elif dpt_builder is not None and isinstance(rec, DeltaRec) \
                    and rec.lsn > rssp_lsn:
                dpt_builder.feed(rec)
        if dpt_builder is not None:
            self.dpt, self.last_delta_tc_lsn, self.pf_list = \
                dpt_builder.finish()
        self.redo_pid_floor = self.store.next_pid
        if preload_index:
            pids = self.index_pids_from_meta()
            if self.pool.iosim is not None:
                self.pool.iosim.prefetch(pids, contiguous=True)
            for pid in pids:
                self.pool.get(pid)

    def index_pids_from_meta(self) -> list[PID]:
        return self.btree.index_pids()

    # ---------------------------------------------------------- redo service
    def redo_basic(self, rec: UpdateRec) -> None:
        """Algorithm 2: traverse, fetch, pLSN test, maybe re-execute."""
        self.redo_stats.submitted += 1
        k = rec_key(rec)
        pid = self.btree.find_leaf(k)
        page = self.pool.get(pid)
        if rec.lsn <= page.plsn:
            self.redo_stats.skipped_plsn += 1
            return
        self._reexecute(rec, k, pid)

    def redo_with_dpt(self, rec: UpdateRec) -> None:
        """Algorithm 5: DPT-assisted logical redo with log-tail fallback."""
        self.redo_stats.submitted += 1
        k = rec_key(rec)
        pid = self.btree.find_leaf(k)
        if rec.lsn <= self.last_delta_tc_lsn:
            e = self.dpt.find(pid)
            if e is None or rec.lsn < e.rlsn:
                self.redo_stats.skipped_dpt += 1
                return
        else:
            self.redo_stats.tail_ops += 1
        page = self.pool.get(pid)
        if rec.lsn <= page.plsn:
            self.redo_stats.skipped_plsn += 1
            return
        self._reexecute(rec, k, pid)

    # ----------------------------------------------------- batched apply
    def apply_batch(self, recs, *, mode: str = "execute",
                    cursor: Optional[LeafCursor] = None) -> int:
        """Batched logical apply: sort a window of records by
        ``(composite key, lsn)`` and walk it with a leaf-resident cursor,
        amortizing index traversal across consecutive ops to the same leaf
        (the paper's Section 5 locality optimizations, made logical).
        Returns the number of ops executed (non-skipped).

        Modes select the redo tests:

          execute  replica / restore apply — no tests, every op executes
                   (the records are committed absolute after-images that
                   were just appended to the local log);
          basic    batched Log0 — page-LSN idempotence test only;
          dpt      batched Log1/Log2 — DPT prune + page-LSN test.

        Reordering within the window is sound because per-key LSN order is
        preserved (the sort is keyed on (key, lsn)) and ops carry absolute
        after-images: keys commute, re-execution is idempotent.  The
        page-LSN test, however, must not compare against a pLSN advanced
        by *this* window's out-of-order ops — so each leaf "group" captures
        its pre-window pLSN on entry and tests the whole group against
        that base.  A split during the group inherits the leaf's data
        state (and pLSN), so keys still inside the group's original
        separator interval keep the captured base; a key beyond it enters
        a fresh group and reads a fresh (window-untouched — keys ascend)
        base.  Across windows the test is exact again: windows partition
        the log in LSN order, so a later window's LSNs all exceed any pLSN
        this one can write.

        In dpt mode, a missing DPT entry prunes only pages that existed
        when redo began (``redo_pid_floor``): pages born from redo-time
        splits are absent from the DPT by construction, and — unlike the
        per-record LSN-order path, whose repeat-of-history guarantees
        their images — a key-ordered batch may reach them before their
        content does, so they must repeat history unconditionally."""
        # ``recs`` must arrive in stream (LSN) order — every caller is a
        # log-ordered window — so the stable sort on the composite key
        # alone preserves per-key LSN order without comparing LSNs
        rs = sorted(recs, key=rec_key)
        ks = [r.ck for r in rs]           # parallel key array for the span
        # bisects (rec_key above filled every ck)
        cur = cursor if cursor is not None else self.btree.cursor()
        stats = self.redo_stats
        pool = self.pool
        if mode not in ("execute", "basic", "dpt"):
            raise ValueError(f"unknown apply_batch mode {mode!r}")
        test_plsn = mode != "execute"
        dpt_mode = mode == "dpt"
        delta = self.delta if mode == "execute" else None
        dpt_find = self.dpt.find if dpt_mode else None
        tc_lsn = self.last_delta_tc_lsn
        floor = self.redo_pid_floor
        delete_op = RecKind.DELETE
        page_size = self.page_size
        ALWAYS = 1 << 62          # group rlsn: no DPT entry, pre-redo page
        NEVER = -1                # group rlsn: redo-born page, never prune
        bis_right = bisect.bisect_right

        # local tallies, folded into redo_stats once at the end — attribute
        # read-modify-writes per record are measurable at window scale
        sub = skd = skp = red = tails = executed = spans = 0

        # The sorted window is processed leaf *span* at a time: one
        # traversal, one DPT consult, one page fetch and one pre-window
        # pLSN ("base") capture per span; the span end comes from one
        # bisect against the leaf's upper separator, so a pruned record —
        # the common case — costs two integer comparisons
        n = len(ks)
        i = 0
        carry = False                     # split mid-span: carry the base
        carry_hi: Optional[bytes] = None
        carry_base: LSN = NULL_LSN
        while i < n:
            spans += 1
            k0 = ks[i]
            pid = cur.seek(k0)
            ghi = cur.hi
            j = n if ghi is None else bis_right(ks, ghi, i)
            page = None
            if carry and not (carry_hi is not None and k0 > carry_hi):
                base, base_valid = carry_base, True
            else:
                carry = False
                base, base_valid = NULL_LSN, False
            if dpt_mode:
                e = dpt_find(pid)
                grlsn = e.rlsn if e is not None else \
                    (ALWAYS if pid < floor else NEVER)
            if test_plsn:
                sub += j - i
            idx = i
            split = False
            while idx < j:
                rec = rs[idx]
                lsn = rec.lsn
                idx += 1
                if dpt_mode:
                    if lsn <= tc_lsn:
                        if lsn < grlsn:
                            skd += 1
                            continue
                    else:
                        tails += 1
                if page is None:
                    # pinned for the span: a bounded pool may otherwise
                    # evict the frame mid-mutation (the split path below
                    # fetches index pages through the same pool)
                    page = pool.get(pid, pin=True)
                    if not base_valid:
                        base = page.plsn  # pre-window pLSN of this leaf
                        base_valid = True
                if test_plsn:
                    if lsn <= base:
                        skp += 1
                        continue
                    red += 1
                after = rec.after
                if rec.op == delete_op or after is None:
                    page.delete(rec.ck, lsn)
                    pool.mark_dirty(pid, lsn)
                    rec.pid = pid
                elif not page.would_overflow(rec.ck, after, page_size):
                    page.put(rec.ck, after, lsn)
                    pool.mark_dirty(pid, lsn)
                    rec.pid = pid
                else:
                    # split path: repeat history through the ordinary put;
                    # separators moved under the cursor, so the rest of the
                    # span re-seeks.  Keys still inside this span's original
                    # interval keep its captured base (split leaves inherit
                    # data state + pLSN); the carry interval is pinned at
                    # the first split so later sub-splits cannot narrow it
                    rec.pid = self.btree.put(rec.ck, after, lsn)
                    cur.invalidate()
                    if test_plsn:
                        if not carry:
                            carry, carry_hi, carry_base = True, ghi, base
                        sub -= j - idx    # tail re-counts in the next span
                    executed += 1
                    if delta is not None and lsn > delta.applied_lsn:
                        delta.applied_lsn = lsn
                    split = True
                    break
                executed += 1
                if delta is not None and lsn > delta.applied_lsn:
                    delta.applied_lsn = lsn
            if page is not None:
                pool.unpin(pid)
            consumed = (idx if split else j) - i
            if consumed > 1:
                cur.reuses += consumed - 1    # ops that paid no traversal
            i = idx if split else j
        stats.submitted += sub
        stats.skipped_dpt += skd
        stats.skipped_plsn += skp
        stats.redone += red
        stats.tail_ops += tails
        _C_AB_CALLS.inc()
        _C_AB_RECORDS.inc(n)
        _C_AB_SPANS.inc(spans)
        if _TRACER.enabled:
            _TRACER.event("dc.apply_batch", records=n, spans=spans,
                          mode=mode, executed=executed)
        return executed

    def _reexecute(self, rec, k: bytes, pid: PID) -> None:
        self.redo_stats.redone += 1
        page = self.pool.get(pid)
        if rec.op == RecKind.DELETE or rec.after is None:
            page.delete(k, rec.lsn)
            self.pool.mark_dirty(pid, rec.lsn)
        elif not page.would_overflow(k, rec.after, self.page_size):
            page.put(k, rec.after, rec.lsn)
            self.pool.mark_dirty(pid, rec.lsn)
        else:
            # repeat history: the original insert split here too
            self.btree.put(k, rec.after, rec.lsn)
