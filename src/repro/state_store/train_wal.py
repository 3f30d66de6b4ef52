"""TrainWAL: the paper's logical recovery as the framework's fault-tolerance
layer.

Roles (mirroring DESIGN.md's mapping):
  TC  = the training coordinator: logs *logical* records — per-step metadata
        (step id, data cursor) every step, and state-chunk after-images every
        ``chunk_interval`` steps (an incremental, fuzzy checkpoint).  It
        never knows which page a chunk lives on.
  DC  = the record store: pages + B-tree + buffer pool; flushes dirty pages
        lazily (``bg_flush_pages`` per step — continuous checkpointing, no
        stop-the-world), emits Delta-log records, answers RSSP.

Recovery after a crash:
  1. DC recovery + DPT-pruned logical redo (Algorithm 5) restores the record
     store to the last *committed* state — cost proportional to dirty pages,
     NOT total state size (the paper's claim, now for training state).
  2. The trailing steps (after the last chunk txn) are redone by *replay*:
     the data pipeline is counter-based, so the logged cursor + deterministic
     train_step reproduce them exactly — the training-world analogue of the
     "tail of the log" falling back to op re-execution.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core import Database, Strategy, recover
from repro.core.dc import make_key
from repro.obs import metrics as obs_metrics
from repro.obs.trace import TRACER

from .chunking import CHUNK_ELEMS, records_to_tree, tree_to_records

META_TABLE = "meta"
STATE_TABLE = "state"
_META = struct.Struct("<qqq")      # step, cursor, state_step
_C_LOG_BYTES = obs_metrics.counter("log.bytes_appended")


@dataclass
class WALConfig:
    chunk_interval: int = 10       # steps between state-chunk transactions
    ckpt_interval: int = 50        # steps between RSSP checkpoints
    bg_flush_pages: int = 8        # fuzzy-flush budget per step
    cache_pages: int = 4096
    chunk_elems: int = 8192        # 32 KiB fp32 / 16 KiB bf16 per record
    tracker_interval: int = 200    # updates between Delta-log records
    # blob-sized pages: checkpoint stores use large blocks; several chunk
    # records fit one page (and the replica example restores the same log
    # into a store with a different page_size)
    page_size: int = 65536
    strategy: Strategy = Strategy.LOG2


class TrainWAL:
    def __init__(self, cfg: WALConfig | None = None):
        self.cfg = cfg or WALConfig()
        self.db = Database(cache_pages=self.cfg.cache_pages,
                           tracker_interval=self.cfg.tracker_interval,
                           page_size=self.cfg.page_size)
        self.db.bootstrap_empty()
        self._bootstrapped = False
        self._digests: dict[bytes, int] = {}     # chunk key -> crc32

    # -------------------------------------------------------------- logging
    def log_state(self, step: int, cursor: int, state: Any,
                  delta_only: bool = True) -> None:
        """One transaction: changed state chunks + the metadata record.
        ``delta_only`` skips chunks whose bytes did not change since the last
        log_state (embedding rows / routed experts / frozen towers) — the
        update stream becomes sparse, which is exactly the locality the
        paper's DPT machinery exploits.  Commit forces the WAL.

        Spans (live only under ``obs.enable()`` or a profiler session):
        ``wal.log_state`` over ``wal.save.wait``, the per-leaf spans of
        ``tree_to_records``, ``wal.save.commit`` and ``pool.bg_flush``."""
        import zlib
        with TRACER.span("wal.log_state", step=step) as sp:
            with TRACER.span("wal.save.wait"):
                jax.block_until_ready(state)
            log0 = _C_LOG_BYTES.value
            txn = self.db.tc.begin()
            n_upd = n_skip = state_bytes = 0
            for key, value in tree_to_records(state, self.cfg.chunk_elems):
                state_bytes += len(value)
                if delta_only and self._bootstrapped:
                    dig = zlib.crc32(value)
                    if self._digests.get(key) == dig:
                        n_skip += 1
                        continue
                    self._digests[key] = dig
                elif delta_only:
                    self._digests[key] = zlib.crc32(value)
                if self._bootstrapped:
                    self.db.tc.update(txn, STATE_TABLE, key, value)
                else:
                    self.db.tc.insert(txn, STATE_TABLE, key, value)
                n_upd += 1
                if n_upd % self.cfg.tracker_interval == 0:
                    self.db.dc.emit_trackers()
            with TRACER.span("wal.save.commit"):
                meta = _META.pack(step, cursor, step)
                if self._bootstrapped:
                    self.db.tc.update(txn, META_TABLE, b"latest", meta)
                else:
                    self.db.tc.insert(txn, META_TABLE, b"latest", meta)
                self.db.tc.commit(txn)
                self._bootstrapped = True
                self.db.dc.emit_trackers()
                # keep tracker records themselves durable (group-committed)
                self.db.log.flush()
            self.db.dc.maybe_background_flush(self.cfg.bg_flush_pages)
            sp.set(chunks_written=n_upd, chunks_skipped=n_skip,
                   log_bytes=_C_LOG_BYTES.value - log0,
                   state_bytes=state_bytes)

    def log_step_meta(self, step: int, cursor: int, state_step: int) -> None:
        """Per-step heartbeat: step id + data cursor (tiny txn)."""
        with TRACER.span("wal.log_step_meta", step=step):
            txn = self.db.tc.begin()
            meta = _META.pack(step, cursor, state_step)
            self.db.tc.update(txn, META_TABLE, b"latest", meta)
            self.db.tc.commit(txn)
            self.db.dc.maybe_background_flush(self.cfg.bg_flush_pages)

    def maybe_checkpoint(self, step: int) -> bool:
        if step % self.cfg.ckpt_interval == 0 and step > 0:
            self.db.checkpoint()
            return True
        return False

    # ------------------------------------------------------------- recovery
    def crash(self):
        return self.db.crash()

    @classmethod
    def restore(cls, image, template_state: Any, wal_cfg: WALConfig | None = None,
                strategy: Optional[Strategy] = None):
        """Recover the record store, rebuild the state pytree, return
        (wal, state, step, cursor, state_step, recovery_stats).

        Spans: ``wal.restore`` over ``recover``, ``wal.restore.scan`` and
        the per-leaf spans of ``records_to_tree``."""
        cfg = wal_cfg or WALConfig()
        with TRACER.span("wal.restore"):
            db, stats = recover(image, strategy or cfg.strategy,
                                cache_pages=cfg.cache_pages,
                                page_size=cfg.page_size)
            raw_meta = db.dc.read(META_TABLE, b"latest")
            assert raw_meta is not None, \
                "no committed training state to restore"
            step, cursor, state_step = _META.unpack(raw_meta)

            with TRACER.span("wal.restore.scan"):
                records: dict[bytes, bytes] = {}
                prefix = make_key(STATE_TABLE, b"")
                for k, v in db.scan_all():
                    if k.startswith(prefix):
                        records[k[len(prefix):]] = v
            state = records_to_tree(template_state, records, cfg.chunk_elems)

        wal = cls.__new__(cls)
        wal.cfg = cfg
        wal.db = db
        wal._bootstrapped = True
        wal._digests = {}          # rebuilt lazily; first post-restore
        return wal, state, step, cursor, state_step, stats


# ----------------------------------------------------------------- trainer
def train_with_recovery(*, train_step: Callable, init_state: Any,
                        batch_at: Callable[[int], Any], n_steps: int,
                        wal: TrainWAL, start_step: int = 0,
                        log_every: int = 0,
                        on_step: Optional[Callable] = None):
    """Generic fault-tolerant loop: the full state is logged every
    chunk_interval steps; every step logs the (step, cursor) heartbeat."""
    state = init_state
    state_step = start_step
    for step in range(start_step, n_steps):
        batch = batch_at(step)
        state, metrics = train_step(state, batch)
        if (step + 1) % wal.cfg.chunk_interval == 0:
            wal.log_state(step + 1, step + 1, state)
            state_step = step + 1
        else:
            wal.log_step_meta(step + 1, step + 1, state_step)
        wal.maybe_checkpoint(step + 1)
        if on_step is not None:
            on_step(step, state, metrics)
        if log_every and (step + 1) % log_every == 0:
            print(f"  step {step + 1}: loss={float(metrics['loss']):.4f}")
    return state


def resume_from_crash(image, template_state, *, train_step, batch_at,
                      wal_cfg: WALConfig | None = None,
                      strategy: Optional[Strategy] = None):
    """Restore + replay the tail: chunks give state at ``state_step``; the
    heartbeat says training reached ``step``; deterministic replay re-executes
    (state_step, step] to reproduce the exact pre-crash state."""
    wal, state, step, cursor, state_step, stats = TrainWAL.restore(
        image, template_state, wal_cfg, strategy)
    for s in range(state_step, step):
        state, _ = train_step(state, batch_at(s))
    return wal, state, step, stats
