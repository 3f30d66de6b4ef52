"""State store: chunking round-trips + end-to-end crash/restore of a real
(tiny) training run — the paper's technique as training fault tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Strategy
from repro.models import build_model
from repro.optim import AdamWConfig, apply_updates, init_opt_state
from repro.state_store import (TrainWAL, WALConfig, records_to_tree,
                               resume_from_crash, train_with_recovery,
                               tree_to_records)


def test_chunking_roundtrip_mixed_dtypes():
    tree = {
        "a": jnp.arange(100_000, dtype=jnp.float32).reshape(100, 1000),
        "b": {"w": jnp.ones((33,), jnp.bfloat16) * 1.5,
              "s": jnp.asarray(7, jnp.int32)},
    }
    records = dict(tree_to_records(tree, chunk_elems=4096))
    assert len(records) > 25            # 'a' split into many chunks
    out = records_to_tree(tree, records, chunk_elems=4096)
    assert jnp.array_equal(out["a"], tree["a"])
    assert jnp.array_equal(out["b"]["w"], tree["b"]["w"])
    assert out["b"]["s"] == 7
    assert out["b"]["w"].dtype == jnp.bfloat16


def _tiny_trainer():
    cfg = get_config("llama3.2-3b").reduced()
    api = build_model(cfg)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=100)
    params = api.init(jax.random.PRNGKey(0))
    state0 = {"params": params, "opt": init_opt_state(params)}

    @jax.jit
    def train_step(state, batch):
        loss, grads = jax.value_and_grad(api.loss)(state["params"], batch)
        new_p, new_opt, m = apply_updates(state["params"], grads,
                                          state["opt"], opt_cfg)
        return {"params": new_p, "opt": new_opt}, {"loss": loss, **m}

    def batch_at(idx):
        key = jax.random.fold_in(jax.random.PRNGKey(42), idx)
        return {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size,
                                             dtype=jnp.int32)}
    return train_step, state0, batch_at


def _trees_equal(a, b, atol=0.0):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol)


@pytest.mark.parametrize("strategy", [Strategy.LOG1, Strategy.LOG2,
                                      Strategy.SQL1])
def test_crash_restore_replay_exact(strategy):
    train_step, state0, batch_at = _tiny_trainer()
    wal_cfg = WALConfig(chunk_interval=4, ckpt_interval=8, bg_flush_pages=4,
                        cache_pages=512, chunk_elems=8192,
                        tracker_interval=50)
    wal = TrainWAL(wal_cfg)
    wal.log_state(0, 0, state0)

    n_steps = 11                        # crash mid-interval: tail replay needed
    final = train_with_recovery(train_step=train_step, init_state=state0,
                                batch_at=batch_at, n_steps=n_steps, wal=wal)
    image = wal.crash()

    wal2, restored, step, stats = resume_from_crash(
        image, state0, train_step=train_step, batch_at=batch_at,
        wal_cfg=wal_cfg, strategy=strategy)
    assert step == n_steps
    # bf16 params + f32 opt state replayed deterministically => exact
    _trees_equal(restored, final)
    assert stats.redo.submitted > 0


def test_restore_continues_training():
    train_step, state0, batch_at = _tiny_trainer()
    wal_cfg = WALConfig(chunk_interval=3, ckpt_interval=6, bg_flush_pages=2,
                        cache_pages=256, chunk_elems=8192)
    wal = TrainWAL(wal_cfg)
    wal.log_state(0, 0, state0)
    # run 7 steps, crash, restore, run 3 more == straight-through 10 steps
    mid = train_with_recovery(train_step=train_step, init_state=state0,
                              batch_at=batch_at, n_steps=7, wal=wal)
    image = wal.crash()
    wal2, restored, step, _ = resume_from_crash(
        image, state0, train_step=train_step, batch_at=batch_at,
        wal_cfg=wal_cfg)
    resumed = train_with_recovery(train_step=train_step, init_state=restored,
                                  batch_at=batch_at, n_steps=10, wal=wal2,
                                  start_step=step)
    straight = state0
    for s in range(10):
        straight, _ = train_step(straight, batch_at(s))
    _trees_equal(resumed, straight)


def test_recovery_cost_scales_with_dirty_pages_not_state_size():
    """The paper's core claim transplanted: with the DPT, redo fetches ~dirty
    pages, NOT every page the log mentions.  The workload is sparse (an
    embedding-table-like state where each step touches a few rows) — the
    regime DESIGN.md documents as the technique's sweet spot; a dense-AdamW
    state dirties everything every step and the DPT honestly degenerates."""
    import numpy as np
    rng = np.random.default_rng(0)
    n_rows, row_elems = 400, 2048          # ~3.2 MB "embedding table"
    state = {"table": jnp.asarray(rng.normal(size=(n_rows, row_elems)),
                                  jnp.float32)}

    wal_cfg = WALConfig(chunk_interval=1, ckpt_interval=100,
                        bg_flush_pages=16, cache_pages=2048,
                        chunk_elems=row_elems, tracker_interval=10)
    wal = TrainWAL(wal_cfg)
    wal.log_state(0, 0, state)
    wal.db.checkpoint()
    arr = np.array(state["table"])
    for step in range(1, 25):
        rows = rng.integers(0, n_rows, size=6)     # sparse touch
        arr[rows] += rng.normal(size=(len(rows), row_elems)).astype(np.float32)
        state = {"table": jnp.asarray(arr)}
        wal.log_state(step, step, state)           # delta_only: 6 chunks/step
    image = wal.crash()
    from repro.core import recover
    _, s_log0 = recover(image, Strategy.LOG0, cache_pages=2048,
                        page_size=wal_cfg.page_size)
    _, s_log1 = recover(image, Strategy.LOG1, cache_pages=2048,
                        page_size=wal_cfg.page_size)
    assert s_log1.redo.skipped_dpt > 0
    assert s_log1.io.sync_reads < s_log0.io.sync_reads, \
        (s_log1.io.sync_reads, s_log0.io.sync_reads)


# --------------------------------- the update save's one-traversal update
import dataclasses

from repro import obs
from repro.core import Database, TruncatedLogError
from repro.core.records import BWRec, DeltaRec, RecKind, UpdateRec
from repro.state_store.train_wal import _META, META_TABLE, STATE_TABLE

CH = 1024                               # elements per chunk in these tests


def _leaves(k, n_list=0, bf16=True, long=3):
    """A state of mixed leaves; ``k`` shifts every value, so two values of
    ``k`` differ in every chunk."""
    st = {"w": jnp.arange(long * CH + 5, dtype=jnp.float32) + k,
          "s": jnp.asarray(3 + k, jnp.int32)}
    if bf16:
        st["b"] = ((jnp.arange(CH + 9, dtype=jnp.float32) + k) / 7
                   ).astype(jnp.bfloat16)
    if n_list:                          # flattened layers/0, 1, 2 ... 11;
        st["layers"] = [jnp.full((CH // 2 + i,), k + i, jnp.float32)
                        for i in range(n_list)]     # stored 0, 1, 10, 11, 2
    return st


def _poke(state, path, byte=0):
    """``state`` with one byte of one leaf flipped (a low mantissa bit)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    out = []
    for p, leaf in leaves:
        if jax.tree_util.keystr(p) == path:
            arr = np.array(leaf)
            arr.view(np.uint8).reshape(-1)[byte] ^= 1
            leaf = jnp.asarray(arr)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _read_then_apply(tc, txn, table, key, value):
    """An update as a read, then a logged and applied op: two traversals,
    the generic ``dc.apply`` path."""
    before = tc.dc.read(table, key)
    tc._log_op(txn, table, key, before, value, RecKind.UPDATE)


def _reference_update_save(wal, step, state, delta_only):
    """The update save as one read-then-apply update per chunk, with the
    Delta and BW records every ``tracker_interval`` updates."""
    tc, dc, cfg = wal.db.tc, wal.db.dc, wal.cfg
    txn = tc.begin()
    n = 0
    for key, value in tree_to_records(state, cfg.chunk_elems):
        if delta_only and dc.read(STATE_TABLE, key) == value:
            continue
        _read_then_apply(tc, txn, STATE_TABLE, key, value)
        n += 1
        if n % cfg.tracker_interval == 0:
            dc.emit_trackers()
    _read_then_apply(tc, txn, META_TABLE, b"latest",
                     _META.pack(step, step, step))
    tc.commit(txn)
    dc.emit_trackers()
    wal.db.log.flush()
    dc.maybe_background_flush(cfg.bg_flush_pages)


def _unordered(rec):
    """Delta and BW records compare as sets of pages."""
    if isinstance(rec, DeltaRec):
        return dataclasses.replace(rec, dirty_set=sorted(rec.dirty_set),
                                   written_set=sorted(rec.written_set))
    if isinstance(rec, BWRec):
        return dataclasses.replace(rec, written_set=sorted(rec.written_set))
    return rec


def _every_other_layer():
    """``_leaves(0, n_list=10)`` with layers 0, 2, 4, 6, 8 changed."""
    st = _leaves(0, n_list=10)
    new = _leaves(1, n_list=10)["layers"]
    st["layers"] = [new[i] if i % 2 == 0 else leaf
                    for i, leaf in enumerate(st["layers"])]
    return st


_EQUIV_CASES = {
    # name: (states before and saved, delta_only, tracker_interval)
    "dense": (lambda: (_leaves(0, bf16=False), _leaves(1, bf16=False)),
              False, 4),
    "sparse": (lambda: (_leaves(0, n_list=3), _poke(_poke(
        _leaves(0, n_list=3), "['w']", 4 * 2 * CH), "['layers'][1]")),
        True, 4),
    "fp32_bf16": (lambda: (_leaves(0), _leaves(1)), True, 3),
    "half_the_leaves": (lambda: (_leaves(0, n_list=10),
                                 _every_other_layer()), True, 3),
    "leaf_across_trackers": (lambda: (_leaves(0, long=11),
                                      _leaves(-1, long=11)), True, 5),
    "list_of_12_leaves": (lambda: (_leaves(0, n_list=12),
                                   _leaves(2, n_list=12)), True, 7),
}


@pytest.mark.parametrize("checkpoint_first", [False, True],
                         ids=["dirty_pages", "after_checkpoint"])
@pytest.mark.parametrize("case", list(_EQUIV_CASES))
def test_update_save_logs_what_update_per_chunk_logs(case, checkpoint_first):
    states, delta_only, interval = _EQUIV_CASES[case]
    before, saved = states()
    cfg = WALConfig(chunk_elems=CH, tracker_interval=interval,
                    cache_pages=4096, bg_flush_pages=2)
    wals = [TrainWAL(cfg), TrainWAL(cfg)]
    for w in wals:
        w.log_state(0, 0, before)
        if checkpoint_first:
            w.db.checkpoint()
    lo = wals[0].db.log.end_lsn
    assert lo == wals[1].db.log.end_lsn
    wals[0].log_state(1, 1, saved, delta_only=delta_only)
    _reference_update_save(wals[1], 1, saved, delta_only)
    got, want = wals[0].db.log, wals[1].db.log
    assert got.end_lsn == want.end_lsn
    n_upd = n_trackers = 0
    for lsn in range(lo + 1, want.end_lsn + 1):
        a, b = got.record(lsn), want.record(lsn)
        assert type(a) is type(b), lsn
        # an update: kind, key, before- and after-image, txn, prev_lsn, pid
        assert _unordered(a) == _unordered(b), lsn
        n_upd += isinstance(b, UpdateRec) and b.table == STATE_TABLE
        n_trackers += isinstance(b, DeltaRec)
    if case == "sparse":
        assert n_upd == 2
    elif case == "half_the_leaves":
        assert n_upd == 5
    else:
        assert n_upd == len(list(tree_to_records(saved, CH)))
        assert n_trackers > n_upd // interval >= 1
    assert dict(wals[0].db.scan_all()) == dict(wals[1].db.scan_all())


def test_update_matches_read_then_apply_through_splits():
    """Values that grow split leaves: ``tc.update`` falls back to the
    ordinary put there and leaves the log and tree that a read, then a
    logged and applied op, leave; ``skip_unchanged`` logs nothing."""
    rows = [(b"k%03d" % i, b"v" * 40) for i in range(60)]
    grown = [(k, bytes([65 + i % 26]) * (40 + 7 * i)) for i, (k, _) in
             enumerate(rows)]
    dbs = []
    for one_traversal in (True, False):
        db = Database(cache_pages=256, page_size=1024)
        db.bootstrap_empty()
        db.run_txn([("insert", "t", k, v) for k, v in rows])
        txn = db.tc.begin()
        for k, v in grown:
            if one_traversal:
                assert db.tc.update(txn, "t", k, v, skip_unchanged=True)
            else:
                _read_then_apply(db.tc, txn, "t", k, v)
        if one_traversal:
            assert not any(db.tc.update(txn, "t", k, v, skip_unchanged=True)
                           for k, v in grown[:5])
        db.tc.commit(txn)
        dbs.append(db)
    a, b = dbs[0].log, dbs[1].log
    assert a.end_lsn == b.end_lsn
    assert [a.record(i) for i in range(1, a.end_lsn + 1)] == \
        [b.record(i) for i in range(1, b.end_lsn + 1)]
    assert dbs[0].dc.btree.smo_count == dbs[1].dc.btree.smo_count > 0
    assert dbs[0].scan_all() == dbs[1].scan_all()
    assert dict(dbs[0].dc.scan_range("t")) == dict(grown)


@pytest.mark.parametrize("strategy", [Strategy.LOG0, Strategy.LOG1,
                                      Strategy.LOG2])
def test_crash_inside_an_update_save_recovers_the_last_commit(
        strategy, monkeypatch):
    cfg = WALConfig(chunk_elems=CH, tracker_interval=3, cache_pages=4096,
                    bg_flush_pages=4)
    wal = TrainWAL(cfg)
    committed = _leaves(1, n_list=3)
    wal.log_state(0, 0, _leaves(0, n_list=3))
    wal.log_state(1, 1, committed)
    want = dict(tree_to_records(committed, CH))
    tc = wal.db.tc
    update = tc.update
    seen = {"calls": 0}

    def crash_after_seven(txn, table, key, value, skip_unchanged=False):
        out = update(txn, table, key, value, skip_unchanged)
        seen["calls"] += 1
        if seen["calls"] == 7:                      # past two tracker points
            wal.db.log.flush()                      # these updates stable
            wal.db.dc.maybe_background_flush(64)    # and some of the pages
            for key, value in want.items():
                assert tc.committed_read(STATE_TABLE, key) == value
            assert any(wal.db.dc.read(STATE_TABLE, k) != v
                       for k, v in want.items())
            seen["image"] = wal.crash()
            seen["txn"] = txn
        return out
    monkeypatch.setattr(tc, "update", crash_after_seven)
    wal.log_state(2, 2, _leaves(2, n_list=3))
    assert seen["calls"] > 7
    image = seen["image"]
    stable = [r for r in image.log.scan(1)
              if isinstance(r, UpdateRec) and r.txn == seen["txn"]]
    assert 0 < len(stable) < len(want)
    _, restored, step, cursor, state_step, stats = TrainWAL.restore(
        image, jax.eval_shape(lambda: committed), cfg, strategy)
    assert (step, cursor, state_step) == (1, 1, 1)
    assert stats.losers == 1 and stats.undone_ops == len(stable)
    for got, exp in zip(jax.tree.leaves(restored),
                        jax.tree.leaves(committed)):
        assert got.dtype == exp.dtype
        assert np.asarray(got).tobytes() == np.asarray(exp).tobytes()


def test_delta_only_after_restore_logs_just_the_changed_chunk():
    cfg = WALConfig(chunk_elems=CH, tracker_interval=4, cache_pages=4096)
    wal = TrainWAL(cfg)
    state = _leaves(0, n_list=2)
    wal.log_state(0, 0, state)
    wal.log_state(1, 1, _leaves(1, n_list=2))
    wal2, restored, *_ = TrainWAL.restore(
        wal.crash(), jax.eval_shape(lambda: state), cfg)
    chunks = dict(tree_to_records(restored, CH))
    log = wal2.db.log

    def save(step, st):
        """The state chunks one save logs, and ``log.bytes_appended``'s
        reading less the meta record's before- and after-image."""
        lo, b0 = log.end_lsn, obs.value("log.bytes_appended")
        wal2.log_state(step, step, st)
        recs = [log.record(i) for i in range(lo + 1, log.end_lsn + 1)]
        keys = [r.key for r in recs if isinstance(r, UpdateRec)
                and r.table == STATE_TABLE]
        return keys, obs.value("log.bytes_appended") - b0 - 2 * _META.size

    keys, state_bytes = save(2, restored)
    assert keys == [] and state_bytes == 0
    keys, state_bytes = save(3, _poke(restored, "['layers'][1]", 5))
    assert keys == [b"layers/1#000000"]
    assert state_bytes == 2 * len(chunks[b"layers/1#000000"])
    keys, _ = save(4, _poke(restored, "['w']", 4 * 2 * CH + 1))
    assert keys == [b"layers/1#000000", b"w#000002"]


# ------------------------------------ the log cut at each checkpoint
def _image_bytes_held(log) -> int:
    """Before- plus after-image bytes of the update records in memory,
    counted record by record."""
    return sum(len(r.before or b"") + len(r.after or b"")
               for r in log.scan(log.retained_lsn)
               if isinstance(r, UpdateRec))


@pytest.mark.parametrize("strategy", [Strategy.LOG0, Strategy.LOG1,
                                      Strategy.LOG2])
def test_resume_after_discards_is_bit_exact(strategy):
    """Three checkpoint periods, each cutting the log, then a crash after
    the save that follows the last cut: the resumed state is the lost one,
    bit for bit."""
    train_step, state0, batch_at = _tiny_trainer()
    wal_cfg = WALConfig(chunk_interval=2, ckpt_interval=4, bg_flush_pages=4,
                        cache_pages=512, chunk_elems=8192,
                        tracker_interval=50)
    wal = TrainWAL(wal_cfg)
    wal.log_state(0, 0, state0)
    cuts = []
    checkpoint = wal.maybe_checkpoint

    def checkpoint_and_note(step):
        taken = checkpoint(step)
        if taken:
            cuts.append(wal.db.log.retained_lsn)
        return taken
    wal.maybe_checkpoint = checkpoint_and_note
    n_steps = 15                        # cuts at 4, 8, 12; save at 14
    final = train_with_recovery(train_step=train_step, init_state=state0,
                                batch_at=batch_at, n_steps=n_steps, wal=wal)
    image = wal.crash()
    assert len(cuts) == 3 and cuts == sorted(cuts) and cuts[0] > 1
    assert image.log.retained_lsn == image.log.master.bckpt_lsn == cuts[-1]
    with pytest.raises(TruncatedLogError):
        image.log.record(cuts[-1] - 1)

    wal2, restored, step, stats = resume_from_crash(
        image, state0, train_step=train_step, batch_at=batch_at,
        wal_cfg=wal_cfg, strategy=strategy)
    assert step == n_steps and stats.scan_from == cuts[-1]
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(final)):
        assert got.dtype == want.dtype
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("strategy", [Strategy.LOG0, Strategy.LOG1,
                                      Strategy.LOG2])
def test_crash_inside_an_update_save_after_a_discard_recovers_the_last_commit(
        strategy, monkeypatch):
    cfg = WALConfig(chunk_elems=CH, tracker_interval=3, cache_pages=4096,
                    bg_flush_pages=4, ckpt_interval=1)
    wal = TrainWAL(cfg)
    committed = _leaves(2, n_list=3)
    wal.log_state(0, 0, _leaves(0, n_list=3))
    wal.log_state(1, 1, _leaves(1, n_list=3))
    assert wal.maybe_checkpoint(1)
    cut = wal.db.log.retained_lsn
    assert cut > 1
    wal.log_state(2, 2, committed)
    want = dict(tree_to_records(committed, CH))
    tc = wal.db.tc
    update = tc.update
    seen = {"calls": 0}

    def crash_after_seven(txn, table, key, value, skip_unchanged=False):
        out = update(txn, table, key, value, skip_unchanged)
        seen["calls"] += 1
        if seen["calls"] == 7:
            wal.db.log.flush()
            wal.db.dc.maybe_background_flush(64)
            seen["image"] = wal.crash()
            seen["txn"] = txn
        return out
    monkeypatch.setattr(tc, "update", crash_after_seven)
    wal.log_state(3, 3, _leaves(3, n_list=3))
    image = seen["image"]
    assert image.log.retained_lsn == cut
    stable = [r for r in image.log.scan(cut)
              if isinstance(r, UpdateRec) and r.txn == seen["txn"]]
    assert 0 < len(stable) < len(want)
    _, restored, step, cursor, state_step, stats = TrainWAL.restore(
        image, jax.eval_shape(lambda: committed), cfg, strategy)
    assert (step, cursor, state_step) == (2, 2, 2)
    assert stats.losers == 1 and stats.undone_ops == len(stable)
    for got, exp in zip(jax.tree.leaves(restored),
                        jax.tree.leaves(committed)):
        assert got.dtype == exp.dtype
        assert np.asarray(got).tobytes() == np.asarray(exp).tobytes()


@pytest.mark.parametrize("periods", [2, 6])
def test_log_after_each_checkpoint_holds_only_the_saves_since(periods):
    """Whatever the number of periods, each checkpoint leaves only its own
    records in memory, and before the next one the log holds the records
    of the saves and heartbeats since: the same count every period."""
    cfg = WALConfig(chunk_elems=CH, tracker_interval=3, cache_pages=4096,
                    bg_flush_pages=2, chunk_interval=2, ckpt_interval=4)
    wal = TrainWAL(cfg)
    log = wal.db.log
    wal.log_state(0, 0, _leaves(0, n_list=2))
    n_chunks = len(list(tree_to_records(_leaves(0, n_list=2), CH)))
    after_ckpt, before_ckpt = [], []

    def on_step(step, state, metrics):
        if (step + 1) % cfg.ckpt_interval == 0:
            after_ckpt.append((log.in_memory_records,
                               log.end_lsn - log.master.bckpt_lsn + 1,
                               log.live_image_bytes))
        elif (step + 2) % cfg.ckpt_interval == 0:
            before_ckpt.append(log.in_memory_records)
    train_with_recovery(
        train_step=lambda st, k: (_leaves(k, n_list=2), {}),
        init_state=_leaves(0, n_list=2), batch_at=lambda s: s + 1,
        n_steps=periods * cfg.ckpt_interval, wal=wal, on_step=on_step)
    assert len(after_ckpt) == periods
    for held, since_bckpt, image_bytes in after_ckpt:
        assert held == since_bckpt < n_chunks and image_bytes == 0
    # the first period's log also held the insert save; from then on one
    # update save (its chunks, trackers and commit) and two heartbeats
    first, *rest = before_ckpt
    assert len(set(rest)) == 1 and n_chunks < rest[0] < first
    assert log.live_image_bytes == _image_bytes_held(log)


def test_live_image_bytes_agree_with_bytes_appended():
    """The log's count of the image bytes it holds is, summed over
    appends, what ``log.bytes_appended`` adds; the discard lowers it by
    what it drops, and a crash image counts only its own records."""
    cfg = WALConfig(chunk_elems=CH, tracker_interval=3, cache_pages=4096,
                    ckpt_interval=2)
    wal = TrainWAL(cfg)
    log = wal.db.log
    b0 = obs.value("log.bytes_appended")
    wal.log_state(0, 0, _leaves(0, n_list=2))
    wal.log_step_meta(1, 1, 0)
    wal.log_state(2, 2, _leaves(1, n_list=2))
    appended = obs.value("log.bytes_appended") - b0
    assert log.live_image_bytes == appended == _image_bytes_held(log)
    assert wal.maybe_checkpoint(2)
    assert log.live_image_bytes == 0 == _image_bytes_held(log)
    wal.log_state(3, 3, _leaves(2, n_list=2))
    wal.log_step_meta(4, 4, 3)
    appended = obs.value("log.bytes_appended") - b0 - appended
    assert log.live_image_bytes == appended == _image_bytes_held(log)
    txn = wal.db.tc.begin()             # an unforced tail the crash loses
    wal.db.tc.update(txn, META_TABLE, b"latest", _META.pack(9, 9, 9))
    image = wal.crash()
    assert image.log.live_image_bytes == appended
    assert image.log.live_image_bytes == _image_bytes_held(image.log)
    assert log.live_image_bytes == appended + 2 * _META.size


def _twin_leaves():
    """Host leaves: fp32 with a partial last chunk, bf16, int32, a 0-d
    scalar, and an fp32 leaf of exactly one chunk."""
    return {"w": np.arange(2 * CH + 5, dtype=np.float32) / 3,
            "b": (np.arange(CH + 9, dtype=np.float32) / 7
                  ).astype(jnp.bfloat16),
            "i": np.arange(-150, 150, dtype=np.int32).reshape(3, 100),
            "s": np.asarray(-7, np.int32),
            "one": np.linspace(-1, 1, CH, dtype=np.float32).reshape(32, 32)}


@pytest.mark.parametrize("kind", ["jax", "mixed", "numpy"])
def test_prefetched_save_matches_its_numpy_twin(kind):
    """Leaves whose copy to the host starts ahead give the keys and bytes
    of the same tree held in numpy, in the same order, and a save of them
    restores bit for bit."""
    host = _twin_leaves()
    names = sorted(host)
    on_device = {"jax": names, "mixed": names[::2], "numpy": []}[kind]
    tree = {k: jnp.asarray(v) if k in on_device else v
            for k, v in host.items()}
    assert sum(isinstance(v, jax.Array) for v in tree.values()) == len(
        on_device)
    assert list(tree_to_records(tree, CH)) == list(tree_to_records(host, CH))

    wal = TrainWAL(WALConfig(chunk_elems=CH, cache_pages=256))
    wal.log_state(1, 1, tree)
    _, restored, step, cursor, state_step, _ = TrainWAL.restore(
        wal.crash(), host, wal.cfg)
    assert (step, cursor, state_step) == (1, 1, 1)
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(host)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.asarray(got).tobytes() == want.tobytes()
