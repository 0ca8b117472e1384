"""Differential (delta-chain) checkpoints: RowDelta semantics, chain
replay bit-identity, chain bounds, GC ancestor pinning, rank-local
items, resize behavior, corrupt-link fallback, and the mid-delta-write
kill drill."""

import os
import threading

import numpy as np
import pytest

from horovod_tpu.checkpoint import (CheckpointCorruptError,
                                    CheckpointManager,
                                    LocalCommitCoordinator, RowDelta,
                                    assemble_table)
from horovod_tpu.checkpoint import manifest as mf
from horovod_tpu.common import env as henv
from horovod_tpu.common import failpoints, metrics


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    failpoints.set_crash_handler(None)
    yield
    failpoints.reset()
    failpoints.set_crash_handler(None)


@pytest.fixture
def chain_max(monkeypatch):
    def set_max(n):
        monkeypatch.setenv(henv.HOROVOD_CKPT_DELTA_CHAIN_MAX, str(n))
    return set_max


# ---------------------------------------------------------------------------
# RowDelta unit semantics
# ---------------------------------------------------------------------------

def test_rowdelta_merge_overlay_and_ordering():
    base = RowDelta([0, 2, 4], np.arange(6.).reshape(3, 2), 6)
    newer = RowDelta([2, 5], np.full((2, 2), 9.0), 6)
    merged = base.merged_with(newer)
    assert merged.rows.tolist() == [0, 2, 4, 5]
    np.testing.assert_array_equal(merged.values[1], [9.0, 9.0])
    np.testing.assert_array_equal(merged.values[0], [0.0, 1.0])
    # operands untouched
    np.testing.assert_array_equal(base.values[1], [2.0, 3.0])


def test_rowdelta_validation():
    with pytest.raises(ValueError):
        RowDelta([0, 7], np.zeros((2, 2)), 4)       # id out of range
    with pytest.raises(ValueError):
        RowDelta([0], np.zeros((2, 2)), 4)          # length mismatch
    with pytest.raises(ValueError):
        RowDelta([0, 1], np.zeros((2, 2)), 4).merged_with(
            RowDelta([0], np.zeros((1, 2)), 8))     # resized table


def test_assemble_table_requires_full_coverage():
    a = RowDelta([0, 2], np.ones((2, 3)), 4)
    b = RowDelta([1, 3], np.full((2, 3), 2.0), 4)
    tab = assemble_table({"t/rows.r0": a, "t/rows.r1": b}, "t/rows")
    np.testing.assert_array_equal(tab[0], 1.0)
    np.testing.assert_array_equal(tab[3], 2.0)
    with pytest.raises(ValueError, match="covered by no shard"):
        assemble_table({"t/rows.r0": a}, "t/rows")
    assert assemble_table({}, "t/rows") is None


# ---------------------------------------------------------------------------
# single-rank chain: bit-identity, bounds, fallback, GC
# ---------------------------------------------------------------------------

def _save_chain(m, tmp_path, steps, touch, chain_max_n, num_rows=32,
                dim=2):
    """Drive `steps` saves with deterministic sparse touches; returns
    the live table after each committed step."""
    table = np.zeros((num_rows, dim), np.float32)
    history = {}
    for s in range(1, steps + 1):
        rows = touch(s)
        table[rows] += np.float32(0.5 * s)
        parent = m.delta_plan()
        if parent is None:
            item = RowDelta(np.arange(num_rows), table.copy(), num_rows)
        else:
            item = RowDelta(np.array(rows, np.int64),
                            table[rows].copy(), num_rows)
        m.save(s, {"dense": np.float32(s)},
               local_items={"sparse/t/rows.r00000": item},
               delta_of=parent)
        history[s] = table.copy()
    return history


def _step_bytes(tmp_path, step):
    man = mf.read_manifest(mf.step_dir(str(tmp_path), step))
    return sum(int(e.get("nbytes", 0)) for e in man.shards)


@pytest.mark.parametrize("num_rows,dim,touched,delta_bytes_bound",
                         [(32, 2, 2, None), (4096, 16, 82, 0.1)])
def test_chain_roundtrip_bit_identical_to_full(tmp_path, chain_max,
                                               num_rows, dim, touched,
                                               delta_bytes_bound):
    """Base + K deltas replays to exactly the live state (acceptance:
    bit-identical to a full checkpoint after base + K deltas).  The
    large case is a table at a 2 % touch rate a step: there a delta
    step's shards are at most a tenth of the base step's bytes, the
    Check-N-Run compression claim as a count."""
    chain_max(4)
    m = CheckpointManager(str(tmp_path), keep=None)
    touch = lambda s: sorted({(s * 3 + k * 7) % num_rows
                              for k in range(touched)})
    history = _save_chain(m, tmp_path, 5, touch, 4, num_rows, dim)
    # steps: 1=base, 2..5 deltas (chain_max 4)
    assert m.chain_of(5) == [1, 2, 3, 4, 5]
    for s, expected in history.items():
        items = m.restore(s)
        tab = assemble_table(items, "sparse/t/rows")
        np.testing.assert_array_equal(tab, expected)
        assert tab.dtype == expected.dtype
        assert items["dense"] == np.float32(s)
    if delta_bytes_bound is not None:
        full = _step_bytes(tmp_path, 1)
        for s in range(2, 6):
            assert _step_bytes(tmp_path, s) <= delta_bytes_bound * full, \
                (s, _step_bytes(tmp_path, s), full)
    m.close()


def test_chain_max_forces_full_base(tmp_path, chain_max):
    chain_max(2)
    m = CheckpointManager(str(tmp_path), keep=None)
    touch = lambda s: [s % 32]
    _save_chain(m, tmp_path, 7, touch, 2)
    # chains: 1=base, 2,3 deltas; 4=base, 5,6 deltas; 7=base
    assert m.chain_of(3) == [1, 2, 3]
    assert m.chain_of(4) == [4]
    assert m.chain_of(6) == [4, 5, 6]
    assert m.chain_of(7) == [7]
    m.close()


def test_chain_disabled_by_env_zero(tmp_path, chain_max):
    chain_max(0)
    m = CheckpointManager(str(tmp_path), keep=None)
    m.save(1, _sparse_items(1.0))
    assert m.delta_plan() is None
    m.close()


def _sparse_items(scale):
    return {"dense": np.float32(scale)}


def test_corrupt_chain_link_falls_back_to_earlier_base(tmp_path,
                                                       chain_max):
    """A corrupt BASE invalidates every delta above it; restore_latest
    falls back past the whole chain to the previous valid step —
    the same fallback semantics as dense shards."""
    chain_max(2)
    m = CheckpointManager(str(tmp_path), keep=None)
    touch = lambda s: [s % 32, (s + 11) % 32]
    history = _save_chain(m, tmp_path, 6, touch, 2)
    # 1=base, 2,3 deltas; 4=base, 5,6 deltas.  Corrupt base 4.
    shard = os.path.join(mf.step_dir(str(tmp_path), 4),
                         mf.shard_name(0, 1))
    with open(shard, "r+b") as f:
        f.seek(30)
        f.write(b"\xff\xff\xff")
    for tip in (6, 5, 4):
        with pytest.raises(CheckpointCorruptError):
            m.restore(tip)
    fallbacks0 = metrics.REGISTRY.counter(
        "hvd_ckpt_restore_fallbacks_total").value()
    step, items = m.restore_latest()
    assert step == 3            # newest step whose chain verifies
    tab = assemble_table(items, "sparse/t/rows")
    np.testing.assert_array_equal(tab, history[3])
    assert metrics.REGISTRY.counter(
        "hvd_ckpt_restore_fallbacks_total").value() > fallbacks0
    m.close()


def test_gc_pins_chain_ancestors(tmp_path, chain_max):
    """keep=2 with a live chain must NOT reap the base the kept
    deltas replay from."""
    chain_max(10)
    m = CheckpointManager(str(tmp_path), keep=2)
    touch = lambda s: [s % 32]
    history = _save_chain(m, tmp_path, 5, touch, 10)
    on_disk = mf.list_step_dirs(str(tmp_path))
    assert 1 in on_disk, "base reaped out from under its deltas"
    assert set(on_disk) >= {1, 4, 5}
    step, items = m.restore_latest()
    assert step == 5
    np.testing.assert_array_equal(
        assemble_table(items, "sparse/t/rows"), history[5])
    m.close()


def test_delta_metrics_counted(tmp_path, chain_max):
    chain_max(4)
    rows0 = metrics.REGISTRY.counter(
        "hvd_ckpt_delta_rows_total").value()
    m = CheckpointManager(str(tmp_path), keep=None)
    _save_chain(m, tmp_path, 3, lambda s: [s], 4)
    assert metrics.REGISTRY.counter(
        "hvd_ckpt_delta_rows_total").value() > rows0
    assert metrics.REGISTRY.gauge(
        "hvd_ckpt_delta_chain_len").value() == 2.0
    m.close()


# ---------------------------------------------------------------------------
# multi-rank: two-phase agreement, rank-local items, resize
# ---------------------------------------------------------------------------

def _world_save(tmp_path, coord, world, step, scale, delta_of="auto",
                chain=None):
    """All `world` thread-ranks save `step` with rank-local shard
    items; returns per-rank outcomes."""
    mgrs = [CheckpointManager(str(tmp_path), rank=r, world_size=world,
                              coordinator=coord, keep=None)
            for r in range(world)]
    outcomes = [None] * world

    def run(r):
        ids = np.arange(r, 16, world, dtype=np.int64)
        item = RowDelta(ids, np.full((len(ids), 2), scale,
                                     np.float32), 16)
        d = mgrs[r].delta_plan() if delta_of == "auto" else \
            (delta_of[r] if isinstance(delta_of, (list, tuple))
             else delta_of)
        try:
            outcomes[r] = mgrs[r].save(
                step, {"dense": np.float32(scale)},
                local_items={"sparse/w/rows.r%05d" % r: item},
                delta_of=d)
        except Exception as e:
            outcomes[r] = repr(e)

    threads = [threading.Thread(target=run, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    for m in mgrs:
        m.close(timeout=5)
    return outcomes


def test_two_phase_delta_all_ranks_and_layout(tmp_path, chain_max):
    chain_max(4)
    coord = LocalCommitCoordinator()
    assert _world_save(tmp_path, coord, 4, 1, 1.0, delta_of=None) \
        == ["committed", "prepared", "prepared", "prepared"]
    assert _world_save(tmp_path, coord, 4, 2, 2.0) \
        == ["committed", "prepared", "prepared", "prepared"]
    man = mf.read_manifest(mf.step_dir(str(tmp_path), 2))
    assert man.meta["delta_of"] == 1
    assert man.meta["base_step"] == 1
    assert man.meta["chain_len"] == 1
    # Every rank's local item is in the layout, owned by that rank.
    for r in range(4):
        assert man.layout["sparse/w/rows.r%05d" % r] == r
    m = CheckpointManager(str(tmp_path), rank=0, world_size=1)
    step, items = m.restore_latest()
    assert step == 2
    tab = assemble_table(items, "sparse/w/rows")
    np.testing.assert_array_equal(tab, np.full((16, 2), 2.0))
    m.close()


def test_delta_parent_disagreement_abandons_commit(tmp_path,
                                                   chain_max):
    chain_max(4)
    coord = LocalCommitCoordinator()
    assert _world_save(tmp_path, coord, 2, 1, 1.0, delta_of=None) \
        == ["committed", "prepared"]
    # Rank 1 claims a different parent: the arbiter must refuse.
    outcomes = _world_save(tmp_path, coord, 2, 2, 2.0,
                           delta_of=[1, None])
    assert "committed" not in outcomes
    assert mf.committed_steps(str(tmp_path)) == [1]


def test_resize_n_m_n_roundtrip_with_deltas(tmp_path, chain_max):
    """Save at 4 ranks (base+delta), restore/resave at 2, back at 4:
    the chain breaks at each resize (delta_plan returns None when the
    tip's world differs) and the state stays bit-identical."""
    chain_max(4)
    coord = LocalCommitCoordinator()
    _world_save(tmp_path, coord, 4, 1, 1.0, delta_of=None)
    _world_save(tmp_path, coord, 4, 2, 2.0)
    # world changed: delta_plan must force a full base
    m2 = CheckpointManager(str(tmp_path), rank=0, world_size=2,
                           coordinator=LocalCommitCoordinator())
    assert m2.delta_plan() is None
    m2.close(timeout=5)
    _world_save(tmp_path, LocalCommitCoordinator(), 2, 3, 3.0,
                delta_of=None)
    _world_save(tmp_path, LocalCommitCoordinator(), 2, 4, 4.0)
    man = mf.read_manifest(mf.step_dir(str(tmp_path), 4))
    assert man.meta["delta_of"] == 3 and man.world_size == 2
    _world_save(tmp_path, LocalCommitCoordinator(), 4, 5, 5.0,
                delta_of=None)
    m = CheckpointManager(str(tmp_path), rank=0, world_size=1)
    for step, scale in ((2, 2.0), (4, 4.0), (5, 5.0)):
        items = m.restore(step)
        np.testing.assert_array_equal(
            assemble_table(items, "sparse/w/rows"),
            np.full((16, 2), scale))
    m.close()


def test_delta_parent_gone_abandons_commit(tmp_path, chain_max):
    """delta_of pointing at a step whose manifest is unreadable must
    fail the commit, not publish an unreplayable tip."""
    chain_max(4)
    m = CheckpointManager(str(tmp_path), keep=None)
    m.save(1, _sparse_items(1.0))
    with pytest.raises(Exception):
        m.save(2, _sparse_items(2.0), delta_of=99)   # no such parent
    assert mf.committed_steps(str(tmp_path)) == [1]
    m.close()


# ---------------------------------------------------------------------------
# the kill-mid-delta chaos drill
# ---------------------------------------------------------------------------

def test_delta_chain_drill_kill_mid_delta_write(tmp_path):
    sys_tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    import sys
    if sys_tools not in sys.path:
        sys.path.insert(0, sys_tools)
    from chaos_soak import run_checkpoint_drill
    rec = run_checkpoint_drill("mid_delta", ranks=4, seed=13,
                               steps=12, commit_every=3,
                               ckpt_dir=str(tmp_path / "a"))
    assert rec["ok"], rec
    assert rec["bit_identical"]
    assert rec["tip_is_delta"], \
        "drill degenerated to an all-base run"
    assert rec["torn_checkpoints"] == []
    assert rec["restored_step"] == rec["committed_before_kill"]
    # Determinism: same seed -> same schedule and outcome.
    rec2 = run_checkpoint_drill("mid_delta", ranks=4, seed=13,
                                steps=12, commit_every=3,
                                ckpt_dir=str(tmp_path / "b"))
    for k in ("victim", "kill_commit", "restored_step",
              "restored_chain"):
        assert rec2[k] == rec[k], k
