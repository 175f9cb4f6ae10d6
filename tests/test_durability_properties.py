"""Property suite for the durability subsystem (core/durability.py).

The tentpole contract: with a seeded :class:`CrashInjector` cutting the
process at ANY durability write boundary (:data:`CRASH_POINTS`), recovery
always lands BIT-IDENTICAL to the index after some prefix of the mutation
sequence — exactly pre-op or post-op of the op that died, never a torn
hybrid.  Identity is checked three ways at once: full active membership,
per-cluster generation-stamp/storage-flag state, and actual search
(ids AND scores) against independently rebuilt reference indexes.

Also checked:
  * WAL replay is idempotent — replaying the suffix twice equals once;
  * any single bit flip anywhere in a WAL frame fails that frame's CRC,
    and reading truncates cleanly at it (the valid prefix still parses);
  * a torn trailing frame is physically truncated by recovery;
  * checkpoints bump NO generation stamp (the pipeline's no-staling
    guarantee) and compaction drops exactly the records a snapshot covers.

Every crashpoint property runs over a deterministic grid (always) spanning
all storage codecs incl. pq and the memmap mode; hypothesis (when
installed) additionally fuzzes the op sequence, crash occurrence, and
seeds — same pattern as test_pq_properties.py.
"""
import gc
import os
import shutil
import tempfile
import zlib

import numpy as np
import pytest

from repro.core import (CRASH_POINTS, CrashInjector, Durability,
                        EdgeRAGIndex, RecoveryError, SimulatedCrash,
                        WriteAheadLog, recover)
from repro.core.durability import (IndexSnapshot, _replay_record,
                                   pack_record, unpack_record)
from repro.data import generate_dataset

pytestmark = pytest.mark.fast

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SETTINGS = dict(max_examples=15, deadline=None)

DIM = 16
DS = generate_dataset(n_records=60, dim=DIM, n_topics=4, n_queries=4,
                      seed=7)
TEXTS = {int(i): t for i, t in zip(DS.chunk_ids, DS.texts)}
_ORIG_TEXTS = dict(TEXTS)


def embed_fn(ts):
    out = np.zeros((len(ts), DIM), np.float32)
    for j, t in enumerate(ts):
        r = np.random.default_rng(zlib.crc32(t.encode()))
        out[j] = r.standard_normal(DIM)
    return out / np.linalg.norm(out, axis=1, keepdims=True)


def get_chunks(ids):
    return [TEXTS[int(i)] for i in ids]


CORPUS_EMB = embed_fn(list(DS.texts))
QUERIES = embed_fn(["durable query one", "durable query two"])


def make_ops(n_insert, n_remove, n_update, seed):
    """A deterministic mutation sequence; inserted texts are fat enough
    that some ops cross the store/split thresholds.  TEXTS is reset to
    the pristine corpus first so the dict is a pure function of ``seed``
    (cached reference signatures stay valid across seeds)."""
    TEXTS.clear()
    TEXTS.update(_ORIG_TEXTS)
    rng = np.random.default_rng(seed)
    ops = []
    for j in range(n_insert):
        nid = 50_000 + seed * 1000 + j
        TEXTS[nid] = (f"inserted chunk {seed}/{j} ") * int(rng.integers(5, 40))
        ops.append(("ins", nid))
    for i in rng.choice(DS.chunk_ids, size=n_remove, replace=False):
        ops.append(("rm", int(i)))
    for i in rng.choice(DS.chunk_ids[n_remove:], size=n_update,
                        replace=False):
        TEXTS[int(i)] = f"updated text {seed} " * int(rng.integers(5, 30))
        ops.append(("up", int(i)))
    rng.shuffle(ops)
    return [tuple(op) for op in ops]


def apply_op(ix, op):
    kind, i = op
    if kind == "ins":
        ix.insert(i, TEXTS[i])
    elif kind == "rm":
        ix.remove(i)
    else:
        ix.update(i, TEXTS[i])


def build_index(codec, mode, root=None, maintenance="sync"):
    ix = EdgeRAGIndex(DIM, embed_fn, get_chunks, storage_mode=mode,
                      storage_root=root, storage_codec=codec,
                      slo_s=0.004, split_max_chars=4000,
                      maintenance=maintenance)
    ix.build(DS.chunk_ids, DS.texts, nlist=5, embeddings=CORPUS_EMB)
    return ix


def state_sig(ix):
    """Content-identity signature: membership + per-cluster content state +
    search (ids and scores) over fixed queries.  ``generation`` (the
    storage-EVENT stamp) is deliberately excluded: recovery's self-heal
    legitimately bumps it when it regenerates a lost blob, without
    changing any content — ``content_generation`` and the actual scores
    pin content identity.  Compare signatures with :func:`sig_eq`."""
    ids, vals, _ = ix.search_batch(QUERIES, 6, 3)
    return (
        tuple(sorted(int(i) for c in ix.clusters if c.active for i in c.ids)),
        tuple((tuple(int(i) for i in c.ids), c.char_count, c.stored,
               c.active, c.content_generation)
              for c in ix.clusters),
        ids.tobytes(), np.asarray(vals),
    )


def sig_eq(a, b):
    """Membership, cluster state and ids exactly; scores to f32 rounding
    (a regenerated cluster may be scored in a different slab layout)."""
    return a[:3] == b[:3] and np.allclose(a[3], b[3], rtol=1e-6, atol=1e-6)


_REF_CACHE = {}


def reference_sigs(ops, codec, seed):
    """Signature of a fresh index after every prefix of ``ops`` — the
    pre/post states recovery must land on (memory mode: same codec, same
    put sequence, so stored payloads quantize identically)."""
    key = (seed, codec)
    if key not in _REF_CACHE:
        sigs = []
        for j in range(len(ops) + 1):
            ix = build_index(codec, "memory")
            for op in ops[:j]:
                apply_op(ix, op)
            sigs.append(state_sig(ix))
        _REF_CACHE[key] = sigs
    return _REF_CACHE[key]


# ---------------------------------------------------------------- properties
def check_crash_atomicity(point, codec, mode, at, seed):
    """Crash at occurrence ``at`` of ``point``; recovery must equal some
    op-sequence prefix — and specifically pre-op or post-op of the op
    that was running when the crash hit."""
    ops = make_ops(5, 3, 2, seed)
    refs = reference_sigs(ops, codec, seed)
    root = tempfile.mkdtemp(prefix="dur_prop_")
    try:
        crash = CrashInjector(point, at=at, seed=seed)
        ix = build_index(codec, mode, root=root)
        crashed_at = None
        attach_crashed = False
        try:
            # a snap_* crash at occurrence 1 fires here, inside the
            # baseline checkpoint — before any op ran
            ix.attach_durability(Durability(root, checkpoint_every=3,
                                            crash=crash))
        except SimulatedCrash:
            attach_crashed = True
        if not attach_crashed:
            for j, op in enumerate(ops):
                try:
                    apply_op(ix, op)
                except SimulatedCrash:
                    crashed_at = j
                    break
        del ix          # the crashed process is gone: release the root
        gc.collect()    # (index<->scheduler cycle pins the writer claim)
        try:
            ix2, rep = recover(root, embed_fn, get_chunks, slo_s=0.004,
                               storage_mode=mode, maintenance="sync",
                               split_max_chars=4000)
        except RecoveryError:
            # only legitimate when the crash killed the very first
            # snapshot: nothing durable ever landed
            assert attach_crashed, \
                f"{point}/{codec}/{mode}: recovery refused despite a " \
                f"durable baseline existing"
            return
        sig = state_sig(ix2)
        match = [j for j, s in enumerate(refs) if sig_eq(s, sig)]
        assert match, \
            f"{point}/{codec}/{mode}: recovered state is a hybrid " \
            f"(matches no prefix; crashed at op {crashed_at})"
        if crashed_at is not None:
            assert crashed_at in match or crashed_at + 1 in match, \
                f"{point}/{codec}/{mode}: recovered to prefix {match}, " \
                f"crash was at op {crashed_at} (want pre- or post-op)"
        del ix2
    finally:
        shutil.rmtree(root, ignore_errors=True)


def check_replay_idempotent(seed):
    """Applying the WAL suffix twice must equal applying it once."""
    root = tempfile.mkdtemp(prefix="dur_idem_")
    try:
        ix = build_index("fp32", "disk", root=root)
        dur = Durability(root, checkpoint_every=10**6)  # never checkpoints
        ix.attach_durability(dur)
        for op in make_ops(4, 2, 1, seed):
            apply_op(ix, op)
        records, _, torn = dur.wal.records()
        assert records and not torn
        found = IndexSnapshot.newest_valid(dur.dir)
        assert found is not None
        pre = state_sig(ix)
        del ix
        gc.collect()

        def replay(times):
            jx = EdgeRAGIndex(DIM, embed_fn, get_chunks,
                              storage_mode="disk", storage_root=root,
                              slo_s=0.004, split_max_chars=4000)
            applied, manifest = IndexSnapshot.apply(jx, found[1])
            for _ in range(times):
                cursor = applied
                for rec in records:
                    if int(rec["lsn"]) <= cursor:
                        continue        # the idempotence mechanism: LSN skip
                    _replay_record(jx, rec, manifest)
                    cursor = int(rec["lsn"])
                applied = cursor
            sig = state_sig(jx)
            del jx
            gc.collect()
            return sig

        once = replay(1)
        twice = replay(2)
        assert sig_eq(once, twice)
        assert sig_eq(once, pre)  # and both equal the pre-crash live state
    finally:
        shutil.rmtree(root, ignore_errors=True)


def check_bit_flip_truncates(flip_byte_frac, flip_bit, seed):
    """One flipped bit anywhere past the magic fails exactly one frame's
    CRC; reading stops there and truncation leaves a clean prefix."""
    root = tempfile.mkdtemp(prefix="dur_flip_")
    try:
        wal = WriteAheadLog(os.path.join(root, "wal.log"))
        rng = np.random.default_rng(seed)
        bodies = [pack_record({"lsn": j, "op": "t", "nlist": 0, "gone": [],
                               "pq_version": None, "clusters": [],
                               "pad": rng.integers(0, 9, 4).tolist()})
                  for j in range(1, 6)]
        for b in bodies:
            wal.append(b)
        clean, _, torn = wal.frames()
        assert len(clean) == 5 and not torn
        data = bytearray(open(wal.path, "rb").read())
        pos = 8 + int(flip_byte_frac * (len(data) - 8))   # past the magic
        pos = min(pos, len(data) - 1)
        data[pos] ^= (1 << flip_bit)
        with open(wal.path, "wb") as f:
            f.write(bytes(data))
        frames, _, torn = wal.frames()
        assert torn, "a flipped bit must be detected"
        assert len(frames) < 5
        for got, want in zip(frames, bodies):   # prefix is untouched
            assert got == want
        dropped = wal.truncate_torn_tail()
        assert dropped > 0
        frames2, _, torn2 = wal.frames()
        assert not torn2 and frames2 == frames  # clean after truncation
        assert wal.truncate_torn_tail() == 0    # second cut is a no-op
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------- deterministic
CODEC_ARMS = [("fp32", "disk"), ("fp16", "disk"), ("int8", "disk"),
              ("pq", "disk"), ("pq", "memmap"), ("fp32", "memmap")]


@pytest.mark.parametrize("point", CRASH_POINTS)
@pytest.mark.parametrize("codec,mode", CODEC_ARMS)
def test_crashpoint_atomicity_grid(point, codec, mode):
    check_crash_atomicity(point, codec, mode, at=2, seed=11)


@pytest.mark.parametrize("point", ["wal_pre_append", "wal_torn_append"])
@pytest.mark.parametrize("at,seed", [(3, 4), (3, 13), (1, 17)])
def test_lost_record_rolls_back_replaced_blob(point, at, seed):
    """The crash hits an update, an insert and a remove (in that order of
    the cases) that rewrote a stored blob before its WAL record: the chunk
    texts are already newer than the pre-op index, so only the undo copy —
    not a re-embed — lands recovery on the pre-op state."""
    check_crash_atomicity(point, "fp32", "disk", at=at, seed=seed)


def test_committed_ops_leave_no_undo_copies():
    root = tempfile.mkdtemp(prefix="dur_undo_")
    try:
        ix = build_index("fp32", "disk", root=root)
        ix.attach_durability(Durability(root, checkpoint_every=3))
        for op in make_ops(5, 3, 2, seed=4):
            apply_op(ix, op)
        assert ix.storage.keys()
        assert ix.storage.undo_keys() == []
        assert not [f for _, _, fs in os.walk(root) for f in fs
                    if f.endswith(".undo")]
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_crashpoint_first_occurrence():
    # at=1 dies inside attach_durability's baseline snapshot for the snap_*
    # points — there is nothing durable yet, so recovery must refuse
    # rather than fabricate state
    for point in ("wal_pre_append", "wal_torn_append", "wal_post_append"):
        check_crash_atomicity(point, "fp32", "disk", at=1, seed=3)


def test_recover_without_durable_state_raises():
    root = tempfile.mkdtemp(prefix="dur_none_")
    try:
        with pytest.raises(RecoveryError):
            recover(root, embed_fn, get_chunks)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_wal_replay_idempotent():
    for seed in (0, 1, 2):
        check_replay_idempotent(seed)


def test_bit_flip_truncates():
    for frac, bit, seed in [(0.02, 0, 0), (0.3, 3, 1), (0.55, 7, 2),
                            (0.85, 4, 3), (0.999, 1, 4)]:
        check_bit_flip_truncates(frac, bit, seed)


def test_record_roundtrip_ndarrays():
    rec = {"lsn": 3, "op": "x",
           "a": np.arange(12, dtype=np.float32).reshape(3, 4) / 7,
           "nested": {"ids": np.array([5, -2], np.int64)},
           "s": "text", "none": None}
    out = unpack_record(pack_record(rec))
    assert out["lsn"] == 3 and out["s"] == "text" and out["none"] is None
    assert np.array_equal(out["a"], rec["a"]) and out["a"].dtype == np.float32
    assert np.array_equal(out["nested"]["ids"], rec["nested"]["ids"])


def test_checkpoint_bumps_no_generation_and_compacts():
    """The pipeline no-staling guarantee: a checkpoint leaves every
    generation stamp untouched, so the S3 replan gate never fires on one;
    and the post-snapshot compaction leaves only uncovered records."""
    root = tempfile.mkdtemp(prefix="dur_ckpt_")
    try:
        ix = build_index("fp32", "disk", root=root, maintenance="deferred")
        dur = ix.attach_durability(Durability(root, checkpoint_every=4))
        for op in make_ops(5, 2, 0, seed=5):
            apply_op(ix, op)
        assert any(op.kind == "checkpoint" for op in ix.maintenance.pending)
        stamps = [(c.generation, c.content_generation) for c in ix.clusters]
        snaps_before = dur.snapshots_total
        ix.maintenance.drain(None)
        assert dur.snapshots_total > snaps_before
        # drained split/merge/restore ops legitimately bump stamps; re-run
        # with a now-idle queue so the only executable op is a checkpoint
        for op in make_ops(0, 0, 0, seed=6):
            apply_op(ix, op)
        dur.records_since_snapshot = dur.checkpoint_every  # force one
        ix.maintenance.enqueue("checkpoint", -1)
        stamps = [(c.generation, c.content_generation) for c in ix.clusters]
        rep = ix.maintenance.drain(None)
        assert ("checkpoint", -1) in rep.executed
        assert stamps == [(c.generation, c.content_generation)
                          for c in ix.clusters]
        assert rep.edge_s > 0.0         # snapshot I/O is charged, not free
        # compaction: every WAL record left is newer than the snapshot
        records, _, _ = dur.wal.records()
        assert all(int(r["lsn"]) > dur.next_lsn - 1 - len(records)
                   for r in records)
        assert dur.records_since_snapshot == len(records) == 0
        del ix
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_recover_router_restores_every_tenant():
    """One recover_router call restores a whole crashed multi-tenant
    deployment: per-tenant namespaced WALs under the shared root, each
    tenant's answers identical to pre-crash."""
    from repro.core import TenantRouter
    from repro.core.durability import recover_router

    root = tempfile.mkdtemp(prefix="dur_router_")
    try:
        router = TenantRouter(DIM, slo_s=0.004, storage_mode="disk",
                              storage_root=root)
        for t in ("alpha", "beta"):
            ix = router.create_tenant(t, embed_fn, get_chunks,
                                      slo_s=0.004, maintenance="sync")
            ix.build(DS.chunk_ids, DS.texts, nlist=5,
                     embeddings=CORPUS_EMB)
        handles = router.enable_durability(checkpoint_every=4)
        assert set(handles) == {"alpha", "beta"}
        for t, base in (("alpha", 80_000), ("beta", 90_000)):
            ix = router.tenants[t]
            for j in range(5):
                TEXTS[base + j] = f"tenant {t} chunk {j} " * 15
                ix.insert(base + j, TEXTS[base + j])
            ix.remove(int(DS.chunk_ids[0 if t == "alpha" else 1]))
        pre = {t: router.tenants[t].search_batch(QUERIES, 6, 3)[:2]
               for t in ("alpha", "beta")}
        del router, ix
        gc.collect()

        specs = {t: (embed_fn, get_chunks) for t in ("alpha", "beta")}
        router2, reports = recover_router(
            root, specs,
            tenant_kwargs={"slo_s": 0.004, "maintenance": "sync"})
        assert set(reports) == {"alpha", "beta"}
        for t in ("alpha", "beta"):
            assert reports[t].tenant == t
            ids, vals, _ = router2.tenants[t].search_batch(QUERIES, 6, 3)
            assert np.array_equal(ids, pre[t][0])
            assert np.array_equal(vals, pre[t][1])
            assert router2.tenants[t].durability is not None
        # unknown tenants must be impossible to silently drop
        with pytest.raises(AssertionError):
            recover_router(root, {"alpha": specs["alpha"]})
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ------------------------------------------------------------- hypothesis
if HAVE_HYPOTHESIS:

    @settings(**SETTINGS)
    @given(point=st.sampled_from(CRASH_POINTS),
           codec=st.sampled_from(["fp32", "fp16", "int8", "pq"]),
           at=st.integers(min_value=1, max_value=6),
           seed=st.integers(min_value=0, max_value=50))
    def test_hyp_crashpoint_atomicity(point, codec, at, seed):
        check_crash_atomicity(point, codec, "disk", at=at, seed=seed)

    @settings(**SETTINGS)
    @given(seed=st.integers(min_value=0, max_value=100))
    def test_hyp_replay_idempotent(seed):
        check_replay_idempotent(seed)

    @settings(**SETTINGS)
    @given(frac=st.floats(min_value=0.0, max_value=1.0),
           bit=st.integers(min_value=0, max_value=7),
           seed=st.integers(min_value=0, max_value=100))
    def test_hyp_bit_flip_truncates(frac, bit, seed):
        check_bit_flip_truncates(frac, bit, seed)
