"""Serving plane: exactly-once ingest, WAL journal, batched tenant folds,
crash-recovery bit-identity (SIGKILL subprocess), watchdogs, drift."""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.streaming import StreamingGram
from repro.serve import (BoundedQueue, FoldJournal, IngestLog,
                         JournalCorruptionError, Payload, ServeConfig,
                         StructureServer, TenantTable, TrafficConfig,
                         make_trace, read_journal, split_kinds,
                         unique_payloads)
from repro.serve.journal import (iter_records, list_segments,
                                 prune_segments, scan_segments,
                                 segment_path)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _codes(rng, n=16, d=6):
    return rng.choice(np.asarray([-1, 1], np.int8), size=(n, d))


def _packed_payload(rng, tenant, machine, seq, n=16, d=6):
    from repro.core.quantizers import pack_codes

    bits = rng.integers(0, 2, size=(n, d)).astype(np.int8)
    pad = (-n) % 8
    if pad:
        bits = np.concatenate([bits, np.zeros((pad, d), np.int8)])
    return Payload(tenant, machine, seq,
                   packed=np.asarray(pack_codes(bits.T, 1)), n=n)


# -- payloads / queue --------------------------------------------------------

def test_payload_validation(rng):
    c = _codes(rng)
    with pytest.raises(ValueError):
        Payload(0, 0, 1)                            # neither kind
    with pytest.raises(ValueError):
        Payload(0, 0, 1, codes=c, packed=np.zeros((6, 2), np.uint8), n=3)
    with pytest.raises(ValueError):
        Payload(0, 0, 0, codes=c)                   # seq is 1-based
    with pytest.raises(ValueError):
        Payload(0, 0, 1, packed=np.zeros((6, 2), np.uint8), n=99)
    p = Payload(3, 1, 2, codes=c)
    assert (p.kind, p.d, p.n) == ("codes", 6, 16)
    q = _packed_payload(rng, 0, 0, 1)
    assert (q.kind, q.d, q.n) == ("packed", 6, 16)


def test_bounded_queue_backpressure():
    q = BoundedQueue(2)
    assert q.offer(1) and q.offer(2)
    assert not q.offer(3) and q.rejected == 1       # reject, never block
    assert q.drain(10) == [1, 2] and len(q) == 0


def test_split_kinds_stable(rng):
    ps = [Payload(0, 0, 1, codes=_codes(rng)),
          _packed_payload(rng, 0, 1, 1),
          Payload(0, 0, 2, codes=_codes(rng))]
    codes, packed = split_kinds(ps)
    assert [p.seq for p in codes] == [1, 2] and packed == [ps[1]]


# -- exactly-once ingest cursors ---------------------------------------------

def test_ingest_duplicates_fold_zero_times(rng):
    log = IngestLog(2, 2)
    p = Payload(0, 0, 1, codes=_codes(rng))
    assert log.offer(p, tick=1) == [p]
    assert log.offer(p, tick=1) == []               # replay of accepted
    assert log.offer(p, tick=5) == []               # ... at any later tick
    early = Payload(0, 0, 3, codes=_codes(rng))
    assert log.offer(early, tick=5) == []           # parks in the buffer
    assert log.offer(early, tick=6) == []           # in-buffer duplicate
    assert int(log.duplicates[0]) == 3


def test_ingest_reorder_folds_in_order(rng):
    log = IngestLog(1, 1)
    p1, p2, p3 = (Payload(0, 0, s, codes=_codes(rng)) for s in (1, 2, 3))
    assert log.offer(p3, 1) == [] and log.offer(p2, 1) == []
    assert log.offer(p1, 1) == [p1, p2, p3]         # gap fills, in order
    assert int(log.cursors[0, 0]) == 3
    assert int(log.reordered[0]) == 2 and int(log.lost[0, 0]) == 0


def test_ingest_window_overflow_declares_gap(rng):
    log = IngestLog(1, 1, reorder_window=3)
    ps = {s: Payload(0, 0, s, codes=_codes(rng)) for s in (3, 4, 5, 6)}
    for s in (3, 4, 5):
        assert log.offer(ps[s], 1) == []
    out = log.offer(ps[6], 1)                       # buffer overflows
    assert out == [ps[3], ps[4], ps[5], ps[6]]      # survivors fold
    assert int(log.lost[0, 0]) == 2                 # seqs 1, 2 declared lost
    assert log.degraded_tenants().tolist() == [True]


def test_ingest_deadline_flushes_overdue(rng):
    log = IngestLog(1, 1, reorder_ticks=2)
    p2 = Payload(0, 0, 2, codes=_codes(rng))
    assert log.offer(p2, tick=1) == []
    assert log.flush_overdue(tick=2) == []          # not overdue yet
    assert log.flush_overdue(tick=3) == [p2]        # deadline: gap declared
    assert int(log.lost[0, 0]) == 1 and log.buffered() == 0


def test_ingest_replay_is_idempotent():
    log = IngestLog(1, 1)
    assert log.replay(0, 0, 1) and log.replay(0, 0, 2)
    assert not log.replay(0, 0, 2)                  # superset replays skip
    assert not log.replay(0, 0, 1)
    assert log.replay(0, 0, 5) and int(log.lost[0, 0]) == 2  # gap jump
    assert int(log.cursors[0, 0]) == 5


# -- write-ahead journal -----------------------------------------------------

def test_journal_roundtrip_both_kinds(tmp_path, rng):
    path = str(tmp_path / "j.log")
    sent = [Payload(1, 0, 1, codes=_codes(rng)),
            _packed_payload(rng, 2, 1, 7)]
    sent.append(Payload(3, 2, 4, codes=(_codes(rng) > 0).astype(np.int8),
                        bits=True))
    j = FoldJournal(path)
    for i, p in enumerate(sent):
        j.append(p, tick=10 + i)
    j.close()
    records, torn, valid = read_journal(path)
    assert not torn and valid == os.path.getsize(path)
    assert [t for t, _ in records] == [10, 11, 12]
    for (_, got), p in zip(records, sent):
        assert (got.tenant, got.machine, got.seq, got.kind, got.n,
                got.bits) == \
            (p.tenant, p.machine, p.seq, p.kind, p.n, p.bits)
        ref = p.codes if p.kind == "codes" else p.packed
        other = got.codes if p.kind == "codes" else got.packed
        assert np.array_equal(ref, other)


def test_journal_torn_tail_truncates(tmp_path, rng):
    path = str(tmp_path / "j.log")
    j = FoldJournal(path)
    for s in (1, 2, 3):
        j.append(Payload(0, 0, s, codes=_codes(rng)), tick=s)
    j.close()
    raw = open(path, "rb").read()
    two, _, intact_valid = read_journal(path)
    # torn mid-record: the durable prefix survives, the tail vanishes
    open(path, "wb").write(raw[:len(raw) - 11])
    records, torn, valid = read_journal(path)
    assert torn and [p.seq for _, p in records] == [1, 2]
    # valid_bytes = end of frame 2: truncating there restores a clean
    # segment (the repair recovery applies before reopening for append)
    os.truncate(path, valid)
    records, torn, _ = read_journal(path)
    assert not torn and [p.seq for _, p in records] == [1, 2]
    # corrupt one payload byte of the last frame: CRC rejects it, and
    # the valid prefix ends where the corrupt frame starts
    open(path, "wb").write(raw[:-1] + bytes([raw[-1] ^ 0xFF]))
    records, torn, valid = read_journal(path)
    assert torn and [p.seq for _, p in records] == [1, 2]
    assert valid < intact_valid == os.path.getsize(path)
    assert len(two) == 3  # sanity: intact file had all three


def test_journal_segments_rotate_and_prune(tmp_path, rng):
    d = str(tmp_path)
    for step, seq in ((0, 1), (4, 2), (8, 3)):
        j = FoldJournal(segment_path(d, step))
        j.append(Payload(0, 0, seq, codes=_codes(rng)), tick=step + 1)
        j.close()
    assert [s for s, _ in list_segments(d)] == [0, 4, 8]
    assert [p.seq for _, p in iter_records(d)] == [1, 2, 3]
    prune_segments(d, keep=2)
    assert [s for s, _ in list_segments(d)] == [4, 8]


def test_scan_segments_rejects_torn_middle_segment(tmp_path, rng):
    """Rotated segments were closed + fsynced — a torn frame there is
    disk corruption, never crash residue, and must raise instead of
    silently under-replaying while newer segments still fold."""
    d = str(tmp_path)
    for step, seq in ((0, 1), (8, 2)):
        j = FoldJournal(segment_path(d, step))
        j.append(Payload(0, 0, seq, codes=_codes(rng)), tick=step + 1)
        j.close()
    with open(segment_path(d, 0), "ab") as f:
        f.write(b"torn")
    with pytest.raises(JournalCorruptionError):
        scan_segments(d)
    with pytest.raises(JournalCorruptionError):
        list(iter_records(d))
    # ... but the NEWEST segment may be torn: that is the legal
    # crash-mid-append state, reported per segment for the repair
    os.truncate(segment_path(d, 0),
                os.path.getsize(segment_path(d, 0)) - 4)
    with open(segment_path(d, 8), "ab") as f:
        f.write(b"torn")
    scans = scan_segments(d)
    assert [s.torn for s in scans] == [False, True]
    assert scans[1].total_bytes - scans[1].valid_bytes == 4
    assert [p.seq for _, p in iter_records(d)] == [1, 2]


def test_recovery_truncates_torn_tail_so_later_appends_survive(
        tmp_path, rng):
    """THE torn-tail regression: a crash can tear a frame mid-write;
    recovery must truncate the garbage before reopening the segment for
    append, or every record journaled AFTER it is invisible to the NEXT
    recovery — acked + folded payloads silently lost on a second crash."""
    cfg = ServeConfig(tenants=1, machines=1, d=6, block_n=16,
                      snapshot_every=0)
    d = str(tmp_path)
    payloads = [Payload(0, 0, s + 1, codes=_codes(rng)) for s in range(6)]
    srv = StructureServer(cfg, d)
    for p in payloads[:3]:
        srv.submit(p)
    srv.run_tick()
    srv.close()
    seg = segment_path(d, 0)
    with open(seg, "ab") as f:
        f.write(b"GJ" + b"\xee")    # torn in-flight frame (partial header)
    srv = StructureServer(cfg, d)   # recovery repairs the tail
    assert srv.torn_segments == 1 and srv.torn_bytes_dropped == 3
    assert srv.recovered_records == 3
    for p in payloads[3:]:          # journaled + acked AFTER the repair
        srv.submit(p)
    srv.run_tick()
    srv.close()
    srv = StructureServer(cfg, d)   # second recovery must see all six
    assert srv.torn_segments == 0 and srv.recovered_records == 6
    ref = _fold_reference(payloads, d=6)[0]
    assert np.array_equal(np.asarray(ref.gram, np.float64),
                          srv.table.gram[0])
    assert int(srv.table.n[0]) == ref.n
    assert int(srv.log.cursors[0, 0]) == 6
    srv.close()


# -- TenantTable batched folds ----------------------------------------------

def _fold_reference(payloads, d, method="sign", rate=1):
    refs = {}
    for p in payloads:
        sg = refs.setdefault(
            p.tenant, StreamingGram(d=d, method=method, rate=rate))
        if p.kind == "codes":
            c = ((2 * p.codes.astype(np.int8) - 1).astype(np.int8)
                 if p.bits else p.codes)
            sg.update_codes(jnp.asarray(c))
        else:
            sg.update_packed(jnp.asarray(p.packed), p.n)
    return refs


def test_table_fold_matches_streaming_bitwise(rng):
    t = TenantTable(tenants=4, d=6, block_n=24, max_slots=4)
    ps = []
    for i in range(13):  # mixed kinds, ragged n, several tenants
        tenant, n = int(rng.integers(0, 4)), int(rng.integers(1, 25))
        if rng.random() < 0.5:
            ps.append(Payload(tenant, 0, i + 1, codes=_codes(rng, n=n)))
        else:
            ps.append(_packed_payload(rng, tenant, 1, i + 1, n=n))
    rows = t.fold(ps)
    assert rows == sum(p.n for p in ps)
    for tenant, sg in _fold_reference(ps, d=6).items():
        assert np.array_equal(np.asarray(sg.gram, np.float64),
                              t.gram[tenant])
        assert sg.n == int(t.n[tenant])


def test_table_fold_grouping_invariance(rng):
    """Bit-identical accumulators no matter how ticks batch the payloads
    — the property crash replay rests on (sign path: exact integers)."""
    ps = [Payload(int(rng.integers(0, 3)), 0, i + 1,
                  codes=_codes(rng, n=int(rng.integers(1, 17))))
          for i in range(12)]
    a = TenantTable(tenants=3, d=6, block_n=16, max_slots=2)
    b = TenantTable(tenants=3, d=6, block_n=16, max_slots=8)
    a.fold(ps)
    for lo in range(0, 12, 3):                      # different tick grouping
        b.fold(ps[lo:lo + 3])
    assert np.array_equal(a.gram, b.gram) and np.array_equal(a.n, b.n)


@pytest.mark.parametrize("rate", [1, 2])
def test_table_fold_persymbol(rng, rate):
    t = TenantTable(tenants=2, d=5, method="persymbol", rate=rate,
                    block_n=16, max_slots=4)
    ps = [Payload(i % 2, 0, i + 1,
                  codes=rng.integers(0, 1 << rate,
                                     size=(int(rng.integers(1, 17)), 5)
                                     ).astype(np.int8))
          for i in range(6)]
    t.fold(ps)
    for tenant, sg in _fold_reference(
            ps, d=5, method="persymbol", rate=rate).items():
        # f32 streaming accumulator vs the table's f64 one round
        # differently — value equality is allclose, not bitwise
        assert np.allclose(np.asarray(sg.gram, np.float64),
                           t.gram[tenant], rtol=1e-6, atol=1e-5)
    # determinism: an identical re-fold reproduces the bits exactly
    t2 = TenantTable(tenants=2, d=5, method="persymbol", rate=rate,
                     block_n=16, max_slots=4)
    t2.fold(ps)
    assert np.array_equal(t.gram, t2.gram)
    if rate == 1:
        # 2-level codebook takes the integer sign path: each payload's
        # c^2 * S term is bit-stable under batching, and the f64 sum of
        # those terms is exact -> grouping-invariant accumulators
        t3 = TenantTable(tenants=2, d=5, method="persymbol", rate=1,
                         block_n=16, max_slots=2)
        for p in ps:
            t3.fold([p])
        assert np.array_equal(t.gram, t3.gram)


def test_table_sign_masked_zero_codes_drop_out(rng):
    """A 0 inside a ±1 sign payload is a MASKED entry (e.g. a faulted
    wire symbol): it must contribute nothing to the contraction — not
    silently fold as -1."""
    c = _codes(rng, n=12, d=6)
    c[np.asarray(rng.random(c.shape) < 0.3)] = 0
    assert (c == 0).any()
    t = TenantTable(tenants=1, d=6, block_n=16)
    t.fold([Payload(0, 0, 1, codes=c)])
    want = c.astype(np.int64).T @ c.astype(np.int64)
    assert np.array_equal(t.gram[0], want.astype(np.float64))
    assert int(t.n[0]) == 12


def test_table_bit_codes_fold_as_signs(rng):
    """bits=True marks a {0,1} wire: 0 is a true -1, never a mask."""
    bits = rng.integers(0, 2, size=(10, 6)).astype(np.int8)
    t = TenantTable(tenants=1, d=6, block_n=16)
    t.fold([Payload(0, 0, 1, codes=bits, bits=True)])
    pm1 = 2 * bits.astype(np.int64) - 1
    assert np.array_equal(t.gram[0], (pm1.T @ pm1).astype(np.float64))


def test_table_rejects_bad_payloads(rng):
    t = TenantTable(tenants=2, d=6, block_n=16)
    with pytest.raises(ValueError):
        t.fold([Payload(0, 0, 1, codes=_codes(rng, n=17))])  # n > block_n
    with pytest.raises(ValueError):
        t.fold([Payload(5, 0, 1, codes=_codes(rng))])        # unknown tenant
    with pytest.raises(ValueError):
        t.fold([Payload(0, 0, 1, codes=_codes(rng, d=4))])   # wrong d
    with pytest.raises(ValueError):                          # not a sign
        t.fold([Payload(0, 0, 1, codes=np.full((4, 6), 2, np.int8))])
    with pytest.raises(ValueError):                          # not a bit
        t.fold([Payload(0, 0, 1, codes=-np.ones((4, 6), np.int8),
                        bits=True)])
    with pytest.raises(ValueError):                          # bits ∉ persymbol
        TenantTable(tenants=1, d=6, method="persymbol", rate=2,
                    block_n=16).fold(
            [Payload(0, 0, 1, codes=np.ones((4, 6), np.int8), bits=True)])
    with pytest.raises(ValueError):                          # bits ∉ packed
        Payload(0, 0, 1, packed=np.zeros((6, 2), np.uint8), n=3, bits=True)


def _corr_gram(corr, n):
    """Sign-method Gram whose estimated correlation recovers ``corr``."""
    return np.sin(np.asarray(corr) * np.pi / 2) * n


def _chain_corr(d, rho=0.8):
    i = np.arange(d)
    return rho ** np.abs(i[:, None] - i[None, :])


def test_table_resolve_counts_drift():
    d, n = 8, 1000
    t = TenantTable(tenants=1, d=d)
    t.gram[0] = _corr_gram(_chain_corr(d), n)
    t.n[0] = n
    s = t.resolve(np.asarray([0]))
    chain = t.adj[0].copy()
    assert chain.sum() == 2 * (d - 1)               # first solve: a chain
    assert s == {"solved": 1, "drifted": 1, "drift_edges": d - 1}
    star = np.full((d, d), 0.05)                    # hub rewires the tree
    star[0, :] = star[:, 0] = 0.9
    np.fill_diagonal(star, 1.0)
    t.gram[0] = _corr_gram(star, n)
    s = t.resolve(np.asarray([0]))
    assert t.adj[0, 0].sum() == d - 1               # now a star on node 0
    sym_diff = int((t.adj[0] ^ chain).sum()) // 2   # edge symmetric diff
    assert s["drift_edges"] == sym_diff > 0
    assert int(t.drift[0]) == (d - 1) + sym_diff


def test_table_resolve_cadence():
    t = TenantTable(tenants=2, d=4, resolve_min_new=10)
    assert not t.needs_resolve().any()              # empty: nothing due
    t.gram[0] = _corr_gram(_chain_corr(4), 5)
    t.n[0] = 5
    assert not t.needs_resolve().any()              # below min_new
    t.n[0] = 10
    assert t.needs_resolve().tolist() == [True, False]
    t.resolve(np.flatnonzero(t.needs_resolve()))
    assert not t.needs_resolve().any()              # solved_n caught up


def test_table_resolve_counts_exact_past_f32(rng):
    """The solve normalizes Grams by the int64 counts in float64 on the
    host: counts beyond 2^24 (where f32 rounds) must still solve to the
    right structure, and two tenants encoding the IDENTICAL correlation
    at counts that collide in f32 (2^24, 2^24 + 1) must agree — the
    accumulators are designed to grow forever."""
    d = 8
    corr = _chain_corr(d)
    t = TenantTable(tenants=2, d=d)
    for slot, n in enumerate(((1 << 24), (1 << 24) + 1)):
        t.gram[slot] = _corr_gram(corr, n)
        t.n[slot] = n
    t.resolve(np.arange(2))
    i = np.arange(d)
    chain = np.abs(i[:, None] - i[None, :]) == 1
    assert np.array_equal(t.adj[0], chain)
    assert np.array_equal(t.adj[1], chain)


def test_table_degraded_tenant_solves_finite():
    t = TenantTable(tenants=1, d=4)
    t.n[0] = 1                                      # n_eff < 2: neutralized
    t.gram[0] = np.eye(4)
    t.resolve(np.asarray([0]))
    assert t.adj[0].sum() == 2 * 3                  # still a (arbitrary) tree


def test_table_state_roundtrip_and_streaming_export(rng):
    t = TenantTable(tenants=3, d=6, block_n=16)
    ps = [Payload(i % 3, 0, i + 1, codes=_codes(rng)) for i in range(6)]
    t.fold(ps)
    t.resolve(np.arange(3))
    u = TenantTable(tenants=3, d=6, block_n=16)
    u.load_state(t.state_tree())
    for k, v in t.state_tree().items():
        assert np.array_equal(v, u.state_tree()[k]), k
    sg = t.to_streaming(1)
    merged = t.to_streaming(0).merge(sg).merge(t.to_streaming(2))
    total = _fold_reference(ps, d=6)
    want = sum(np.asarray(r.gram, np.float64) for r in total.values())
    assert np.array_equal(np.asarray(merged.gram, np.float64), want)
    assert merged.n == int(t.n.sum())


# -- StructureServer end-to-end ----------------------------------------------

_TCFG = TrafficConfig(tenants=5, machines=3, ticks=10, n=24, d=8,
                      bit_fraction=0.25,   # exercise the {0,1} bits wire
                      p_duplicate=0.25, p_reorder=0.25, p_drop=0.1, seed=7)
_SCFG = dict(tenants=5, machines=3, d=8, block_n=24, snapshot_every=3,
             reorder_ticks=2, keep_segments=2)


def _run_trace(srv, trace, extra_ticks=4):
    for batch in trace:
        for p in batch:
            srv.submit(p)
        srv.run_tick()
    for _ in range(extra_ticks):                    # drain reorder deadlines
        srv.run_tick()
    srv.force_resolve()
    return srv


def test_server_folds_trace_exactly_once(tmp_path):
    trace = make_trace(_TCFG)
    srv = _run_trace(
        StructureServer(ServeConfig(**_SCFG), str(tmp_path)), trace)
    # fold everything DELIVERED exactly once (duplicates excluded), in any
    # order — sign-path accumulators are exact integers, so the reference
    # fold matches bit for bit even though its order differs
    refs = _fold_reference(unique_payloads(trace), d=8)
    for tenant, sg in refs.items():
        assert np.array_equal(np.asarray(sg.gram, np.float64),
                              srv.table.gram[tenant])
        assert sg.n == int(srv.table.n[tenant])
    assert int(srv.log.duplicates.sum()) > 0        # pathologies did occur
    assert int(srv.log.reordered.sum()) > 0
    assert int(srv.log.lost.sum()) > 0 and srv.log.degraded_tenants().any()
    assert srv.log.buffered() == 0                  # nothing stuck
    srv.close()


def test_server_restart_without_crash_is_bit_identical(tmp_path):
    trace = make_trace(_TCFG)
    a = _run_trace(
        StructureServer(ServeConfig(**_SCFG), str(tmp_path / "a")), trace)
    b = StructureServer(ServeConfig(**_SCFG), str(tmp_path / "b"))
    half = len(trace) // 2
    for batch in trace[:half]:
        for p in batch:
            b.submit(p)
        b.run_tick()
    b.close()                                       # clean shutdown mid-trace
    b = StructureServer(ServeConfig(**_SCFG), str(tmp_path / "b"))
    # the producer re-sends everything unacked (reorder buffers are
    # volatile); cursors skip what already folded
    for p in [q for batch in trace[:half] for q in batch]:
        b.submit(p)
    b.run_tick()
    _run_trace(b, trace[half:])
    sa, sb = a.comparable_state(), b.comparable_state()
    assert all(np.array_equal(sa[k], sb[k]) for k in sa)
    a.close(), b.close()


def test_server_watchdog_fires_for_stale_tenant(tmp_path, rng):
    cfg = ServeConfig(tenants=2, machines=1, d=6, block_n=16,
                      resolve_min_new=10 ** 6,      # cadence never triggers
                      watchdog_ticks=3, snapshot_every=0)
    srv = StructureServer(cfg, str(tmp_path))
    srv.submit(Payload(0, 0, 1, codes=_codes(rng)))
    stats = srv.run_tick()
    assert stats["solved"] == 0                     # cadence says not yet
    solved = sum(srv.run_tick()["solved"] for _ in range(3))
    assert int(srv.watchdog_fires.sum()) == 1       # deadline forced it
    assert solved == 1 and srv.table.adj[0].any()
    srv.close()


def test_server_cusum_alarms_on_midtrace_structure_change(tmp_path):
    """CUSUM drift alarms: a seeded trace whose generating chain is
    column-permuted mid-trace (an INTERLEAVE — a reversal would map the
    chain onto itself and change nothing) must fire the detector, while
    the stationary prefix of the very same trace must not."""
    d = 8
    perm = tuple(range(0, d, 2)) + tuple(range(1, d, 2))
    base = dict(tenants=2, machines=2, ticks=24, n=64, d=d, rho=0.75,
                packed_fraction=0.0, seed=13)
    scfg = dict(tenants=2, machines=2, d=d, block_n=64, snapshot_every=0,
                cusum_k=0.5, cusum_h=1.0)
    still = _run_trace(
        StructureServer(ServeConfig(**scfg), str(tmp_path / "still")),
        make_trace(TrafficConfig(**base)))
    moved = _run_trace(
        StructureServer(ServeConfig(**scfg), str(tmp_path / "moved")),
        make_trace(TrafficConfig(**base, permutation=perm,
                                 permute_from_tick=12)))
    assert int(still.cusum_alarms.sum()) == 0       # stationary: quiet
    assert int(moved.cusum_alarms.sum()) >= 1       # change-point: fires
    tele = moved.run_tick()
    assert tele["cusum_alarms"] == int(moved.cusum_alarms.sum())
    still.close(), moved.close()


def test_cusum_state_survives_snapshot_recovery(tmp_path):
    """The CUSUM statistic and alarm counts are durable state: a server
    recovered from snapshot + journal reports the same alarm history."""
    d = 8
    perm = tuple(range(0, d, 2)) + tuple(range(1, d, 2))
    trace = make_trace(TrafficConfig(
        tenants=2, machines=2, ticks=24, n=64, d=d, rho=0.75,
        packed_fraction=0.0, seed=13, permutation=perm,
        permute_from_tick=12))
    scfg = dict(tenants=2, machines=2, d=d, block_n=64, snapshot_every=4,
                cusum_k=0.5, cusum_h=1.0)
    a = _run_trace(
        StructureServer(ServeConfig(**scfg), str(tmp_path)), trace)
    alarms, stat = a.cusum_alarms.copy(), a.cusum_stat.copy()
    assert int(alarms.sum()) >= 1
    a.close()
    b = StructureServer(ServeConfig(**scfg), str(tmp_path))
    assert np.array_equal(b.cusum_alarms, alarms)
    assert np.array_equal(b.cusum_stat, stat)
    b.close()


def test_traffic_permutation_none_is_byte_identical():
    """permutation=None consumes no RNG draws: the trace equals the
    pre-permutation generator's byte for byte."""
    base = dict(tenants=2, machines=1, ticks=4, n=8, d=6, seed=3)
    t0 = make_trace(TrafficConfig(**base))
    t1 = make_trace(TrafficConfig(**base, permutation=tuple(range(6)),
                                  permute_from_tick=10 ** 9))
    assert len(t0) == len(t1)
    for b0, b1 in zip(t0, t1):
        assert len(b0) == len(b1)
        for p0, p1 in zip(b0, b1):
            assert (p0.tenant, p0.machine, p0.seq) == (
                p1.tenant, p1.machine, p1.seq)
            if p0.kind == "codes":
                assert np.array_equal(p0.codes, p1.codes)
            else:
                assert np.array_equal(p0.packed, p1.packed)


def test_server_backpressure_counts(tmp_path, rng):
    cfg = ServeConfig(tenants=1, machines=1, d=6, block_n=16,
                      queue_capacity=2, snapshot_every=0)
    srv = StructureServer(cfg, str(tmp_path))
    oks = [srv.submit(Payload(0, 0, s + 1, codes=_codes(rng)))
           for s in range(5)]
    assert oks == [True, True, False, False, False]
    assert srv.run_tick()["rejected"] == 3
    srv.close()


_CHILD = """\
import sys
sys.path.insert(0, {src!r})
from repro.serve import ServeConfig, StructureServer
sys.path.insert(0, {here!r})
from test_serve import _SCFG, _TCFG, _run_trace
from repro.serve import make_trace

srv = StructureServer(
    ServeConfig(**_SCFG, crash_after_journal_records={crash}), sys.argv[1])
_run_trace(srv, make_trace(_TCFG))
print("SURVIVED")  # the hook must SIGKILL us before the trace completes
sys.exit(3)
"""


def _crash_child(tmp_path, crash_after: int) -> str:
    """Run the trace in a child that SIGKILLs itself after ``crash_after``
    journal records; returns the server directory it left behind."""
    crash_dir = str(tmp_path / "crash")
    script = tmp_path / "child.py"
    script.write_text(_CHILD.format(
        src=SRC, here=os.path.dirname(os.path.abspath(__file__)),
        crash=crash_after))
    r = subprocess.run([sys.executable, str(script), crash_dir],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == -9, (r.returncode, r.stdout, r.stderr)
    return crash_dir


@pytest.mark.parametrize("crash_after", [17, 55])
def test_crash_recovery_bit_identity(tmp_path, crash_after):
    """THE acceptance gate: SIGKILL mid-tick (between journal append and
    fold), restart, re-deliver everything unacked — recovered accumulators,
    cursors and structures equal the uninterrupted run's bit for bit,
    with duplicated + reordered + lost deliveries in the trace."""
    trace = make_trace(_TCFG)
    clean = _run_trace(
        StructureServer(ServeConfig(**_SCFG), str(tmp_path / "clean")),
        trace)
    crash_dir = _crash_child(tmp_path, crash_after)

    srv = StructureServer(ServeConfig(**_SCFG), crash_dir)  # replays WAL
    assert srv.recovered_records > 0 or srv.snapshot_step > 0
    _run_trace(srv, trace)        # producer re-sends all unacked payloads
    sc, sr = clean.comparable_state(), srv.comparable_state()
    for k in sc:
        assert np.array_equal(sc[k], sr[k]), f"{k} diverged after crash"
    clean.close(), srv.close()


@pytest.mark.parametrize("crash_after", [17, 55])
def test_crash_restart_replays_journal_and_drains(tmp_path, crash_after):
    """The records journaled after the last snapshot are replayed on
    restart (none is lost with the killed process), and once producers
    re-send everything unacked no payload is left in a reorder buffer."""
    srv = StructureServer(ServeConfig(**_SCFG),
                          _crash_child(tmp_path, crash_after))
    assert srv.recovered_records > 0
    _run_trace(srv, make_trace(_TCFG))
    assert srv.log.buffered() == 0
    srv.close()
