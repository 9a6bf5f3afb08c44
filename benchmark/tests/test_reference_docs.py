"""``reference_docs.DocsChecker`` on small hand-written histories,
including the ones that MUST read not correct: a view whose head is
right and something behind it is not, a broker that was told and
handed nothing, a write the final tree lost."""

import importlib.util
import os

import reference_docs as rd

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
#: two tables of 100 and 3,200 segments at 160 B a segment: document
#: 2t is table t's IDEALSTATES, 2t + 1 its EXTERNALVIEW
BASE = [16000, 16000, 512000, 512000]
GROW = 160
WATCHED = [1, 3]


def docs(seed=11, brokers=2):
    return rd.DocsChecker(seed, BASE, GROW, brokers, WATCHED)


def change(c, table, zxid, brokers=(0, 1), t=1.0):
    """One sound change of ``table``: both documents written and
    acknowledged, every broker told and shown the new version."""
    for doc in (2 * table, 2 * table + 1):
        data = c.next_write(doc)
        v = c.write_acked(doc, c.version[doc] + 1, zxid + (doc & 1))
        assert len(data) == BASE[doc] + GROW * v
    doc = 2 * table + 1
    v = c.version[doc]
    for b in brokers:
        c.notified(b, doc)
        c.emitted(b, doc, t, c.expected(doc, v), c.size(doc, v), v, v)


def arm(c, brokers=(0, 1)):
    for doc in WATCHED:
        for b in brokers:
            c.armed(b, doc)
            c.emitted(b, doc, 0.5, c.initial(doc), BASE[doc], 0, 0)


def test_clean_history_whatever_the_interleaving():
    c = docs()
    arm(c)
    change(c, 1, 100)
    change(c, 0, 110)
    change(c, 1, 120, brokers=(1, 0))
    assert c.finish() == 0
    for doc in range(4):
        v = c.version[doc]
        c.final(doc, c.expected(doc, v), c.size(doc, v), v, 'member 2')
    c.final_ephemeral(0, b'x' * 8, 0x1000, b'x' * 8, 0x1000, 'member 1')
    assert c.bad.count == 0 and c.checked > 20
    assert c.version == [1, 1, 2, 2]
    assert c.seen_at(0, 3, 2) == 1.0 and c.seen_at(0, 3, 3) is None
    # a document grows by a segment a version, and two versions differ
    assert len(c.expected(3, 2)) == 512000 + 320
    assert c.expected(3, 1)[:1000] != c.expected(3, 2)[:1000]
    # payloads are the seed's; a large seed (the driver's are) works
    assert docs(12).expected(3, 1) != docs(11).expected(3, 1)
    assert len(docs(2 ** 31 + 12345).expected(2, 3)) == 512000 + 480


def test_a_view_is_compared_over_its_whole_length():
    c = docs()
    arm(c)
    good = c.expected(3, 0)
    at = 300 * 1024
    c.armed(0, 3)
    c.emitted(0, 3, 1.0, good[:at] + bytes([good[at] ^ 1]) + good[at + 1:],
              len(good), 0, 0)
    assert c.bad.by_kind == {'payload': 1}
    assert 'from byte %d on' % (at,) in c.bad.first[0]
    c.armed(0, 3)
    c.emitted(0, 3, 1.0, good[:-1] + bytes([good[-1] ^ 128]), len(good),
              0, 0)
    assert c.bad.by_kind == {'payload': 2}


def test_length_stat_length_and_the_size_of_the_version():
    c = docs()
    good = c.expected(1, 0)
    c.emitted(0, 1, 1.0, good[:-1], len(good), 0, 0)        # short body
    c.emitted(0, 1, 1.0, good, len(good) - 1, 0, 0)         # stat lies
    # version 1's bytes under version 0's stat: the wrong size
    c.emitted(0, 1, 1.0, c.expected(1, 1), 16160, 0, 1)
    assert c.bad.by_kind == {'data-length': 3}


def test_versions_never_go_back_and_none_is_from_the_future():
    c = docs()
    arm(c)
    change(c, 0, 100)
    c.armed(0, 1)
    c.emitted(0, 1, 2.0, c.expected(1, 0), 16000, 0, 1)
    assert c.bad.by_kind == {'stale-view': 1}
    c.armed(1, 1)
    c.emitted(1, 1, 2.0, c.expected(1, 2), 16320, 2, 1)
    assert c.bad.by_kind == {'stale-view': 1, 'future-read': 1}


def test_acked_version_is_the_count_of_the_writers_writes():
    c = docs()
    c.next_write(0)
    c.write_acked(0, 2, 100)
    assert c.bad.by_kind == {'write-version': 1}
    # after a write of unknown outcome the count may be one ahead
    c2 = docs()
    c2.write_unknown(2)
    c2.write_acked(2, 2, 100)
    assert c2.bad.count == 0


def test_told_and_handed_nothing_is_a_missed_change_whatever_comes_later():
    """The case ``drop_emit`` makes: broker 1's listener is not handed
    version 1 of document 3; the NEXT change shows it version 2, which
    covers "that version or a later one" — the owed view does not go
    away."""
    c = docs()
    arm(c)
    change(c, 1, 100, brokers=(0,))
    c.notified(1, 3)                    # told; the emission is swallowed
    change(c, 1, 110)
    assert c.seen_at(1, 3, 1) is not None
    assert c.finish() == 1
    assert c.bad.by_kind == {'missed-change': 1}
    assert 'handed no view' in c.bad.first[0]


def test_never_shown_is_a_missed_change():
    c = docs()
    arm(c)
    change(c, 1, 100, brokers=(0,))     # broker 1 neither told nor shown
    assert c.finish() == 1
    assert c.bad.by_kind == {'missed-change': 1}
    assert 'never shown' in c.bad.first[0]
    # an armed watcher that never emitted its first view is owed it
    c2 = docs()
    c2.armed(0, 1)
    assert c2.finish() == 1


def test_final_tree_lost_write_lost_doc_spliced_body_ephemeral():
    c = docs()
    change(c, 1, 100, brokers=())
    c.final(2, c.expected(2, 0), 512000, 0, 'member 1')     # old version
    c.final(3, None, 0, 0, 'member 1')
    good = c.expected(0, 0)
    c.final(0, good[:8000] + good[12000:] + good[8000:12000], 16000, 0,
            'member 2')
    c.final_ephemeral(4, None, 0, b'e', 7, 'member 0')
    c.final_ephemeral(5, b'e', 8, b'e', 7, 'member 0')
    assert c.bad.by_kind == {'lost-write': 1, 'lost-doc': 1, 'payload': 1,
                             'ephemeral': 2}


def test_a_gap_is_an_eviction():
    c = docs()
    c.gap(17, 'a disconnect')
    assert c.bad.by_kind == {'evicted': 1}


def test_imports_nothing_of_the_program():
    src = open(os.path.join(BENCH, 'reference_docs.py')).read()
    assert 'zkstream' not in src.split('"""', 2)[2]
    assert set(rd.KINDS) >= {'payload', 'data-length', 'missed-change',
                             'evicted', 'lost-write'}


def test_the_schedule_is_the_issues_rule():
    """Smooth weighted round-robin from a fixed start: the first steps
    by hand, the counts over one whole cycle, no seed anywhere."""
    spec = importlib.util.spec_from_file_location(
        'view_change', os.path.join(BENCH, 'engines', 'view_change.py'))
    vc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(vc)
    w = [100, 100, 100, 100, 200, 200, 200, 200, 400, 400, 400, 800, 800,
         1600, 3200, 6000]
    assert sum(w) == 14800
    gen = vc.schedule(w)
    seq = [next(gen) for _ in range(148)]
    # by hand: 6000 leads; then 3200 + 3200; then 1600 x 3; then t15 is
    # back at 9200; then t11 and t12 tie at 4000 and the lower goes
    assert seq[:5] == [15, 14, 13, 15, 11]
    for t, n in enumerate(w):
        assert seq.count(t) == n // 100
    # small weights by hand: [1, 2] gives 1 0 1 | 1 0 1 ...
    gen = vc.schedule([1, 2])
    assert [next(gen) for _ in range(6)] == [1, 0, 1, 1, 0, 1]
