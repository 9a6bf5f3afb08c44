"""The plain reference of the "read latest" cells
(``reference_ycsb_latest.py``): what it passes and what it catches, on
observations made by hand.  (tests/test_ycsb_latest.py, tier-1, holds
the same reference against the real engine on an in-process ensemble.)
"""

import reference_ycsb_latest as ref

LOADED, ROOM = 64, 32


def checker():
    return ref.LatestChecker(2 ** 31 + 9, LOADED, ROOM)


def test_names_and_bytes_are_made_from_the_seed_alone():
    a, b, c = checker(), checker(), ref.LatestChecker(3, LOADED, ROOM)
    assert a.paths == b.paths and len(set(a.paths)) == LOADED + ROOM
    assert a.paths != c.paths
    assert all(p.startswith('/benchmark/user') for p in a.paths)
    assert a.initial(70) == b.initial(70) != a.initial(71)
    assert len(a.initial(70)) == a.records.record_bytes == 1121
    assert a.exists(LOADED - 1) and not a.exists(LOADED)


def test_a_sound_history_passes_and_counts_its_allowed_misses():
    chk = checker()
    k = LOADED
    data = chk.create_sent(1, k)
    chk.miss(2, 0, k, 0.1, 0.2)             # still out
    chk.create_acked(1, 1, k)
    chk.miss(2, 0, k, 0.3, 0.4)             # acknowledged elsewhere
    chk.read(2, 0, k, data, 0, len(data), 500, 500, 0.5)
    chk.read(3, 2, 7, chk.initial(7), 0, 1121, 40, 40, 0.6)
    chk.final(k, data, 0, len(data), 500, 'member 2')
    chk.final(k + 1, None, 0, 0, 0, 'member 0')
    chk.settle()
    assert not chk.bad.first and chk.not_yet_visible == 2
    assert chk.checked == 7 and chk.czxid[k] == 500


def test_each_rule_of_a_miss():
    chk = checker()
    k = LOADED + 1
    data = chk.create_sent(1, k)
    chk.create_acked(1, 1, k)
    chk.miss(3, 0, 5, 1.0, 1.1)                 # 1: a loaded record
    chk.miss(1, 1, k, 1.0, 1.1)                 # 2: its own create
    chk.read(4, 2, k, data, 0, 1121, 600, 600, 2.0)
    chk.miss(4, 2, k, 2.1, 2.2)                 # 3: read it before
    chk.read(5, 0, 9, chk.initial(9), 0, 1121, 650, 650, 2.0)
    chk.miss(5, 0, k, 2.1, 2.2)                 # 4: saw zxid 650 >= 600
    chk.miss(6, 2, k, 2.1, 2.2)                 # 5: member 2 showed it
    assert chk.bad.by_kind == {'stale-miss': 5}
    chk.miss(6, 2, k, 1.9, 2.2)                 # sent before it did
    chk.miss(7, 0, k, 2.1, 2.2)                 # saw nothing, member 0
    assert chk.bad.count == 5 and chk.not_yet_visible == 2


def test_the_read_back():
    chk = checker()
    k = LOADED + 2
    data = chk.create_sent(1, k)
    chk.create_acked(1, 0, k)
    assert chk.readback_member(k, 3) == 1
    chk.final(k, None, 0, 0, 0, 'member 1')
    chk.final(4, None, 0, 0, 0, 'member 1')
    chk.final(k, data[:-2] + b'xx', 0, 1121, 700, 'member 1')
    chk.final(LOADED + 9, chk.initial(LOADED + 9), 0, 1121, 701,
              'member 0')
    assert chk.bad.by_kind == {'lost-create': 1, 'final-tree': 2,
                               'phantom': 1}
