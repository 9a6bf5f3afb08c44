"""``reference_ycsb`` on small hand-written histories, including the
ones that MUST read not correct: each rule tripped once — a flipped
byte, a record of another length, a field nobody wrote, two bodies for
one version, a read ahead of an acknowledgement that the acknowledgement
contradicts, a version nobody is acknowledged for, a version from the
future, two acknowledgements at one version, an old read, a read below
the session's own write, a gap, a lost write, a final tree with other
bytes.  The data: keys and records as the binding stores them."""

import json

import reference_ycsb as ry

N = 64


def seen(n=3, seed=7):
    """Session 1 rewrote key 5 ``n`` times, each acknowledged; session
    2 read every version."""
    chk = ry.YcsbChecker(seed, N)
    data = chk.initial(5)
    size = len(data)
    chk.read(2, 5, data, 0, 50, size)
    versions = [data]
    for v in range(1, n + 1):
        chk.read(1, 5, data, v - 1, 50 + v - 1, size)
        data = chk.rewrite(5, data, v % 10)
        chk.write_acked(1, 0, 5, v, 50 + v, data)
        chk.read(2, 5, data, v, 50 + v, size)
        versions.append(data)
    assert not chk.bad.first
    return chk, versions, size


def test_records_are_the_bindings_shape():
    rec = ry.Records(2 ** 31 + 5, 4096)
    assert len(set(rec.names)) == 4096
    assert all(n.startswith('user') and n[4:].isdigit()
               and 19 <= len(n) - 4 <= 20 for n in rec.names)
    assert rec.paths[0] == '/benchmark/' + rec.names[0]
    data = rec.initial(17)
    assert len(data) == rec.record_bytes == 1121
    doc = json.loads(data)
    assert list(doc) == ['field%d' % (f,) for f in range(10)]
    assert all(len(v) == 100 and v.isalnum() for v in doc.values())
    # what json.dumps makes of the map with no space is the record
    assert json.dumps(doc, separators=(',', ':')).encode() == data
    assert rec.fields(data) == [v.encode() for v in doc.values()]
    # the same seed, the same data; another seed, other data
    again = ry.Records(2 ** 31 + 5, 4096)
    assert again.names == rec.names and again.initial(17) == data
    other = ry.Records(2 ** 31 + 6, 4096)
    assert other.names != rec.names and other.initial(17) != data
    # an update replaces ONE field and keeps the length
    new = rec.replace(data, 3, rec.value(17, 3, 1))
    a, b = rec.fields(data), rec.fields(new)
    assert [f for f in range(10) if a[f] != b[f]] == [3]
    assert rec.fields(data[:-1]) is None
    assert rec.fields(data.replace(b'"field4"', b'"fielD4"')) is None
    # a field's successive values differ
    assert len({rec.value(17, 3, w) for w in range(2000)}) == 2000


def test_sound_history_with_many_writers_is_clean():
    chk = ry.YcsbChecker(9, N)
    base = chk.initial(3)
    size = len(base)
    chk.read(1, 3, base, 0, 10, size)
    chk.read(2, 3, base, 0, 10, size)
    one = chk.rewrite(3, base, 0)
    two = chk.rewrite(3, base, 1)
    chk.read(3, 3, one, 1, 11, size)        # ahead of its ack
    chk.write_acked(2, 1, 3, 2, 12, two)    # the later write's ack first
    chk.write_acked(1, 0, 3, 1, 11, one)
    chk.read(3, 3, two, 2, 12, size)
    # a write of unknown outcome may have landed, or not
    three = chk.rewrite(3, two, 5)
    chk.write_unknown(3)
    chk.read(4, 3, three, 3, 13, size)
    chk.settle()
    chk.final(3, three, 3, size, 'member 2')
    chk.final(4, chk.initial(4), 0, size, 'member 1')
    assert not chk.bad.first, chk.bad.first
    assert (chk.newest[3], chk.newest_member[3]) == (2, 1)
    assert chk.checked == 9
    # ... and had it not landed
    chk.final(3, two, 2, size, 'member 2')
    assert not chk.bad.first


def test_payload():
    chk, versions, size = seen()
    data = versions[3]
    for at in (0, 11, size // 2, size - 1):
        chk.read(2, 5, data[:at] + bytes([data[at] ^ 1]) + data[at + 1:],
                 3, 53, size)
    assert chk.bad.by_kind == {'payload': 4}
    chk.read(2, 5, data[:-1], 3, 53, size - 1)
    chk.read(2, 5, data, 3, 53, size + 1)
    assert chk.bad.by_kind == {'payload': 6}
    # ahead of an acknowledgement: a field nobody wrote
    chk.rewrite(5, data, 0)
    forged = chk.records.replace(data, 0, b'z' * 100)
    chk.read(3, 5, forged, 4, 54, size)
    assert chk.bad.by_kind == {'payload': 7}
    # another key's record is no record of this one
    chk.read(4, 6, chk.initial(5), 0, 40, size)
    assert chk.bad.by_kind == {'payload': 8}


def test_version_bytes():
    chk, versions, size = seen()
    chk.read(2, 5, versions[2], 3, 53, size)
    assert chk.bad.by_kind == {'version-bytes': 1}
    sent = chk.rewrite(5, versions[3], 0)
    chk.read(3, 5, sent, 4, 54, size)
    chk.read(4, 5, versions[3], 4, 54, size)
    assert chk.bad.by_kind == {'version-bytes': 2}
    chk.write_acked(1, 0, 5, 4, 54, chk.rewrite(5, versions[3], 1))
    assert chk.bad.by_kind == {'version-bytes': 3}
    chk.rewrite(5, versions[3], 2)
    chk.read(3, 5, sent, 5, 55, size)
    chk.settle()        # nobody is acknowledged for version 5
    assert chk.bad.by_kind == {'version-bytes': 4}


def test_write_version_and_future_read():
    chk, versions, size = seen()
    chk.read(3, 5, versions[3], 4, 54, size)
    assert chk.bad.by_kind == {'future-read': 1}
    chk.write_acked(3, 2, 5, 9, 59, versions[3])     # above the writes sent
    chk.write_acked(3, 2, 5, 2, 59, versions[2])     # version 2 again
    chk.write_acked(3, 2, 5, 0, 59, versions[0])     # the load's
    assert chk.bad.by_kind['write-version'] == 3
    # acknowledgements that skip a version
    chk2, versions, size = seen(2)
    sent = chk2.rewrite(5, versions[2], 0)
    chk2.rewrite(5, versions[2], 1)
    chk2.write_acked(1, 0, 5, 4, 54, sent)
    chk2.write_unknown(6)
    chk2.settle()
    assert chk2.bad.by_kind == {'write-version': 1}


def test_stale_read_and_evicted():
    chk, versions, size = seen()
    chk.read(2, 5, versions[2], 2, 52, size)
    chk.read(1, 5, versions[1], 1, 51, size)    # below its own write
    chk.read(2, 5, versions[3], 3, 52, size)    # the mzxid alone
    assert chk.bad.by_kind == {'stale-read': 3}
    chk.read(9, 5, versions[1], 1, 51, size)    # another session may
    assert chk.bad.count == 3
    chk.gap(4, 'disconnect')
    assert chk.bad.by_kind['evicted'] == 1


def test_final_tree_and_lost_write():
    chk, versions, size = seen()
    chk.settle()
    chk.final(5, versions[2], 2, size, 'member 1')
    assert chk.bad.by_kind == {'lost-write': 1}
    chk.final(5, versions[3], 4, size, 'member 1')
    assert chk.bad.by_kind == {'lost-write': 2}
    chk.final(5, versions[2], 3, size, 'member 1')
    chk.final(6, None, 0, 0, 'member 1')
    chk.final(7, chk.initial(8), 0, size, 'member 1')
    chk.final(8, chk.initial(8), 0, size + 1, 'member 1')
    assert chk.bad.by_kind == {'lost-write': 2, 'final-tree': 4}
    chk.final(5, versions[3], 3, size, 'member 1')
    assert chk.bad.count == 6
