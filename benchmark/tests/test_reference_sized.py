"""``reference_sized.SizedChecker`` on small hand-written histories,
including the ones that MUST read not correct: a large body whose head
is right and something behind it is not."""

import importlib.util
import os

import reference_sized as rs

HERE = os.path.dirname(os.path.abspath(__file__))
SIZES = [16, 300, 5000, 200 * 1024]
PATHS = ['/configs/cs0/lang/a.txt', '/configs/cs0/lang/b.txt',
         '/configs/cs0/schema.xml', '/configs/cs0/synonyms.txt']


def sized(seed=11):
    return rs.SizedChecker(seed, PATHS, SIZES)


def test_clean_history_whatever_the_interleaving():
    c = sized()
    assert [len(e) for e in c.expected] == SIZES
    c.listing(0, '/configs/cs0', ['schema.xml', 'lang', 'synonyms.txt'])
    c.listing(0, '/configs/cs0/lang', ['b.txt', 'a.txt'])
    for s in (0, 1):
        for idx in (3, 0, 2, 1, 3):
            c.read(s, idx, c.expected[idx], SIZES[idx], 0, 100 + idx)
    for idx in range(4):
        c.final(idx, c.expected[idx], SIZES[idx], 0, 'member 1')
    assert c.bad.count == 0 and c.checked == 2 + 10 + 4
    # payloads are the seed's: two seeds differ, a large seed (the
    # driver's are) makes what its own generator makes
    assert sized(12).expected[3] != c.expected[3]
    big = sized(2 ** 31 + 12345)
    assert big.expected[3] == rs.SizedPayloads(
        2 ** 31 + 12345, max(SIZES)).get(3, 0, SIZES[3])
    # two znodes of one size still differ
    same = rs.SizedChecker(11, PATHS[:2], [300, 300])
    assert same.expected[0] != same.expected[1]


def test_a_body_is_compared_over_its_whole_length():
    c = sized()
    good = c.expected[3]
    at = 150 * 1024
    c.read(0, 3, good[:at] + bytes([good[at] ^ 1]) + good[at + 1:],
           SIZES[3], 0, 7)
    assert c.bad.by_kind == {'payload': 1}
    assert 'from byte %d on' % (at,) in c.bad.first[0]
    # the last byte too
    c.read(0, 3, good[:-1] + bytes([good[-1] ^ 128]), SIZES[3], 0, 7)
    assert c.bad.by_kind == {'payload': 2}


def test_length_and_stat_length_and_version():
    c = sized()
    c.read(0, 2, c.expected[2][:-1], SIZES[2], 0, 5)        # short body
    c.read(0, 2, c.expected[2], SIZES[2] - 1, 0, 5)         # stat lies
    c.read(0, 2, c.expected[2] + b'\0', SIZES[2], 0, 5)     # long body
    assert c.bad.by_kind == {'data-length': 3}
    c.read(0, 1, c.expected[1], SIZES[1], 1, 5)     # nobody wrote it
    assert c.bad.by_kind == {'data-length': 3, 'version': 1}


def test_a_session_never_goes_back():
    c = sized()
    c.read(0, 0, c.expected[0], 16, 0, 90)
    c.read(1, 0, c.expected[0], 16, 0, 80)      # another session: fine
    c.read(0, 0, c.expected[0], 16, 0, 80)
    assert c.bad.by_kind == {'stale-read': 1}


def test_listing_final_tree_and_evictions():
    c = sized()
    c.listing(3, '/configs/cs0', ['schema.xml', 'synonyms.txt'])
    c.listing(3, '/configs/cs0/lang', ['a.txt', 'b.txt', 'c.txt'])
    assert c.bad.by_kind == {'listing': 2}
    assert "missing ['lang']" in c.bad.first[0]
    assert "unexpected ['c.txt']" in c.bad.first[1]
    c.final(1, None, 0, 0, 'member 2')
    c.final(2, c.expected[2][::-1], SIZES[2], 0, 'member 2')
    c.gap(5, 'a disconnect')
    assert c.bad.by_kind == {'listing': 2, 'lost-znode': 1, 'payload': 1,
                             'evicted': 1}


def _control(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, '..', 'controls', name + '.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_splice_large_keeps_length_and_head_and_is_caught(monkeypatch):
    """What the control does to a large body: same length, same first
    64 KiB, other bytes behind — ``payload``, and nothing else."""
    import asyncio

    monkeypatch.delenv('JAX_PLATFORMS', raising=False)     # full size
    ctl = _control('splice_large')
    c = sized()

    class Client:
        async def get(self, path, **kw):
            idx = PATHS.index(path)
            return c.expected[idx], SIZES[idx]
    cl = ctl.wrap_client(Client())
    spliced = 0
    for _ in range(ctl.EVERY):
        for idx, path in enumerate(PATHS):
            data, length = asyncio.run(cl.get(path))
            assert len(data) == length == SIZES[idx]
            if data != c.expected[idx]:
                spliced += 1
                assert idx == 3
                assert data[:ctl.HEAD] == c.expected[3][:ctl.HEAD]
                assert sorted(data) == sorted(c.expected[3])
            c.read(0, idx, data, length, 0, 9)
    assert spliced == 1 and c.bad.by_kind == {'payload': 1}
