"""The reference model and every validator on small hand-written
histories, including the ones that MUST read not correct."""

import pytest
import reference as ref
import stats


def test_payload_is_a_function_of_seed_index_version():
    a, b = ref.Payloads(7, 64), ref.Payloads(7, 64)
    assert a.get(3, 2) == b.get(3, 2) and len(a.get(3, 2)) == 64
    assert a.get(3, 2) != a.get(3, 3) != a.get(4, 3)
    assert ref.Payloads(8, 64).get(3, 2) != a.get(3, 2)
    big = 2 ** 31 + 12345           # the driver's seeds are large
    assert ref.Payloads(big, 64).get(0, 0) == ref.Payloads(big, 64).get(0, 0)


def test_tree_model_semantics():
    t = ref.TreeModel()
    t.create('/a', b'x')
    assert t.create('/a/i-', b'1', owner=5, sequential=True) == '/a/i-0000000000'
    assert t.create('/a/i-', b'2', owner=6, sequential=True) == '/a/i-0000000001'
    assert t.children('/a') == (['i-0000000000', 'i-0000000001'], 2)
    t.delete('/a/i-0000000000')
    # the suffix never comes back, cversion counts both kinds of change
    assert t.create('/a/i-', b'3', owner=5, sequential=True) == '/a/i-0000000002'
    assert t.children('/a')[1] == 4
    assert t.set('/a', b'y') == 1 and t.get('/a') == (b'y', 1)
    with pytest.raises(ref.ModelError, match='BAD_VERSION'):
        t.set('/a', b'z', version=0)
    with pytest.raises(ref.ModelError, match='NOT_EMPTY'):
        t.delete('/a')
    with pytest.raises(ref.ModelError, match='NO_CHILDREN_FOR_EPHEMERALS'):
        t.create('/a/i-0000000001/x', b'')
    with pytest.raises(ref.ModelError, match='NODE_EXISTS'):
        t.create('/a', b'')
    with pytest.raises(ref.ModelError, match='NO_NODE'):
        t.get('/nope')


PATHS = ['/kv/p00/c%03d' % i for i in range(8)]


def kv():
    return ref.KvChecker(11, PATHS, 32)


def test_kv_clean_history_whatever_the_interleaving():
    k = kv()
    p = k.payloads
    # session 0 writes znode 3 twice; session 1 reads it between and
    # after, session 2 reads an old version it had never gone past
    w1 = k.next_write(3)
    assert w1 == p.get(3, 1)
    k.write_acked(0, 3, 1, 100)
    k.read(1, 3, p.get(3, 1), 1, 100, sent_writes=1)
    k.read(2, 3, p.get(3, 0), 0, 50, sent_writes=2)   # a lagging member
    k.write_acked(0, 3, 2, 120)
    k.read(1, 3, p.get(3, 2), 2, 120, sent_writes=2)
    k.read(0, 3, p.get(3, 2), 2, 120, sent_writes=2)
    for i in range(8):
        k.final(i, p.get(i, 2 if i == 3 else 0), 2 if i == 3 else 0, 'm1')
    assert k.bad.count == 0 and k.checked == 14


def test_kv_stale_read_below_the_sessions_floor():
    k = kv()
    k.write_acked(0, 3, 1, 100)
    k.read(0, 3, k.payloads.get(3, 0), 0, 50, sent_writes=1)
    assert k.bad.by_kind == {'stale-read': 1}


def test_kv_stale_zxid_within_a_session():
    k = kv()
    k.read(4, 2, k.payloads.get(2, 0), 0, 90)
    k.read(4, 2, k.payloads.get(2, 0), 0, 80)
    assert k.bad.by_kind == {'stale-read': 1}


def test_kv_lost_acknowledged_write():
    k = kv()
    k.write_acked(0, 3, 1, 100)
    k.write_acked(0, 3, 2, 110)
    k.final(3, k.payloads.get(3, 1), 1, 'm2')
    assert k.bad.by_kind == {'lost-write': 1}
    k.final(4, None, 0, 'm2')
    assert k.bad.by_kind == {'lost-write': 1, 'lost-znode': 1}


def test_kv_flipped_payload_byte():
    k = kv()
    good = k.payloads.get(5, 0)
    k.read(1, 5, bytes([good[0] ^ 1]) + good[1:], 0, 10)
    assert k.bad.by_kind == {'payload': 1}
    k.final(5, good[:-1] + bytes([good[-1] ^ 0x80]), 0, 'm0')
    assert k.bad.by_kind == {'payload': 2}


def test_kv_read_from_the_future_and_wrong_ack_version():
    k = kv()
    k.read(1, 5, k.payloads.get(5, 1), 1, 10, sent_writes=0)
    assert k.bad.by_kind == {'future-read': 1}
    k.write_acked(0, 6, 3, 20)
    assert k.bad.by_kind == {'future-read': 1, 'write-version': 1}


def test_kv_unknown_outcome_allows_either_version():
    k = kv()
    k.write_acked(0, 3, 1, 100)
    k.write_unknown(3)
    k.final(3, k.payloads.get(3, 2), 2, 'm1')
    k.final(3, k.payloads.get(3, 1), 1, 'm1')
    assert k.bad.count == 0
    k.final(3, k.payloads.get(3, 0), 0, 'm1')
    assert k.bad.by_kind == {'lost-write': 1}


SVC = ['/svc/s00', '/svc/s01']


def membership():
    m = ref.MembershipChecker(SVC)
    # three sessions race for the suffixes of each service: the acks
    # come back in another order than the server numbered them
    for g in range(2):
        for name, owner in (('i-0000000002', 70), ('i-0000000000', 71),
                            ('i-0000000001', 72)):
            m.register(g, name, owner + 10 * g, b'd')
    m.open_window()
    return m


N0 = ['i-0000000000', 'i-0000000001', 'i-0000000002']


def test_membership_clean_history():
    m = membership()
    assert m.base == [3, 3] and m.bad.count == 0
    assert m.listing(5, 0, N0, 3) == 0
    k1 = m.change(0, 'delete', 'i-0000000001')
    k2 = m.change(0, 'create', 'i-0000000003', owner=72, data=b'e')
    assert (k1, k2) == (1, 2)
    # watcher 5 sees the first change, watcher 6 only the second (a
    # one-shot watch covers both): both are fine
    assert m.listing(5, 0, ['i-0000000000', 'i-0000000002'], 4) == 1
    assert m.listing(5, 0, ['i-0000000000', 'i-0000000002',
                            'i-0000000003'], 5) == 2
    assert m.listing(6, 0, ['i-0000000003', 'i-0000000002',
                            'i-0000000000'], 5) == 2
    assert m.finish([[5, 6], []]) == 0
    m.final(0, ['i-0000000000', 'i-0000000002', 'i-0000000003'],
            {'i-0000000000': 71, 'i-0000000002': 70, 'i-0000000003': 72},
            'm1')
    m.final(1, N0, {}, 'm2')
    assert m.bad.count == 0


def test_membership_list_missing_an_acknowledged_instance():
    m = membership()
    m.change(0, 'delete', 'i-0000000001')
    m.change(0, 'create', 'i-0000000003', owner=72)
    m.listing(5, 0, ['i-0000000000', 'i-0000000002'], 5)
    assert m.bad.by_kind == {'children': 1}


def test_membership_list_with_a_deleted_instance():
    m = membership()
    m.change(0, 'delete', 'i-0000000001')
    m.listing(5, 0, N0, 4)
    assert m.bad.by_kind == {'children': 1}


def test_membership_stale_list_and_future_list():
    m = membership()
    m.change(0, 'delete', 'i-0000000001')
    m.listing(5, 0, ['i-0000000000', 'i-0000000002'], 4)
    m.listing(5, 0, N0, 3)
    assert m.bad.by_kind == {'stale-list': 1}
    m.listing(5, 0, N0, 9)
    assert m.bad.by_kind == {'stale-list': 1, 'future-list': 1}


def test_membership_watcher_never_notified():
    m = membership()
    m.listing(5, 0, N0, 3)
    m.listing(6, 0, N0, 3)
    m.change(0, 'delete', 'i-0000000001')
    m.listing(5, 0, ['i-0000000000', 'i-0000000002'], 4)
    assert m.finish([[5, 6], []]) == 1
    assert m.bad.by_kind == {'not-notified': 1}


def test_membership_sequential_name_and_owner_and_final():
    m = membership()
    m.change(0, 'delete', 'i-0000000001')
    m.change(0, 'create', 'i-0000000007', owner=72)
    assert m.bad.by_kind == {'sequential-name': 1}
    m = membership()
    m.final(0, N0[:2], {'i-0000000000': 99}, 'm1')
    assert m.bad.by_kind == {'final-children': 1, 'ephemeral-owner': 1}


def test_membership_unknown_change_stops_prediction_not_the_run():
    m = membership()
    m.change(0, 'delete', None)
    m.change(0, 'create', 'i-0000000003')
    assert m.listing(5, 0, ['whatever'], 5) == 2
    m.final(0, ['whatever'], {}, 'm1')
    assert m.bad.count == 0


def test_membership_registration_names_must_be_the_models_set():
    m = ref.MembershipChecker(SVC)
    m.register(0, 'i-0000000000', 1, b'')
    m.register(0, 'i-0000000005', 2, b'')
    m.open_window()
    assert m.bad.by_kind == {'sequential-names': 1}


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2], 95) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_held_guarantees_on_hand_written_rows():
    import harness
    cfg = {'voters': 3, 'member_env': {'ZKSTREAM_MEMBER_SYNC': 'tick'},
           'held': {'quorum_degraded_max': 3, 'quorum_hold_min_ms': 200}}

    def rows(sync='tick', members='3', degraded=0, fsyncs=5, index=9,
             errs=0):
        follower = {'zk_wal_sync': sync, 'zk_wal_sync_errors': str(errs),
                    'zk_wal_fsyncs': str(fsyncs),
                    'zk_wal_last_index': str(index)}
        leader = dict(follower, zk_quorum_degraded=str(degraded))
        if members is not None:
            leader['zk_quorum_members'] = members
        return [follower, leader, follower]

    def kinds(start, before, final, hold=251.0):
        return harness.held_guarantees(cfg, 1, start, before, final,
                                       hold)[1]
    sound = rows()
    assert kinds(sound, sound, rows(fsyncs=9, index=20)) == {}
    # one ack in a stalled machine's run is within the limit, four not
    assert kinds(sound, sound, rows(degraded=1)) == {}
    assert kinds(sound, sound, rows(degraded=4)) == {'quorum-degraded': 1}
    assert kinds(rows(members=None), sound, sound) == {'quorum-members': 1}
    assert kinds(rows(members='2'), sound, sound) == {'quorum-members': 1}
    assert kinds(rows(sync='never'), sound, sound) == {'wal-sync': 3}
    assert kinds(sound, sound, rows(index=20)) == {'wal-unsynced': 1}
    assert kinds(sound, sound, rows(errs=2)) == {'wal-sync-errors': 1}
    assert kinds(sound, sound, [{}, {}, {}]) == {'guarantee-unread': 1}
    assert kinds(sound, sound, sound, hold=103.0) == {'quorum-hold': 1}
    assert kinds(sound, sound, sound, hold=None) == {'guarantee-unread': 1}
