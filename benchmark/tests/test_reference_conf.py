"""``reference_conf.ConfChecker`` on small hand-written histories,
including the ones that MUST read not correct."""

import reference_conf as rc

KEYS, SUBS, SIZE = 4, 3, 32


def conf(seed=11):
    return rc.ConfChecker(seed, KEYS, SIZE, SUBS)


def publish(c, key, zxid, t=0.0, subs=range(SUBS), version=None):
    """One acknowledged change that every subscriber in ``subs`` is
    told of and refreshes to."""
    want = c.write_acked(key, version or c.version[key] + 1, zxid)
    for s in subs:
        c.notified(s, key, zxid)
        c.refreshed(s, key, zxid, t + 0.01 * (s + 1),
                    c.payloads.get(key, want), want, sent=want)
    return want


def test_clean_history_whatever_the_interleaving():
    c = conf()
    p = c.payloads
    assert c.next_write(2) == p.get(2, 1) and c.initial(2) == p.get(2, 0)
    c.read(0, 2, p.get(2, 0), 0, sent=0)            # before any change
    c.read(1, 2, p.get(2, 0), 0, sent=1)            # a lagging hit: fine,
    assert publish(c, 2, 100, t=1.0) == 1           # it saw nothing newer
    c.read(0, 2, p.get(2, 1), 1, sent=1)
    # the second change's notification overtakes nothing: subscriber 2
    # refreshes to version 2 on the FIRST event (the later change was
    # already applied), then again on the second
    c.write_acked(2, 2, 120)
    c.notified(2, 2, 120)
    c.refreshed(2, 2, 120, 2.5, p.get(2, 2), 2, sent=2)
    for s in (0, 1):
        c.notified(s, 2, 120)
        c.refreshed(s, 2, 120, 2.0 + s, p.get(2, 2), 2, sent=2)
    assert c.finish() == 0
    for k in range(KEYS):
        v = 2 if k == 2 else 0
        c.final(k, p.get(k, v), v, 'member 1')
    assert c.bad.count == 0
    assert c.seen_at(0, 2, 1) == 1.01 and c.seen_at(0, 2, 2) == 2.0
    assert c.seen_at(2, 2, 2) == 2.5 and c.seen_at(0, 2, 3) is None
    # a large seed, as the driver's are
    big = rc.ConfChecker(2 ** 31 + 12345, KEYS, SIZE, SUBS)
    assert big.initial(1) == rc.Payloads(2 ** 31 + 12345, SIZE).get(1, 0)


def test_payload_of_a_refresh_and_of_a_cached_read():
    c = conf()
    publish(c, 1, 50)
    c.read(0, 1, b'x' * SIZE, 1, sent=1)
    c.write_acked(1, 2, 60)
    c.notified(0, 1, 60)
    c.refreshed(0, 1, 60, 1.0, c.payloads.get(1, 1), 2, sent=2)
    assert c.bad.by_kind == {'payload': 2}


def test_acked_write_returns_the_models_next_version():
    c = conf()
    c.write_acked(0, 1, 10)
    c.write_acked(0, 3, 11)         # the model says 2
    assert c.bad.by_kind == {'write-version': 1}
    # after a write of unknown outcome either is allowed
    d = conf()
    d.write_unknown(0)
    d.write_acked(0, 2, 12)
    assert d.bad.count == 0


def test_refresh_older_than_the_change_that_caused_it():
    c = conf()
    c.write_acked(3, 1, 40)
    c.notified(0, 3, 40)
    c.refreshed(0, 3, 40, 1.0, c.payloads.get(3, 0), 0, sent=1)
    c.finish()
    assert c.bad.by_kind['refresh-version'] == 1
    # ... and it does not count as having seen the change
    assert c.bad.by_kind['missed-change'] == SUBS


def test_a_subscriber_that_is_never_told_is_a_missed_change():
    c = conf()
    publish(c, 0, 30, subs=(0, 2))
    assert c.finish() == 1
    assert c.bad.by_kind == {'missed-change': 1}
    assert 'subscriber 1 was never told' in c.bad.first[0]


def test_told_but_never_refreshed_is_a_missed_change_too():
    c = conf()
    publish(c, 0, 30, subs=(0, 1))
    c.notified(2, 0, 30)
    assert c.finish() == 1
    assert 'subscriber 2 never refreshed' in c.bad.first[0]


def test_a_later_change_stands_for_the_refresh_but_not_the_telling():
    """The second change's refresh shows the first too; but the first
    event itself never arrived: the invalidation stream had a gap."""
    c = conf()
    publish(c, 1, 30, subs=(0, 1))
    publish(c, 1, 31)
    assert c.finish() == 1
    assert c.bad.by_kind == {'missed-change': 1}


def test_versions_never_go_back_within_a_session_and_key():
    c = conf()
    p = c.payloads
    publish(c, 2, 70)
    c.read(1, 2, p.get(2, 0), 0, sent=1)    # below its own refresh
    c.read(0, 3, p.get(3, 0), 0, sent=0)    # another key: fine
    assert c.bad.by_kind == {'stale-hit': 1}
    # a read that showed version 1, then a hit on a dropped entry
    d = conf()
    d.write_acked(2, 1, 70)
    d.read(0, 2, p.get(2, 1), 1, sent=1)
    d.read(0, 2, p.get(2, 0), 0, sent=1)
    d.read(1, 2, p.get(2, 0), 0, sent=1)    # subscriber 1 saw nothing newer
    assert d.bad.by_kind == {'stale-hit': 1}


def test_a_read_above_the_writes_sent():
    c = conf()
    c.read(0, 1, c.payloads.get(1, 1), 1, sent=0)
    assert c.bad.by_kind == {'future-read': 1}


def test_a_notification_no_publisher_acknowledged():
    c = conf()
    c.notified(0, 1, 99)
    c.refreshed(0, 1, 99, 1.0, c.payloads.get(1, 0), 0, sent=0)
    c.finish()
    assert c.bad.by_kind == {'unknown-notification': 1}
    d = conf()                      # ... unless a write's outcome is unknown
    d.write_unknown(1)
    d.notified(0, 1, 99)
    d.refreshed(0, 1, 99, 1.0, d.payloads.get(1, 1), 1, sent=1)
    d.finish()
    assert d.bad.count == 0


def test_a_gap_in_the_stream_is_a_violation():
    c = conf()
    c.gap(1, "'resumed'")
    c.gap(2, 'a disconnect')
    assert c.bad.by_kind == {'evicted': 2}


def test_final_tree_equals_the_model():
    c = conf()
    p = c.payloads
    publish(c, 0, 10)
    publish(c, 0, 11)
    c.final(0, p.get(0, 1), 1, 'member 2')          # an acked write lost
    c.final(1, None, 0, 'member 2')
    c.final(2, p.get(2, 1), 0, 'member 2')          # right version, wrong bytes
    c.final(3, p.get(3, 0), 0, 'member 2')
    assert c.bad.by_kind == {'lost-write': 1, 'lost-key': 1, 'payload': 1}
    d = conf()
    d.write_unknown(0)
    d.final(0, d.payloads.get(0, 1), 1, 'member 1')  # allowed: unknown
    assert d.bad.count == 0


def test_every_kind_is_printed_by_the_engine():
    """``engines/config_push.py`` prints one ``# compared`` line a kind
    from ``KINDS``: a kind the checker can raise must be in it."""
    import inspect
    import re
    raised = set(re.findall(r"bad\.add\(\s*'([a-z\-]+)'",
                            inspect.getsource(rc)))
    assert raised == set(rc.KINDS)
