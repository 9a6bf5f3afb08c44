"""``reference_live.LiveChecker`` on small hand-written histories,
including the ones that MUST read not correct: each rule tripped
once — a ghost, a missing name, a duplicate, a list handed out twice, a
list from the future, a node that was told and handed nothing, a pair
never shown, a wrong create path, a gap, a final tree that kept a
closed node's ephemeral."""

import reference_live as rl

N = 6


def live(seed=11):
    return rl.LiveChecker(seed, N, '/live_nodes')


def register(c):
    for n in range(N):
        c.registered(n, c.path(n), 0x1000 + n)
    for n in range(N):
        c.armed(n)
        c.emitted(n, 0.5, sorted(c.states[0]), c.base, 0)


def show(c, k, t, but=None, nodes=None):
    """Every node but ``but`` is told and shown change ``k``."""
    for n in (range(N) if nodes is None else nodes):
        if n != but:
            c.notified(n)
            c.emitted(n, t, sorted(c.states[k]), c.base + k, k)


def restart(c, node, t=1.0, sid=0x2000):
    """One sound leave and return of ``node``."""
    k = c.left(node)
    show(c, k, t, but=node)
    k = c.returned(node, c.path(node), sid)
    show(c, k, t + 0.5, but=node)
    c.armed(node)
    c.emitted(node, t + 0.6, sorted(c.states[k]), c.base + k, k)
    return k


def final(c):
    c.final(sorted(c.states[-1]), 'member 1')
    for n in range(N):
        c.final_owner(n, c.owner.get(c.names[n]), 'member 2')


def test_names_are_the_seeds_distinct_and_solr_shaped():
    a, b = rl.node_names(7, 1024), rl.node_names(7, 1024)
    assert a == b and len(set(a)) == 1024
    assert rl.node_names(8, 1024) != a
    assert all(n.startswith('10.') and n.endswith(':8983_solr')
               and 18 <= len(n) <= 24 for n in a)
    # a large seed (the driver's are) works
    assert len(rl.node_names(2 ** 31 + 12345, 24)) == 24


def test_clean_history():
    c = live()
    register(c)
    assert restart(c, 2) == 2
    assert restart(c, 5, t=3.0, sid=0x2001) == 4
    assert c.finish() == 0
    final(c)
    assert c.bad.count == 0 and c.checked > 40
    assert c.changes == 4 and c.by == [None, 2, 2, 5, 5]
    assert c.names[2] not in c.states[1] and c.names[2] in c.states[2]
    assert c.owner[c.names[2]] == 0x2000
    assert c.seen_at(0, 3) == 3.0 and c.seen_at(0, 5) is None
    # the restarted node's new session begins its own floor
    assert c.floor[5] == 4 and c.newest[2] == 4


def test_a_view_that_arrives_before_its_changes_ack_is_judged_later():
    c = live()
    register(c)
    gone = sorted(c.states[0] - {c.names[1]})
    c.notified(0)
    assert c.emitted(0, 1.0, gone, c.base + 1, sent=1) == 1
    c.notified(3)
    c.emitted(3, 1.0, gone + [c.names[1]], c.base + 1, sent=1)
    assert c.bad.count == 0          # not acknowledged yet: pending
    c.left(1)
    c.settle()
    assert c.bad.by_kind == {'children': 1}
    assert 'node 3 after 1 changes' in c.bad.first[0]


def trip(kind, breaker, count=1):
    c = live()
    register(c)
    breaker(c)
    assert c.bad.by_kind == {kind: count}, c.bad.first
    return c


def test_a_ghost_a_missing_name_and_a_duplicate_are_children():
    def ghost(c):
        k = c.left(4)
        c.notified(0)
        c.emitted(0, 1.0, sorted(c.states[k]) + [c.names[4]],
                  c.base + k, k)
    assert 'unexpected' in trip('children', ghost).bad.first[0]

    def short(c):
        k = c.left(4)
        c.notified(0)
        c.emitted(0, 1.0, sorted(c.states[k])[1:], c.base + k, k)
    trip('children', short)

    def twice(c):
        k = c.left(4)
        names = sorted(c.states[k])
        c.notified(0)
        c.emitted(0, 1.0, names + names[:1], c.base + k, k)
    assert 'distinct' in trip('children', twice).bad.first[0]


def test_a_list_handed_out_again_is_stale():
    def again(c):
        k = c.left(4)
        show(c, k, 1.0, but=4)
        c.notified(0)
        c.emitted(0, 2.0, sorted(c.states[k]), c.base + k, k + 1)
    trip('stale-view', again)

    def back(c):
        c.left(4)
        k = c.returned(4, c.path(4), 0x2000)
        show(c, k, 1.0, but=4)
        c.notified(1)
        c.emitted(1, 2.0, sorted(c.states[1]), c.base + 1, k)
    trip('stale-view', back)


def test_a_list_above_the_changes_sent_is_a_future_read():
    def future(c):
        c.notified(0)
        assert c.emitted(0, 1.0, sorted(c.states[0]), c.base + 1,
                         sent=0) == -1
    trip('future-read', future)

    def never_acked(c):
        c.notified(0)
        c.emitted(0, 1.0, sorted(c.states[0]), c.base + 1, sent=1)
        c.finish()
    trip('future-read', never_acked)


def test_told_and_handed_nothing_is_a_missed_change():
    def swallowed(c):
        k = c.left(4)
        show(c, k, 1.0, but=4, nodes=[0, 1, 2, 5])
        c.notified(3)               # told, and no view follows
        k = c.returned(4, c.path(4), 0x2000)
        show(c, k, 2.0, but=4)      # the later change shows it more
        c.armed(4)
        c.emitted(4, 2.1, sorted(c.states[k]), c.base + k, k)
        assert c.finish() == 1
    assert 'handed no view' in trip('missed-change', swallowed).bad.first[0]

    def never(c):
        k = c.left(4)
        show(c, k, 1.0, but=4, nodes=[0, 1, 2, 5])
        assert c.finish() == 1      # node 3: not told, not shown
    assert 'never shown change 1' in trip('missed-change',
                                          never).bad.first[0]


def test_a_leaving_nodes_owed_view_goes_with_its_session():
    c = live()
    register(c)
    c.notified(4)                   # its own delete reaches it first
    k = c.left(4)
    show(c, k, 1.0, but=4)
    assert c.finish() == 0 and c.bad.count == 0


def test_a_wrong_create_path_a_gap_and_the_final_tree():
    trip('create-path', lambda c: c.returned(
        2, '/live_nodes/other:8983_solr', 0x2000))
    trip('evicted', lambda c: c.gap(3, 'a disconnect'))

    def kept(c):
        k = c.left(4)
        show(c, k, 1.0, but=4)
        c.final(sorted(c.states[0]), 'member 0')     # still lists it
    trip('final-children', kept)

    def owner(c):
        k = c.left(4)
        show(c, k, 1.0, but=4)
        c.final_owner(4, 0x1004, 'member 2')         # there, node down
        c.final_owner(1, 0x9999, 'member 2')         # another's
        c.final_owner(2, None, 'member 2')           # missing
    trip('ephemeral-owner', owner, 3)


def test_after_an_unknown_outcome_nothing_is_predicted():
    c = live()
    register(c)
    k = c.unknown(3)
    c.notified(0)
    c.emitted(0, 1.0, ['whatever'], c.base + k, k)
    k2 = c.left(4)
    assert c.states[k2] is None
    c.finish()
    c.final(['anything'], 'member 1')
    c.final_owner(2, None, 'member 2')
    assert c.bad.count == 0
