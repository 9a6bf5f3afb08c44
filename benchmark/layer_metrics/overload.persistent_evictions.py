"""Persistent-watch subscribers the members' overload plane evicted in
the window instead of dropping their notification
(``zk_overload_persistent_evictions``, summed over the members).  Must
read 0: an eviction is a resync at the subscriber, and ``correct``
counts it."""


def read(run):
    deltas = [run.mntr_delta(m, 'zk_overload_persistent_evictions')
              for m in range(len(run.mntr_after))]
    if not deltas or None in deltas:
        return None
    return sum(deltas)
