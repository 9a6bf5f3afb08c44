"""Notification frames the members' persistent fan-out handed to the
send plane in the window (``zk_persistent_notifications``, after less
before, summed over the members) per change acknowledged in it: the
number of subscribers when no one was closed or evicted.  None against
a program without the row."""


def read(run):
    changes = run.result.get('counters', {}).get('writes_acked')
    deltas = [run.mntr_delta(m, 'zk_persistent_notifications')
              for m in range(len(run.mntr_after))]
    if not changes or not deltas or None in deltas:
        return None
    return sum(deltas) / changes
