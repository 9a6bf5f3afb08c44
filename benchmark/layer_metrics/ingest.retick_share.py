"""Share (%) of the traced window's device ticks that scheduled a
follow-up tick because a stream hit the per-tick frame bound with more
buffered or a slot held more than it gave: the ``ingest.tick`` host
spans whose ``retick`` field is set over those that carry the field
(the spans' share of the ingest's always-on ``reticks`` / ``ticks``;
``bound`` and ``cut`` beside it say for which of the two).  0 where a
tick drains every session's whole window — ``max_frames`` requests
outstanding or fewer; above it where a slot is made to wait for the
tick after.  None against a program whose tick spans carry no such
field (the parent of the PR that brought it), in an untraced run, or
in a window without a device tick."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    ticks = reticks = 0
    for s in ring.spans():
        if s.op != 'ingest.tick' or s.tick is None:
            continue
        flag = getattr(s, 'retick', None)
        if flag is None:
            continue
        ticks += 1
        reticks += bool(flag)
    if not ticks:
        return None
    return 100.0 * reticks / ticks
