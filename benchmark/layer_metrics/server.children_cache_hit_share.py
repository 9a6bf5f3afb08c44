"""Share (%) of the children replies the members sent in the window
whose serialized body they already held: hits / (hits + misses) of the
members' children-reply cache, the cumulative ``mntr`` rows
``zk_children_cache_hits`` / ``zk_children_cache_misses`` after less
before, summed over the members.  A change of a directory costs every
member ONE miss, whoever asks first; a herd of N re-lists reads
(N - members) / N.  None against a program without the rows (it sorts
and encodes every reply)."""

import inside


def read(run):
    hits = misses = 0.0
    for m in inside.members(run):
        h = run.mntr_delta(m, 'zk_children_cache_hits')
        x = run.mntr_delta(m, 'zk_children_cache_misses')
        if h is None or x is None:
            return None
        hits += h
        misses += x
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
