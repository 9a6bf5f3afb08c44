"""Share of the fleet's cache-eligible reads in the window that its
cache planes served locally: hits / (hits + misses), the planes' own
counters summed over the fleet before and after the window (the
engine keeps the difference).  A refresh after a change event is a
miss by construction, so the share is what the subscribers' own reads
leave of it."""


def read(run):
    c = run.result.get('counters', {}).get('cache') or {}
    total = c.get('hits', 0) + c.get('misses', 0)
    if not total:
        return None
    return 100.0 * c['hits'] / total
