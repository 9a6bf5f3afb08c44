"""Cache entries the fleet's planes dropped on change events in the
window (``CachePlane.invalidations``, summed over the fleet, after
less before) per change acknowledged in it: one entry a subscriber a
change — the fleet's size — when every notification arrived and every
refresh had refilled the entry before the key changed again."""


def read(run):
    c = run.result.get('counters', {}).get('cache') or {}
    changes = run.result.get('counters', {}).get('writes_acked')
    if 'invalidations' not in c or not changes:
        return None
    return c['invalidations'] / changes
