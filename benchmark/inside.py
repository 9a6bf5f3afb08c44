"""What the program recorded about itself, read over the window: the
host spans it left in its process-wide ring during the profiler
session (``zkstream_tpu.utils.trace.host_ring``), and the members'
duration histograms, which their ``mntr`` exports cumulatively
(``<name>_bucket{..,le=".."}`` / ``_sum`` / ``_count``) and the harness
keeps from before and after the window (``run.mntr_before/after``).

Against a program that has neither (the parent of the PR that brought
them) every function here finds nothing and returns None.

The arithmetic is the benchmark's own: nothing here imports the
program's ``Histogram``.
"""

from __future__ import annotations

import re

import stats

# ---------------------------------------------------------------------
# the host ring: the traced window, from inside the fleet's process
# ---------------------------------------------------------------------


def host_ring(run):
    """The program's host-span ring, or None: no traced window, a
    program without one, or a ring that wrapped (its spans are then
    not the window's)."""
    if not run.trace:
        return None
    try:
        from zkstream_tpu.utils import trace
    except ImportError:
        return None
    ring = getattr(trace, 'host_ring', None)
    if ring is None or ring.dropped:
        return None
    return ring


def span_median_ms(run, name: str) -> float | None:
    """Median duration (ms) of the ring's spans called ``name``."""
    ring = host_ring(run)
    if ring is None:
        return None
    vals = [s.duration_ms for s in ring.spans() if s.op == name]
    return stats.percentile(vals, 50) if vals else None


def span_total_share(run, name: str) -> float | None:
    """Share (%) of the traced window spent inside the per-op boundary
    ``name`` (the ring keeps a count and a total for those, no span
    each)."""
    ring = host_ring(run)
    window_s = (run.trace or {}).get('window_s')
    if ring is None or not window_s or name not in ring.totals:
        return None
    _count, total_ns = ring.totals[name]
    return 100.0 * total_ns / 1e9 / window_s


# ---------------------------------------------------------------------
# windowed histograms from cumulative mntr rows
# ---------------------------------------------------------------------

_BUCKET = re.compile(r'^(.+)_bucket\{(.*)\}$')
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _num(rows: dict, key: str) -> float:
    try:
        return float(rows[key])
    except (KeyError, ValueError):
        return 0.0


def _labels(labels: dict | None) -> str:
    if not labels:
        return ''
    return '{%s}' % ','.join('%s="%s"' % kv for kv in sorted(labels.items()))


def window_hist(before: dict, after: dict, name: str,
                labels: dict | None = None) -> dict | None:
    """The histogram ``name{labels}`` of what was observed between two
    scrapes: ``{'buckets': [(le, count), ...], 'sum', 'count'}`` with
    ``le`` ascending, ``inf`` last, and ``count`` the observations in
    (previous le, le] — the cumulative rows of ``after`` less those of
    ``before`` (a series that did not exist yet counts 0), then
    de-cumulated.  None when ``after`` has no such series."""
    want = dict(labels or {})
    edges = []
    for key in after:
        m = _BUCKET.match(key)
        if not m or m.group(1) != name:
            continue
        got = dict(_LABEL.findall(m.group(2)))
        le = got.pop('le', None)
        if le is None or got != want:
            continue
        edges.append((float('inf') if le == '+Inf' else float(le), key))
    if not edges:
        return None
    edges.sort()
    buckets, prev = [], 0.0
    for le, key in edges:
        cum = _num(after, key) - _num(before, key)
        buckets.append((le, cum - prev))
        prev = cum
    tail = _labels(labels)
    return {'buckets': buckets,
            'sum': (_num(after, name + '_sum' + tail)
                    - _num(before, name + '_sum' + tail)),
            'count': prev}


def percentile(hist: dict | None, q: float) -> float | None:
    """The ``q``-th percentile (0..100) of a ``window_hist``, as
    ``histogram_quantile`` estimates it: the bucket the rank falls in,
    linearly between its edges; a rank in the ``+Inf`` bucket reads the
    largest finite edge.  None for an empty window."""
    if not hist or hist['count'] <= 0:
        return None
    rank = q / 100.0 * hist['count']
    cum, lo = 0.0, 0.0
    finite = [le for le, _n in hist['buckets'] if le != float('inf')]
    for le, n in hist['buckets']:
        if le == float('inf'):
            break
        if cum + n >= rank:
            return lo + (le - lo) * ((rank - cum) / n if n else 0.0)
        cum += n
        lo = le
    return finite[-1] if finite else None


def member_hist(run, member: int, name: str,
                labels: dict | None = None) -> dict | None:
    try:
        return window_hist(run.mntr_before[member], run.mntr_after[member],
                           name, labels)
    except IndexError:
        return None


def members(run) -> range:
    return range(min(len(run.mntr_before), len(run.mntr_after)))


def member_window_ms(run, member: int) -> float | None:
    """The time between the member's two scrapes on the member's own
    clock (``zk_uptime_ms``), else the run's window."""
    d = run.mntr_delta(member, 'zk_uptime_ms')
    if d and d > 0:
        return d
    return run.window_s * 1e3 if run.window_s else None


def phase_share(run, member: int, phases=None) -> float | None:
    """Share (%) of the window the member's loop spent in the tick
    ledger's ``phases`` (every phase when None): the phases'
    ``zk_tick_phase_ms_sum`` deltas over the window.  Nested phases are
    subtracted from their parents by the ledger, so the shares add."""
    window_ms = member_window_ms(run, member)
    if not window_ms:
        return None
    total, found = 0.0, False
    for key in run.mntr_after[member]:
        if not key.startswith('zk_tick_phase_ms_sum{'):
            continue
        phase = dict(_LABEL.findall(key)).get('phase')
        if phases is not None and phase not in phases:
            continue
        d = run.mntr_delta(member, key)
        if d is None:       # the series opened inside the window
            d = _num(run.mntr_after[member], key)
        total += d
        found = True
    return 100.0 * total / window_ms if found else None


def largest(values) -> float | None:
    vals = [v for v in values if v is not None]
    return max(vals) if vals else None
