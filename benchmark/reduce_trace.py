"""From a profiler trace to numbers: device busy and idle time, the
time of each device program, what the chip could have done in that
time, and what the host was doing in the device's idle gaps.

The reduction works on a neutral form of the trace — ``{'planes':
[{'name', 'lines': [{'name', 'events': [[name, start_ns, dur_ns],
...]}]}]}`` — which ``load_xplane`` makes from the ``.xplane.pb`` the
JAX profiler writes and which a test can keep as JSON.

What a v5e trace looks like (looked at by hand, PR 23): one plane per
chip named ``/device:TPU:<n>``; on it the line ``XLA Modules`` carries
one event per executed program, named ``<module>(<fingerprint>)`` —
the ingest's tick program is ``jit_step`` — and the line ``XLA Ops``
one event per HLO op inside it.  The host's threads are lines of the
plane ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` is an event on
the line of the thread that opened it.  All planes share one clock.
"""

from __future__ import annotations

import bisect
import json
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
HOST_PLANE = '/host:CPU'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = []
    for base, _dirs, files in os.walk(trace_dir):
        found += [os.path.join(base, f) for f in files
                  if f.endswith('.xplane.pb')]
    return max(found, key=os.path.getmtime) if found else None


def op_name(event_name: str) -> str:
    """The trace names a device op by its whole HLO line (``%while.1 =
    (s32[], ...) while(...)``): keep the result's name, ``while.1``."""
    return event_name.split(' = ', 1)[0].lstrip('%')


def load_xplane(path: str, keep_host=None) -> dict:
    """The neutral form of an ``.xplane.pb``.  Of the host plane only
    the events named in ``keep_host`` are kept (it is large); device
    planes are kept whole, their ops under their short names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    if keep_host is not None:
        keep_host = frozenset(keep_host)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[op_name(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or keep_host is None
                      or e.name in keep_host]
            if events:
                lines.append({'name': line.name, 'events': events})
        planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals; sorted, disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def module_name(event_name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return event_name.split('(', 1)[0]


def _device_planes(trace: dict) -> list[dict]:
    return [p for p in trace['planes'] if DEVICE_PLANE.match(p['name'])]


def _line(plane: dict, name: str) -> list:
    for line in plane['lines']:
        if line['name'] == name:
            return line['events']
    return []


def reduce(trace: dict, window_ns: float | None = None,
           host_spans=(), rest: str = 'unattributed') -> dict:
    """The numbers the per-layer metrics and the result line read.

    ``window_ns``: the traced window's length on the host's clock; when
    None the span from the first to the last event of the trace.
    ``host_spans``: the annotation names to attribute idle gaps to, in
    order of precedence (an inner span listed first wins the overlap);
    idle time under none of them goes to ``rest``.

    Returns ``{'chips', 'window_s', 'busy_s' (mean over chips), 'ops':
    [[name, seconds], ...] most time first, 'programs': {module:
    {'seconds', 'count'}}, 'idle_gaps': [[span or 'unattributed',
    seconds], ...]}``; ``busy_s`` is 0.0 when no device plane has an
    event (the caller refuses such a run)."""
    planes = _device_planes(trace)
    lo = hi = None
    busy_per_chip = []
    ops: dict[str, float] = {}
    programs: dict[str, dict] = {}
    busy_first: list[tuple[float, float]] = []
    for i, plane in enumerate(planes):
        events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        merged = union((s, s + d) for _n, s, d in events)
        if i == 0:
            busy_first = merged
        busy_per_chip.append(sum(e - s for s, e in merged))
        for name, s, d in events:
            ops[name] = ops.get(name, 0.0) + d
            lo = s if lo is None else min(lo, s)
            hi = s + d if hi is None else max(hi, s + d)
        for name, _s, d in _line(plane, MODULES_LINE):
            prog = programs.setdefault(module_name(name),
                                       {'seconds': 0.0, 'count': 0})
            prog['seconds'] += d / 1e9 / len(planes)
            prog['count'] += 1
    if window_ns is None:
        window_ns = (hi - lo) if lo is not None else 0.0
    host = [p for p in trace['planes'] if p['name'] == HOST_PLANE]
    spans = {name: [] for name in host_spans}
    for plane in host:
        for line in plane['lines']:
            for name, s, d in line['events']:
                if name in spans:
                    spans[name].append((s, s + d))
    gaps = [(a[1], b[0]) for a, b in zip(busy_first, busy_first[1:])]
    return {
        'chips': len(planes),
        'window_s': window_ns / 1e9,
        'busy_s': (sum(busy_per_chip) / len(busy_per_chip) / 1e9
                   if busy_per_chip else 0.0),
        'ops': [[n, t / 1e9 / max(1, len(planes))] for n, t in
                sorted(ops.items(), key=lambda kv: -kv[1])],
        'programs': programs,
        'idle_gaps': attribute_gaps(gaps, spans, host_spans, rest),
    }


def attribute_gaps(gaps, spans: dict, order,
                   rest_name: str = 'unattributed') -> list:
    """Seconds of device idle time by what the host was doing: each
    gap's overlap with the named host spans, an earlier name in
    ``order`` taking what it covers first; the rest is
    ``rest_name``.  Most time first.

    One name at a time over what the earlier names left of the gaps:
    each piece finds the first span that ends after its start by
    bisection and walks on from there, so a name with tens of
    thousands of spans (the program annotates every request) costs its
    spans once, not once a gap."""
    total = {}
    left = list(gaps)
    for name in order:
        merged = union(spans[name])
        ends = [b for _a, b in merged]
        covered = 0.0
        nxt = []
        for s, e in left:
            cur = s
            for i in range(bisect.bisect_right(ends, s), len(merged)):
                a, b = merged[i]
                if a >= e:
                    break
                if a > cur:
                    nxt.append((cur, a))
                    cur = a
                b = min(b, e)
                covered += b - cur
                cur = b
            if cur < e:
                nxt.append((cur, e))
        total[name] = covered
        left = nxt
    out = [[name, t / 1e9] for name, t in total.items()]
    out.append([rest_name, sum(e - s for s, e in left) / 1e9])
    return sorted(out, key=lambda kv: -kv[1])


# ---------------------------------------------------------------------
# what the tick program has to move
# ---------------------------------------------------------------------

#: int32 planes of [Bp, max_frames] in the packed tick output
#: (starts, sizes, xids, errs, zxid_hi, zxid_lo) after 3 head columns
#: (n_frames, resid, bad) — the layout of ``FleetIngest._trace_step``
#: (headers only: reply bodies are parsed on the host)
HEADER_PLANES = 6
HEAD_COLUMNS = 3


def tick_bytes(bp: int, length: int, max_frames: int) -> int:
    """Bytes one tick program must move through HBM for a
    ``[bp, length]`` bucket: read the u8 batch and the int32 lengths
    once, write the packed int32 result once.  A lower bound (no
    intermediate is counted), so the share it gives is an upper bound
    on how close to the bytes roofline the program runs."""
    read = bp * length + 4 * bp
    write = 4 * bp * (HEAD_COLUMNS + HEADER_PLANES * max_frames)
    return read + write


def tick_roofline_share(run, program: str) -> float | None:
    """The tick program's share (%) of its bytes roofline: the bytes
    the traced window's ticks had to move (``tick_bytes`` of each
    tick's bucket) over the chip's HBM bandwidth, over the device time
    the program took.  Bytes-bound: the scan does a handful of integer
    ops per byte.  ``run`` is the harness's ``Run``."""
    prog = (run.trace or {}).get('programs', {}).get(program)
    peak = run.peaks.get('hbm_bytes_per_s')
    if not prog or not prog['seconds'] or not run.tick_buckets or not peak:
        return None
    frames = int(run.ingest_params['max_frames'])
    moved = sum(tick_bytes(bp, length, frames)
                for *_, bp, length in run.tick_buckets)
    # the host saw len(tick_buckets) ticks start in the traced window;
    # the trace holds prog['count'] executions: scale to what was timed
    moved *= prog['count'] / len(run.tick_buckets)
    return 100.0 * (moved / peak) / prog['seconds']


def summarize(trace: dict, top: int = 12) -> str:
    """Planes, lines and the first events of each, for reading a trace
    by hand."""
    rows = []
    for plane in trace['planes']:
        rows.append('PLANE %s' % (plane['name'],))
        for line in plane['lines']:
            ev = line['events']
            rows.append('  LINE %-40s events=%d' % (line['name'], len(ev)))
            for name, s, d in ev[:top]:
                rows.append('      %-60s start=%.0f dur=%.0f'
                            % (name[:60], s, d))
    return '\n'.join(rows)
