"""The trace reduction: interval arithmetic on a synthetic trace, the
bytes function against a hand count, the loader on a trace the CPU
backend writes, and the whole reduction on a small trace recorded on
the chip with the numbers it must give."""

import json
import os

import pytest
import reduce_trace as rt
from conftest import BENCH

DATA = os.path.join(BENCH, 'tests', 'data')


def test_union_and_gap_attribution():
    assert rt.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    trace = {'planes': [
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Modules', 'events': [
                ['jit_step(1)', 100.0, 50.0], ['jit_step(2)', 400.0, 100.0],
                ['jit_other(3)', 900.0, 100.0]]},
            {'name': 'XLA Ops', 'events': [
                ['fusion.1', 100.0, 20.0], ['copy.2', 110.0, 40.0],
                ['fusion.1', 400.0, 100.0], ['fusion.9', 900.0, 100.0]]}]},
        {'name': '/host:CPU', 'lines': [{'name': 'main', 'events': [
            ['ingest_tick', 90.0, 100.0],        # covers 150..190 of gap 1
            ['await_replies', 150.0, 200.0],     # 150..350, tick wins 150..190
            ['await_replies', 600.0, 250.0]]}]},  # 600..850 of gap 2
    ]}
    red = rt.reduce(trace, window_ns=2000.0,
                    host_spans=('ingest_tick', 'await_replies'))
    assert red['chips'] == 1
    assert red['busy_s'] == pytest.approx((50 + 100 + 100) / 1e9)
    assert red['window_s'] == pytest.approx(2000 / 1e9)
    assert red['programs']['jit_step'] == {
        'seconds': pytest.approx(150 / 1e9), 'count': 2}
    assert red['ops'][0] == ['fusion.1', pytest.approx(120 / 1e9)]
    gaps = dict(red['idle_gaps'])
    # gap 1 = 150..400, gap 2 = 500..900
    assert gaps['ingest_tick'] == pytest.approx(40 / 1e9)
    assert gaps['await_replies'] == pytest.approx((160 + 250) / 1e9)
    assert gaps['unattributed'] == pytest.approx((50 + 150) / 1e9)


def test_the_harness_lists_an_inner_span_before_the_one_it_lies_in():
    """``harness.HOST_SPANS`` as the program nests its spans (PRs 35,
    36): a collection lies inside whatever was open, the tick's phases
    inside the tick, the send tier's hand-over and reaping inside its
    flush, a connection's receive inside the receive reap — an inner
    name stands first, so each keeps its own time and the outer one
    what is left; a span no name covers is the loop's rest."""
    import harness

    order = harness.HOST_SPANS
    at = order.index
    assert at('gc.pause') == 0
    for inner, outer in (('ingest.batch', 'ingest.tick'),
                         ('ingest.dispatch', 'ingest.tick'),
                         ('ingest.readback', 'ingest.tick'),
                         ('ingest.route', 'ingest.tick'),
                         ('client.notify', 'ingest.route'),
                         ('client.handoff', 'client.flush'),
                         ('client.reap', 'client.flush'),
                         ('client.rx', 'client.rx_reap')):
        assert at(inner) < at(outer), (inner, outer)
    assert {'client.prepare', 'client.submit', 'client.resume',
            'client.deadline'} < set(order)
    assert order[-2:] == ('await_replies', 'validate')
    # one idle gap, 0..1000: a reap 100..400 that delivers twice
    # (150..200, 250..300), a tick 500..800 whose route 600..760 holds
    # a collection 650..700, and an API call's three stretches
    host = [['client.rx_reap', 100.0, 300.0], ['client.rx', 150.0, 50.0],
            ['client.rx', 250.0, 50.0], ['ingest.tick', 500.0, 300.0],
            ['ingest.route', 600.0, 160.0], ['gc.pause', 650.0, 50.0],
            ['client.prepare', 820.0, 10.0], ['client.submit', 830.0, 30.0],
            ['client.resume', 900.0, 20.0], ['select', 0.0, 90.0]]
    trace = {'planes': [
        {'name': '/device:TPU:0', 'lines': [{'name': 'XLA Ops', 'events': [
            ['fusion.1', -10.0, 10.0], ['fusion.1', 1000.0, 10.0]]}]},
        {'name': '/host:CPU', 'lines': [{'name': 'main', 'events': host}]}]}
    gaps = dict(rt.reduce(trace, window_ns=1020.0, host_spans=order,
                          rest=harness.LOOP_REST)['idle_gaps'])
    want = {'gc.pause': 50, 'ingest.route': 110, 'ingest.tick': 140,
            'client.rx': 100, 'client.rx_reap': 200, 'client.prepare': 10,
            'client.submit': 30, 'client.resume': 20}
    for name in order:
        assert gaps[name] == pytest.approx(want.get(name, 0) / 1e9), name
    assert gaps[harness.LOOP_REST] == pytest.approx(
        (1000 - sum(want.values())) / 1e9)


def plain_attribution(gaps, spans, order):
    """``attribute_gaps`` as it stood before the sweep: every gap walks
    every name's merged list from its start.  The answers the sweep is
    held to."""
    total = {name: 0.0 for name in order}
    rest = 0.0
    merged = {name: rt.union(spans[name]) for name in order}
    for gs, ge in gaps:
        left = [(gs, ge)]
        for name in order:
            nxt = []
            for s, e in left:
                cur = s
                for a, b in merged[name]:
                    if b <= cur:
                        continue
                    if a >= e:
                        break
                    a2, b2 = max(a, cur), min(b, e)
                    if a2 > cur:
                        nxt.append((cur, a2))
                    total[name] += b2 - a2
                    cur = b2
                if cur < e:
                    nxt.append((cur, e))
            left = nxt
        rest += sum(e - s for s, e in left)
    return dict({n: t / 1e9 for n, t in total.items()},
                unattributed=rest / 1e9)


@pytest.mark.parametrize('seed', range(8))
def test_the_sweep_gives_the_plain_walks_answers(seed):
    """Nested, abutting, overlapping and empty spans, spans that
    straddle a gap's edges, names with nothing, gaps given out of
    order: the same seconds under every name, and every gap's length
    accounted for."""
    import random

    rng = random.Random(seed)
    order = ('inner', 'mid', 'outer', 'other', 'none')
    spans = {n: [] for n in order}
    for _ in range(rng.randrange(1, 60)):
        s = rng.randrange(0, 10_000)
        d = rng.randrange(40, 900)
        spans['outer'].append((s, s + d))
        if rng.random() < 0.7:       # a child inside it, and a grandchild
            a = s + rng.randrange(0, d // 2)
            b = a + rng.randrange(1, d // 2)
            spans['mid'].append((a, b))
            if rng.random() < 0.5:
                spans['inner'].append((a, a + (b - a) // 2))
    spans['other'] = [(s, s + rng.choice((0, 1, 50, 300))) for s in
                      (rng.randrange(0, 10_000) for _ in range(40))]
    edges = sorted(rng.sample(range(0, 10_000), 2 * rng.randrange(1, 30)))
    gaps = list(zip(edges[::2], edges[1::2]))
    rng.shuffle(gaps)
    got = dict(rt.attribute_gaps(gaps, spans, order))
    assert got == pytest.approx(plain_attribution(gaps, spans, order),
                                abs=1e-18)
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in gaps) / 1e9)
    assert got['none'] == 0.0


def test_the_sweep_takes_50_000_spans_a_name_in_its_stride():
    """The program annotates every request: ~40,000 spans a name in
    the read cell's 4 s against a few thousand gaps.  Held to 2 s (the
    walk it replaced took minutes), and to a hand count."""
    import time

    names = ('a', 'b', 'c')
    # name k's n-th span covers [100 n + 10 k, 100 n + 10 k + 10)
    spans = {name: [(100.0 * n + 10 * k, 100.0 * n + 10 * k + 10)
                    for n in range(50_000)]
             for k, name in enumerate(names)}
    # 2,500 gaps of 1,000 ns, one every 2,000 ns: 10 spans a name each
    gaps = [(2000.0 * g, 2000.0 * g + 1000) for g in range(2500)]
    t = time.perf_counter()
    got = dict(rt.attribute_gaps(gaps, spans, names, 'rest'))
    assert time.perf_counter() - t < 2.0
    for name in names:
        assert got[name] == pytest.approx(2500 * 10 * 10 / 1e9)
    assert got['rest'] == pytest.approx(2500 * (1000 - 300) / 1e9)


def test_no_device_event_is_zero_busy():
    red = rt.reduce({'planes': [{'name': '/host:CPU', 'lines': []}]},
                    window_ns=1e9)
    assert red['busy_s'] == 0.0 and red['chips'] == 0


def test_tick_bytes_against_a_hand_count():
    # bucket [1024, 4096], 8 frames a stream: the u8 batch 4,194,304 B
    # and 1,024 int32 lengths 4,096 B are read; 1,024 rows of
    # 3 + 6*8 = 51 int32 = 208,896 B are written
    assert rt.tick_bytes(1024, 4096, 8) == 4_194_304 + 4_096 + 208_896
    assert rt.tick_bytes(8, 4096, 8) == 32_768 + 32 + 1_632


def test_loader_reads_what_the_profiler_writes(tmp_path):
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    import numpy as np

    f = jax.jit(lambda x: x.astype('int32').sum(axis=1))
    x = np.zeros((8, 128), np.uint8)
    np.asarray(f(x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation('ingest_tick'):
        np.asarray(f(x))
    jax.profiler.stop_trace()
    path = rt.find_xplane(str(tmp_path))
    assert path and path.endswith('.xplane.pb')
    trace = rt.load_xplane(path, keep_host=('ingest_tick',))
    host = [p for p in trace['planes'] if p['name'] == rt.HOST_PLANE]
    names = {e[0] for p in host for ln in p['lines'] for e in ln['events']}
    assert names == {'ingest_tick'}
    # a CPU trace has no TPU plane: nothing ran on a device
    assert rt.reduce(trace, host_spans=('ingest_tick',))['busy_s'] == 0.0
    assert 'PLANE /host:CPU' in rt.summarize(trace)


@pytest.mark.skipif(not os.path.isfile(os.path.join(DATA, 'v5e_read.json')),
                    reason='no recorded trace')
def test_recorded_v5e_trace_reduces_to_the_recorded_numbers():
    trace = rt.load_json(os.path.join(DATA, 'v5e_read.json'))
    with open(os.path.join(DATA, 'v5e_read.expected.json')) as f:
        want = json.load(f)
    red = rt.reduce(trace, window_ns=want['window_ns'],
                    host_spans=('ingest_tick', 'await_replies', 'validate'))
    assert red['chips'] == want['chips']
    assert red['busy_s'] == pytest.approx(want['busy_s'], rel=1e-9)
    assert red['programs']['jit_step']['count'] == want['jit_step_count']
    assert red['programs']['jit_step']['seconds'] == pytest.approx(
        want['jit_step_seconds'], rel=1e-9)
    assert red['ops'][0][0] == want['top_op']
    assert dict(red['idle_gaps']) == pytest.approx(want['idle_gaps'],
                                                   rel=1e-9)
    assert 0 < red['busy_s'] < red['window_s']
    # by hand: the five tick programs of this window ran 458,390 +
    # 1,981,906 + 459,359 + 1,982,578 + 459,777 ns; the ops inside them
    # leave a few hundred ns between them uncovered
    by_hand = 458_390 + 1_981_906 + 459_359 + 1_982_578 + 459_777
    assert sum(want['module_ns_by_hand']) == by_hand
    assert red['programs']['jit_step']['seconds'] == pytest.approx(
        by_hand / 1e9)
    assert 0.999 * by_hand <= red['busy_s'] * 1e9 <= by_hand
