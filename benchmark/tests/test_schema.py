"""``BENCHMARK.json`` against the contract's limits, and every file it
names."""

import json
import os
import re

import pytest
from conftest import BENCH, ROOT, entries

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_.\-/]{1,200}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


@pytest.fixture(scope='module')
def bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def line(s, n=200):
    return (isinstance(s, str) and 1 <= len(s) <= n and '\n' not in s
            and '\t' not in s)


def test_top_level(bench):
    assert set(bench) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= len(bench['command']) <= 32
    assert all(line(w) and not w.startswith('/') and '..' not in w
               for w in bench['command'])
    assert 1 <= len(bench['paths']) <= 16
    assert all(PATH.match(p) for p in bench['paths'])
    assert isinstance(bench['run_seconds'], int)
    assert 1 <= bench['run_seconds'] <= 51
    # a full check with 24 cells has to fit
    cells = 24
    runs = 2 + 14 * cells
    assert (runs * (bench['run_seconds'] + 60) + cells * 2 * 90 + 1200
            <= 43200)


def under_paths(bench, path):
    return any(path.startswith(p.rstrip('/') + '/') for p in bench['paths'])


def test_configs(bench):
    assert 1 <= len(bench['configs']) <= 24
    names = [c['name'] for c in bench['configs']]
    files = [c['file'] for c in bench['configs']]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w['config'] for w in bench['workloads']}
    for c in bench['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert NAME.match(c['name']) and c['name'] in used
        assert line(c['source']) and line(c['why'])
        assert PATH.match(c['file']) and under_paths(bench, c['file'])
        assert len(c['reduced']) <= 16
        assert all(NAME.match(k) for k in c['reduced'])
        with open(os.path.join(ROOT, c['file'])) as f:
            cfg = json.load(f)
        assert cfg['source'] == c['source']
        assert sorted(cfg['reduced']) == sorted(c['reduced'])
        assert cfg['guarantees'] and cfg['assumed']
        assert cfg['chips'] in (1, 4)


def test_workloads(bench):
    assert 1 <= len(bench['workloads']) <= 24
    names = [w['name'] for w in bench['workloads']]
    assert len(set(names)) == len(names)
    pairs = [(w['config'], w['traffic']) for w in bench['workloads']]
    assert len(set(pairs)) == len(pairs)
    configs = {c['name'] for c in bench['configs']}
    four = sum(w['chips'] == 4 for w in bench['workloads'])
    assert four <= max(1, len(names) // 2)
    for w in bench['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert w['config'] in configs and w['chips'] in (1, 4)
        assert line(w['why'])
        mix = os.path.join(BENCH, 'traffic', w['traffic'] + '.json')
        with open(mix) as f:
            engine = json.load(f)['engine']
        assert os.path.isfile(os.path.join(BENCH, 'engines', engine + '.py'))


def metric_ok(m, extra):
    assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source'} | extra
    assert NAME.match(m['name']) and UNIT.match(m['unit'])
    assert m['better'] in ('lower', 'higher') and m['source'] in SOURCES


def reader(kind, name):
    """The metric's file, or the file of the name less its last dotted
    part (x.read -> x.py): ``harness.reader_path``'s one rule."""
    import harness
    return harness.reader_path(kind, name) is not None


def test_reader_rule_is_exact_name_then_less_the_suffix():
    import harness
    base = lambda p: p and os.path.basename(p)      # noqa: E731
    assert base(harness.reader_path('end_to_end', 'setup_s')) == 'setup_s.py'
    assert base(harness.reader_path(
        'end_to_end', 'ops_per_s.read')) == 'ops_per_s.py'
    assert base(harness.reader_path(
        'layer_metrics', 'decode.read.jit_step_roofline')) \
        == 'decode.read.jit_step_roofline.py'
    # no search over the other parts: a.b.c never finds a.c or b.c
    assert harness.reader_path('layer_metrics', 'ingest.read.tick_ms_p50') \
        is None
    assert harness.reader_path('layer_metrics', 'read.gen.late_ms_p95') \
        is None


def test_metrics(bench):
    cells = [w['name'] for w in bench['workloads']]
    e2e = bench['end_to_end']
    layers = bench['per_layer']
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m['name'] for m in e2e + layers]
    assert len(set(names)) == len(names)
    reports = {c: set() for c in cells}
    for m in e2e:
        metric_ok(m, {'bound'})
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.25
        assert reader('end_to_end', m['name'])
        for c in m.get('workloads', cells):
            assert c in cells
            reports[c].add(m['name'])
    assert 'setup_s' in {m['name'] for m in e2e}
    layer_cells = {c: 0 for c in cells}
    for m in layers:
        metric_ok(m, {'layer', 'moves'})
        assert line(m['layer'])
        assert reader('layer_metrics', m['name'])
        if m['name'].endswith('_roofline') or 'mfu' in m['name']:
            assert m['unit'] == '%'
        for c in m.get('workloads',
                       [c for c in cells if m['moves'] in reports[c]]):
            assert m['moves'] in reports[c], (m['name'], c)
            layer_cells[c] += 1
    for c in cells:
        assert 'setup_s' in reports[c] and len(reports[c]) >= 2
        assert layer_cells[c] >= 1


#: PR 34 retired these: each read a histogram since the member started
#: (set-up's handshake burst, not the window) beside a windowed twin
RETIRED = {('server.decode_apply_ms_p99', 'hunt3_1k.read'),
           ('wal.fsync_gate_ms_p99', 'hunt3_1k.write'),
           ('wal.fsync_gate_ms_p99.relist', 'discovery3.relist'),
           ('fanout.flush_ms_p99', 'discovery3.relist')}
#: ... and merged these three files into the two beside them, as PR 46
#: did the two that PRs 38 and 40 had to add for their cells: all the
#: same call as ``decode.write.jit_step_roofline.py``, which stands
MERGED = {'decode.load.jit_step_roofline.py':
          'decode.read.jit_step_roofline.py',
          'decode.push.jit_step_roofline.py':
          'decode.converge.jit_step_roofline.py',
          'decode.viewchange.jit_step_roofline.py':
          'decode.converge.jit_step_roofline.py',
          'decode.livenodes.jit_step_roofline.py':
          'decode.converge.jit_step_roofline.py',
          'decode.ycsb.jit_step_roofline.py':
          'decode.read.jit_step_roofline.py'}
#: the frozen tables: (entry, cell, reader file) as a merging PR's
#: parent had them, and how many rows each holds
TABLES = {'pr33': 128, 'pr45': 185}


def old_pairs(table):
    with open(os.path.join(BENCH, 'tests', 'data',
                           'per_layer_pairs.%s.txt' % (table,))) as f:
        return [tuple(row.split()) for row in f
                if not row.startswith('#')]


def still_read(layers, cell, want):
    """Some entry lists ``cell`` and resolves to the file ``want``."""
    return bool(entries(want[:-len('.py')], cell, layers))


@pytest.mark.parametrize('table', sorted(TABLES))
def test_every_old_pair_is_still_read(bench, table):
    """Merging entries lost no reader's coverage of any cell: every
    (entry, cell) pair of the 128 entries PR 33 left, and of the 128
    PR 45 left, is read by the same file in the same cell under SOME
    entry of today's list — but the four since-start entries, and
    nothing else."""
    import inspect

    import harness

    pairs = old_pairs(table)
    assert len(pairs) == TABLES[table] == len(set(pairs))
    retired = RETIRED & {(n, c) for n, c, _f in pairs}
    assert retired == (RETIRED if table == 'pr33' else set())
    lost = [(name, cell) for name, cell, want in pairs
            if (name, cell) not in retired
            and not still_read(bench['per_layer'], cell,
                               MERGED.get(want, want))]
    assert not lost, lost
    for name, cell, want in pairs:
        if (name, cell) in retired:
            assert not still_read(bench['per_layer'], cell, want)
    stands = harness._load_module('layer_metrics',
                                  'decode.write.jit_step_roofline').read
    for name in set(MERGED.values()):
        got = harness._load_module('layer_metrics', name[:-3]).read
        assert inspect.getsource(got) == inspect.getsource(stands)
    for name in MERGED:
        assert not os.path.exists(os.path.join(BENCH, 'layer_metrics',
                                               name))


@pytest.mark.parametrize('table', sorted(TABLES))
def test_the_check_sees_a_cell_dropped_from_a_list(bench, table):
    """Each of the pairs that stand is read under exactly ONE entry:
    that entry's ``workloads`` less the pair's cell loses it."""
    layers = bench['per_layer']
    for _name, cell, want in old_pairs(table):
        if (_name, cell) in RETIRED:
            continue
        want = MERGED.get(want, want)
        (m,) = entries(want[:-len('.py')], cell, layers)
        cut = [dict(x, workloads=[c for c in x['workloads'] if c != cell])
               if x is m else x for x in layers]
        assert not still_read(cut, cell, want), (m['name'], cell)


def test_one_entry_a_reader_and_end_to_end_metric(bench):
    """No two entries share a reader file and ``moves``: such a pair
    differs in nothing but the suffix and is one entry with both cells
    on its ``workloads`` list (the roofline files, one to a family
    because the name must END in ``_roofline``, count by their
    ``read``)."""
    import harness
    seen = {}
    for m in bench['per_layer']:
        path = harness.reader_path('layer_metrics', m['name'])
        key = (MERGED.get(os.path.basename(path), path), m['moves'])
        assert key not in seen, (m['name'], seen[key])
        seen[key] = m['name']
        assert m['workloads'], m['name']
    assert len(bench['per_layer']) <= 128


def test_every_file_under_paths_has_a_contract_name(bench):
    ok = re.compile(r'^[A-Za-z0-9_.\-/]+$')
    for p in bench['paths']:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != '__pycache__']
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_peaks_name_their_source():
    with open(os.path.join(BENCH, 'peaks.json')) as f:
        peaks = json.load(f)
    assert peaks['TPU v5 lite']['hbm_bytes_per_s'] == 819e9
    assert all('source' in v for v in peaks.values())
