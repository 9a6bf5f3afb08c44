"""``hunt3_1k.read_deep`` at toy size on the CPU backend: the cell a
pipelined fleet runs (8 reads outstanding a session), its mix, its
control and its two readers."""

import json
import os
import types

import pytest

from conftest import BENCH, entry
from test_runs import (compiled_in_window, members_alive, rehearse,
                       run_dirs, tmp)  # noqa: F401  (tmp: a fixture)

CELL = 'hunt3_1k.read_deep'


def test_the_mix_is_the_one_the_warm_up_was_proved_on():
    """``traffic/read_deep.json`` is ``tests/data/read_deep.json``,
    letter for letter (PR 46 proved ``warm_max_len`` on that file)."""
    with open(os.path.join(BENCH, 'traffic', 'read_deep.json'), 'rb') as f:
        mix = f.read()
    with open(os.path.join(BENCH, 'tests', 'data', 'read_deep.json'),
              'rb') as f:
        assert f.read() == mix
    mix = json.loads(mix)
    assert (mix['engine'], mix['outstanding'], mix['warm_max_len']) == (
        'kv_closed', 8, 16384)


def test_the_configuration_is_hunt3_1k_with_its_clients_pipelined():
    """``configs/hunt3_1k_pipelined.json`` differs from ``hunt3_1k.json``
    in the client's shape alone, and states the depth the mix runs."""
    cfgs = {}
    for name in ('hunt3_1k', 'hunt3_1k_pipelined'):
        with open(os.path.join(BENCH, 'configs', name + '.json')) as f:
            cfgs[name] = json.load(f)
    base, deep = cfgs['hunt3_1k'], cfgs['hunt3_1k_pipelined']
    with open(os.path.join(BENCH, 'traffic', 'read_deep.json')) as f:
        assert deep.pop('outstanding_per_session') == json.load(
            f)['outstanding']
    told = {'name', 'source', 'deployment', 'reduced', 'assumed'}
    assert set(deep) == set(base)
    assert {k for k in base if base[k] != deep[k]} == told
    assert set(deep['reduced']) == set(base['reduced']) - {'active_writers'}
    assert entry('ingest.frames_per_slot', CELL).endswith('.deep')
    with open(os.path.join(os.path.dirname(BENCH), 'BENCHMARK.json')) as f:
        cell = next(w for w in json.load(f)['workloads']
                    if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'hunt3_1k_pipelined', 'read_deep', 1)


def test_sound_run_is_correct_and_compiles_nothing_in_the_window(tmp):
    r, out = rehearse(tmp, '--one', CELL, '--seed', str(2 ** 31 + 47),
                      '--seconds', '2')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert {'ops_per_s.read', 'read_p95_ms', 'setup_s'} <= set(
        out['metrics'])
    assert compiled_in_window(r.stdout) == '[]'
    assert not members_alive() and not run_dirs(tmp)


def test_swapped_replies_read_not_correct(tmp):
    r, out = rehearse(tmp, '--one', CELL, '--seed', '5', '--seconds', '3',
                      '--control', 'swap_replies')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False and out['failed'] == 0
    assert '# NOT CORRECT' in r.stdout and 'payload' in r.stdout
    assert not members_alive() and not run_dirs(tmp)


def test_swapping_needs_a_second_reply_in_flight(tmp):
    """At one request outstanding the control finds nothing to swap."""
    r, out = rehearse(tmp, '--one', 'hunt3_1k.read', '--seed', '5',
                      '--seconds', '2', '--control', 'swap_replies')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0


def test_traced_run_reports_what_a_pipelined_tick_did(tmp):
    r, out = rehearse(tmp, '--one', CELL, '--seed', '9', '--seconds', '3',
                      '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True
    m = out['metrics']
    per_slot = m[entry('ingest.frames_per_slot', CELL)]['value']
    assert 2.0 < per_slot <= 8.0
    # 8 outstanding = max_frames: a tick drains a session's whole
    # window, so no slot waits for the tick after
    assert m[entry('ingest.retick_share', CELL)]['value'] < 5.0
    assert m[entry('ingest.frames_per_tick', CELL)]['value'] > 24
    assert entry('gc.pause_share', CELL) in m
    # no device, no device metric
    assert entry('decode.kernel_ms_per_tick', CELL) not in m


def _span(op, tick, **fields):
    return types.SimpleNamespace(op=op, tick=tick, **fields)


@pytest.mark.parametrize('reader,spans,want', [
    ('ingest.frames_per_slot',
     [_span('ingest.tick', 1, batch=24), _span('ingest.dispatch', 1, rows=3),
      _span('ingest.tick', None, batch=7),
      _span('ingest.tick', 2, batch=8), _span('ingest.dispatch', 2, rows=1)],
     8.0),
    ('ingest.frames_per_slot', [_span('ingest.tick', 1)], None),
    ('ingest.retick_share',
     [_span('ingest.tick', 1, retick=1), _span('ingest.tick', 2, retick=0),
      _span('ingest.tick', 3, retick=0), _span('ingest.tick', None),
      _span('ingest.tick', 4, retick=0)], 25.0),
    # the parent's shape: device ticks whose spans carry no such field
    ('ingest.retick_share',
     [_span('ingest.tick', 1, batch=24), _span('ingest.tick', 2, batch=8)],
     None)])
def test_the_readers_on_spans_with_and_without_the_fields(
        monkeypatch, reader, spans, want):
    import harness
    import inside

    mod = harness._load_module('layer_metrics', reader + '.deep')
    ring = types.SimpleNamespace(spans=lambda: spans, dropped=0)
    monkeypatch.setattr(inside, 'host_ring', lambda run: ring)
    assert mod.read(object()) == want
    monkeypatch.setattr(inside, 'host_ring', lambda run: None)
    assert mod.read(object()) is None
