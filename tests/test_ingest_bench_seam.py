"""The names the benchmark holds ``FleetIngest`` by (ROADMAP D19).

Files under ``benchmark/`` construct the ingest from each config's
``ingest`` block, read its counters by name, wrap ``_bucket``, call
``_step_fn(False)`` and compute the tick program's bytes from its
packed layout.  Tier-1 does not collect ``benchmark/tests``: this file
is what fails when a change to io/ingest.py breaks one of them.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os

import pytest

from zkstream_tpu.io.ingest import FleetIngest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')

#: benchmark/harness.py INGEST_COUNTERS
COUNTERS = ('ticks', 'ticks_scalar', 'ticks_warming', 'ticks_frag',
            'frames_routed', 'body_fallbacks')


def _blocks():
    """(id, ``ingest`` block) of every config, full and toy, merged as
    benchmark/harness.py ``Cell`` merges a ``toy`` block."""
    for path in sorted(glob.glob(os.path.join(BENCH, 'configs',
                                              '*.json'))):
        with open(path) as f:
            cfg = json.load(f)
        name = os.path.splitext(os.path.basename(path))[0]
        full = cfg['ingest']
        yield pytest.param(full, id=name + '-full')
        yield pytest.param(
            {**full, **cfg.get('toy', {}).get('ingest', {})},
            id=name + '-toy')


@pytest.mark.parametrize('block', list(_blocks()))
def test_a_config_block_builds_the_ingest_the_harness_reads(block):
    ingest = FleetIngest(placement='host', **block)
    try:
        key = ingest._bucket(8, block['min_len'])
        assert len(key) == 3 and key[0] is False
        assert key[2] == block['min_len']
        for name in COUNTERS:
            assert type(getattr(ingest, name)) is int, name
        assert callable(ingest._step_fn(False))
    finally:
        ingest.close()


def test_the_tick_program_is_the_one_the_trace_reduction_finds():
    """``jit_step`` by name, and as many bytes out as
    benchmark/reduce_trace.py ``tick_bytes`` counts for its bucket."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        'bench_reduce_trace', os.path.join(BENCH, 'reduce_trace.py'))
    reduce_trace = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reduce_trace)

    with open(os.path.join(BENCH, 'configs', 'hunt3_1k.json')) as f:
        block = json.load(f)['ingest']
    ingest = FleetIngest(placement='host', **block)
    try:
        fn = ingest._step_fn(False)
        _bodies, bp, length = ingest._bucket(1024, block['min_len'])
        out = jax.eval_shape(
            fn, jax.ShapeDtypeStruct((bp, length), jnp.uint8),
            jax.ShapeDtypeStruct((bp,), jnp.int32))
    finally:
        ingest.close()
    assert fn.__name__ == 'step'
    frames = block['max_frames']
    assert out.dtype == jnp.int32 and out.shape == (
        bp, reduce_trace.HEAD_COLUMNS
        + reduce_trace.HEADER_PLANES * frames)
    assert reduce_trace.tick_bytes(bp, length, frames) == (
        bp * length + 4 * bp + 4 * out.size)
