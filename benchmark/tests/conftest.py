"""These tests are the benchmark's own: run them by hand
(``python -m pytest benchmark/tests -q``); tier-1 does not collect
them."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def entries(reader: str, cell: str | None = None,
            layers: list | None = None) -> list[dict]:
    """The ``per_layer`` entries (of ``BENCHMARK.json``, or of
    ``layers``) that ``harness.reader_path`` resolves to
    ``layer_metrics/<reader>.py`` — those that list ``cell``, when one
    is given.  A test names what READS an entry: not where it stands in
    the file, how many there are, nor which suffix a merge gave it."""
    import json

    import harness

    if layers is None:
        with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
            layers = json.load(f)['per_layer']
    want = os.path.join(BENCH, 'layer_metrics', reader + '.py')
    return [m for m in layers
            if (cell is None or cell in m['workloads'])
            and harness.reader_path('layer_metrics', m['name']) == want]


def entry(reader: str, cell: str) -> str:
    """The name of THE entry through which ``cell`` reads ``reader``."""
    (m,) = entries(reader, cell)
    return m['name']
