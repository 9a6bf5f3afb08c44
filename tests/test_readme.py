"""README.md against the tree.

The quick-start must actually run: its first python code block is
executed in a subprocess — documentation drift (renamed imports,
changed signatures) fails CI instead of greeting new users.

And what the README tells a reader to run, set or open must exist.
``zkanalyze``'s drift rule holds code -> README (a knob or metric the
code has is documented); the second test holds the other direction, so
a deletion that forgets the README is a red test.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = open(os.path.join(REPO, 'README.md')).read()


def test_readme_quickstart_runs():
    m = re.search(r'## Quick start\s+```python\n(.*?)```', README,
                  re.DOTALL)
    assert m, 'README quick-start code block not found'
    snippet = m.group(1)
    assert 'asyncio.run(main())' in snippet
    r = subprocess.run(
        [sys.executable, '-c', snippet], capture_output=True,
        text=True, cwd=REPO, timeout=90)
    assert r.returncode == 0, (r.stdout, r.stderr)
    # the snippet registers a session listener that prints
    assert 'new session' in r.stdout, r.stdout


_SKIP_DIRS = {'__pycache__', 'chiprun_out'}


def _tree() -> list[str]:
    """Repo-relative paths of every file outside dot-directories and
    build leftovers."""
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith('.') and d not in _SKIP_DIRS]
        rel = os.path.relpath(root, REPO)
        out += [os.path.normpath(os.path.join(rel, f)) for f in files]
    return out


def _stale_make_targets() -> set[str]:
    makefile = open(os.path.join(REPO, 'Makefile')).read()
    targets = set(re.findall(r'^([a-z][a-z-]*):', makefile, re.M))
    return set(re.findall(r'\bmake ([a-z][a-z-]*)', README)) - targets


def _stale_knobs() -> set[str]:
    """``ZKSTREAM_*`` names the README mentions and no code reads (a
    name ending in ``_`` is a family: some name must start with it)."""
    code = set()
    for p in _tree():
        if p.endswith(('.py', '.c', '.cpp')) or p == 'Makefile':
            with open(os.path.join(REPO, p), errors='replace') as f:
                code |= set(re.findall(r'ZKSTREAM_[A-Z0-9_]+', f.read()))
    return {k for k in set(re.findall(r'ZKSTREAM_[A-Z0-9_]+', README))
            if not (any(c.startswith(k) for c in code)
                    if k.endswith('_') else k in code)}


def _stale_paths() -> set[str]:
    """``*.md`` / ``*.py`` / ``*.c`` / ``*.cpp`` paths the README names
    that are nowhere: not from the root, not inside the package, and
    (a bare file name) not the name of any file in the tree."""
    tree = _tree()
    names = {os.path.basename(p) for p in tree}
    stale = set()
    for p in set(re.findall(r'[A-Za-z0-9_][A-Za-z0-9_./-]*\.(?:md|py|cpp|c)\b',
                            README)):
        if '/' not in p:
            ok = p in names
        else:
            ok = any(os.path.exists(os.path.join(REPO, base, p))
                     for base in ('', 'zkstream_tpu'))
        if not ok:
            stale.add(p)
    return stale


@pytest.mark.parametrize('rule', [
    _stale_make_targets, _stale_knobs, _stale_paths],
    ids=['make-targets', 'knobs', 'paths'])
def test_readme_names_only_what_exists(rule):
    assert rule() == set()
