"""utils/native.py failure paths: build failures, ABI mismatches,
load latches, the ZKSTREAM_NO_NATIVE kill switch, and the background
builder — the code that only runs when the toolchain or artifacts are
broken (VERDICT r3 weak #5: coverage thinnest on failure paths).

Every test redirects the source/artifact paths into a tmpdir so the
real build products are never touched, and restores the module-level
latches afterward.
"""

from __future__ import annotations

import os
import subprocess
import sysconfig
import threading
import time

import pytest

from zkstream_tpu.utils import native


@pytest.fixture
def pristine(monkeypatch, tmp_path):
    """Snapshot/restore the loader's global latches and point every
    path helper into a private tmpdir."""
    saved = (native._lib, native._load_failed, native._builder,
             native._ext, native._ext_load_failed, native._ext_builder)
    native._lib = None
    native._load_failed = False
    native._builder = None
    native._ext = None
    native._ext_load_failed = False
    native._ext_builder = None
    monkeypatch.setattr(native, 'source_path',
                        lambda: str(tmp_path / 'zkwire.cpp'))
    monkeypatch.setattr(native, 'lib_path',
                        lambda: str(tmp_path / 'libzkwire.test.so'))
    monkeypatch.setattr(native, 'ext_source_path',
                        lambda: str(tmp_path / 'zkwire_ext.c'))
    monkeypatch.setattr(native, 'ext_path',
                        lambda: str(tmp_path / '_zkwire_ext.test.so'))
    yield tmp_path
    (native._lib, native._load_failed, native._builder,
     native._ext, native._ext_load_failed, native._ext_builder) = saved


def have_cc() -> bool:
    try:
        subprocess.run(['g++', '--version'], capture_output=True,
                       timeout=30)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False


def test_build_missing_source_returns_none(pristine):
    assert native.build() is None
    assert native.build_ext() is None


def test_build_compile_failure_returns_none(pristine):
    if not have_cc():
        pytest.skip('no compiler')
    (pristine / 'zkwire.cpp').write_text('int main( {')   # broken
    (pristine / 'zkwire_ext.c').write_text('int main( {')
    assert native.build() is None
    assert native.build_ext() is None
    # no artifact and no half-written tmp left behind
    leftovers = [p for p in os.listdir(pristine) if '.so' in p]
    assert leftovers == []


def test_ensure_lib_and_ext_fail_cleanly(pristine):
    """The blocking variants return None (never raise) when the build
    cannot produce an artifact."""
    assert native.ensure_lib() is None
    assert native.ensure_ext() is None


def test_kill_switch_disables_everything(pristine, monkeypatch):
    monkeypatch.setenv('ZKSTREAM_NO_NATIVE', '1')
    assert native.get_lib() is None
    assert native.ensure_lib() is None
    assert native.get_ext() is None
    assert native.ensure_ext() is None
    assert native._builder is None       # no builder ever spawned
    assert native._ext_builder is None


def test_abi_mismatch_latches_lib(pristine):
    """A stale-ABI artifact (version-named files should prevent this,
    but belt-and-braces) must latch load-failed, not bind."""
    if not have_cc():
        pytest.skip('no compiler')
    src = pristine / 'zkwire.cpp'
    src.write_text('extern "C" int zkwire_abi_version() '
                   '{ return 987654; }\n')
    out = native.build()
    assert out is not None               # the build itself succeeded
    with native._lock:
        native._try_load()
    assert native._lib is None
    assert native._load_failed           # latched: no rebind attempts
    assert native.get_lib() is None


def test_abi_mismatch_latches_ext(pristine):
    if not have_cc():
        pytest.skip('no compiler')
    src = pristine / 'zkwire_ext.c'
    src.write_text(
        '#include <Python.h>\n'
        'static PyObject* abi_version(PyObject* s, PyObject* a)'
        '{ return PyLong_FromLong(987654); }\n'
        'static PyMethodDef m[] = {{"abi_version", abi_version, '
        'METH_NOARGS, ""}, {NULL, NULL, 0, NULL}};\n'
        'static struct PyModuleDef mod = {PyModuleDef_HEAD_INIT, '
        '"_zkwire_ext", NULL, -1, m};\n'
        'PyMODINIT_FUNC PyInit__zkwire_ext(void)'
        '{ return PyModule_Create(&mod); }\n')
    out = native.build_ext()
    if out is None:
        pytest.skip('Python.h unavailable')
    with native._lock:
        native._try_load_ext()
    assert native._ext is None
    assert native._ext_load_failed
    assert native.get_ext() is None


def test_get_lib_background_build_failure_latches(pristine):
    """get_lib with no artifact spawns the background builder; a build
    failure latches load-failed so gcc is never respawned."""
    (pristine / 'zkwire.cpp').write_text('int main( {')
    assert native.get_lib() is None      # kicks the builder
    builder = native._builder
    assert builder is not None
    builder.join(120)
    assert not builder.is_alive()
    assert native._load_failed
    # the latch holds: no new builder on subsequent calls
    assert native.get_lib() is None
    assert native._builder is builder


def test_get_ext_background_build_failure_latches(pristine):
    (pristine / 'zkwire_ext.c').write_text('int main( {')
    assert native.get_ext() is None
    builder = native._ext_builder
    assert builder is not None
    builder.join(120)
    assert native._ext_load_failed
    assert native.get_ext() is None
    assert native._ext_builder is builder


def test_corrupt_artifact_load_failure_latches(pristine):
    """An artifact dlopen cannot load (truncated/garbage .so) latches
    rather than raising into the caller."""
    src = pristine / 'zkwire.cpp'
    src.write_text('// source\n')
    bad = pristine / 'libzkwire.test.so'
    bad.write_bytes(b'\x7fELF garbage')
    with native._lock:
        native._try_load()
    assert native._lib is None and native._load_failed

    esrc = pristine / 'zkwire_ext.c'
    esrc.write_text('// source\n')
    ebad = pristine / '_zkwire_ext.test.so'
    ebad.write_bytes(b'\x7fELF garbage')
    with native._lock:
        native._try_load_ext()
    assert native._ext is None and native._ext_load_failed


def test_concurrent_get_lib_single_builder(pristine):
    """Hammering get_lib from threads while no artifact exists spawns
    at most one live builder (the lock-guarded spawn)."""
    (pristine / 'zkwire.cpp').write_text('int main( {')
    seen = set()

    def hit():
        for _ in range(5):
            native.get_lib()
            b = native._builder
            if b is not None:
                seen.add(b)
    ts = [threading.Thread(target=hit) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # builders may chain if one exits between calls, but never two
    # alive at once; after the latch lands no more spawn
    if native._builder is not None:
        native._builder.join(120)
    assert native._load_failed
    before = native._builder
    native.get_lib()
    assert native._builder is before


def test_ext_path_is_abi_tagged():
    """The artifact name carries both the extension ABI version and
    the interpreter SOABI tag, so a Python upgrade or ABI bump can
    never bind a stale artifact (no fixture here: the real paths)."""
    tag = sysconfig.get_config_var('SOABI') or 'abi3'
    assert tag in native.ext_path()
    assert 'v%d' % native._EXT_ABI_VERSION in native.ext_path()


# -- artifacts are named by what they were built from -------------------

_GOOD_LIB = ('extern "C" int zkwire_abi_version() { return %d; }\n'
             'extern "C" int zkwire_frame_scan(const void*, int, int, '
             'int, int*, int*, int*) { return 0; }\n'
             % native._ABI_VERSION)


@pytest.fixture
def own_tree(monkeypatch, tmp_path):
    """Like ``pristine``, but with the REAL path helpers over a private
    checkout root, so the hash naming itself is under test."""
    saved = (native._lib, native._load_failed, native._builder)
    native._lib, native._load_failed, native._builder = None, False, None
    (tmp_path / 'native').mkdir()
    (tmp_path / 'tools').mkdir()
    monkeypatch.setattr(native, '_root', lambda: str(tmp_path))
    yield tmp_path
    native._lib, native._load_failed, native._builder = saved


def test_artifact_name_follows_source_and_flags(own_tree, monkeypatch):
    """The name carries a digest of the source bytes and the compile
    command: edit either and the artifact is a different file."""
    src = own_tree / 'native' / 'zkwire.cpp'
    src.write_text(_GOOD_LIB)
    first = native.lib_path()
    assert first == native.lib_path()            # stable
    assert os.path.dirname(first) == str(own_tree / 'native')
    src.write_text(_GOOD_LIB + '// edited\n')
    edited = native.lib_path()
    assert edited != first
    monkeypatch.setattr(native, '_LIB_CC', native._LIB_CC + ['-O3'])
    assert native.lib_path() not in (first, edited)

    lg = own_tree / 'tools' / 'loadgen.c'
    lg.write_text('int main(void) { return 0; }\n')
    a = native.loadgen_path()
    lg.write_text('int main(void) { return 1; }\n')
    assert native.loadgen_path() != a


def test_planted_stale_binary_is_never_loaded(own_tree):
    """A checkout copied with its build outputs: binaries of an OLDER
    source sit in native/ with copy-time (i.e. newer) mtimes — under
    the old version-only name and under the older source's own hash
    name.  Neither is what the current files produce, so neither is
    bound: the loader asks for a build instead."""
    if not have_cc():
        pytest.skip('no compiler')
    src = own_tree / 'native' / 'zkwire.cpp'
    src.write_text(_GOOD_LIB.replace(
        'return %d' % native._ABI_VERSION, 'return 987654'))
    older = native.build()                # a real, loadable, STALE .so
    assert older is not None
    legacy = own_tree / 'native' / ('libzkwire.v%d.so'
                                    % native._ABI_VERSION)
    legacy.write_bytes(open(older, 'rb').read())
    future = time.time() + 3600
    os.utime(older, (future, future))     # "newer than source"
    os.utime(str(legacy), (future, future))

    src.write_text(_GOOD_LIB)             # the files git would commit
    want = native.lib_path()
    assert want not in (older, str(legacy))
    with native._lock:
        native._try_load()
    assert native._lib is None and not native._load_failed
    # the blocking path builds the current source and binds THAT
    lib = native.ensure_lib()
    assert lib is not None
    assert lib.zkwire_abi_version() == native._ABI_VERSION
    assert os.path.exists(want)


def test_loadgen_is_rebuilt_from_current_source(own_tree):
    if not have_cc():
        pytest.skip('no compiler')
    lg = own_tree / 'tools' / 'loadgen.c'
    lg.write_text('int main(void) { return 7; }\n')
    stale = native.build_loadgen()
    assert subprocess.run([stale]).returncode == 7
    lg.write_text('int main(void) { return 9; }\n')
    future = time.time() + 3600
    os.utime(stale, (future, future))
    fresh = native.build_loadgen()
    assert fresh != stale
    assert subprocess.run([fresh]).returncode == 9
