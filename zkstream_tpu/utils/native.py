"""Loader for the native host codec (native/zkwire.cpp).

Builds the shared library on first use with the ambient ``g++`` and
binds it via ctypes.  Design constraints, in order:

- **Never block the event loop.**  ``get_lib()`` only dlopens an
  already-built artifact; when a build is needed it is kicked off on a
  daemon thread and ``get_lib()`` returns None until it lands, so the
  connection path runs pure-Python in the meantime.  Anything that
  measures (benchmark/, chip_smoke.py) calls the blocking ``ensure_*`` /
  ``build_loadgen`` instead and fails on None.
- **Stale artifacts can't poison the process.**  The artifact name
  embeds the ABI version and a hash of the source and compile flags
  (``libzkwire.v1.<hash>.so``); a build of any other source is simply
  a different filename that is never dlopened — file times say nothing
  in a checkout that was copied — sidestepping glibc's same-path
  handle caching as well.
- **Graceful degradation.**  No compiler, failed build, failed load →
  None, and callers keep the pure-Python implementations — mirroring
  how the reference runs on nothing but the OS TCP stack (SURVEY.md §2:
  zero native components required).

``ZKSTREAM_NO_NATIVE=1`` forces the pure-Python path (tests A/B the two
implementations with it).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger('zkstream_tpu.native')

_ABI_VERSION = 1

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed = False
_builder: threading.Thread | None = None


def _root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def source_path() -> str:
    return os.path.join(_root(), 'native', 'zkwire.cpp')


_LIB_CC = ['g++', '-O2', '-shared', '-fPIC', '-std=c++17']


def _digest(src: str, cc: list[str]) -> str:
    """What an artifact was built from: the compile command and the
    source bytes.  It goes into the artifact's name, so the binary that
    loads is by construction the one these files produce."""
    h = hashlib.sha256(' '.join(cc).encode())
    with open(src, 'rb') as f:
        h.update(f.read())
    return h.hexdigest()[:12]


def _compile(what: str, cc: list[str], src: str, out: str) -> str | None:
    """Build ``out`` from ``src`` unless it is already there (its name
    says what it was built from); returns the path, or None when the
    compiler is missing or the compile fails."""
    if os.path.exists(out):
        return out
    tmp = out + '.tmp.%d' % os.getpid()
    try:
        r = subprocess.run(cc + [src, '-o', tmp], capture_output=True,
                           text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.info('%s build unavailable: %s', what, e)
        return None
    if r.returncode != 0:
        log.warning('%s build failed: %s', what, r.stderr.strip())
        return None
    os.replace(tmp, out)  # atomic: concurrent builders can't mix halves
    return out


def lib_path() -> str:
    return os.path.join(
        _root(), 'native', 'libzkwire.v%d.%s.so'
        % (_ABI_VERSION, _digest(source_path(), _LIB_CC)))


def build() -> str | None:
    """Compile the library unless the artifact for this source is
    already there; return its path or None.  Synchronous — call from
    tests/tools, not the event loop (:func:`get_lib` wraps it in a
    background thread)."""
    src = source_path()
    if not os.path.exists(src):
        return None
    return _compile('native', _LIB_CC, src, lib_path())


def _bind(path: str) -> ctypes.CDLL | None:
    lib = ctypes.CDLL(path)
    lib.zkwire_abi_version.restype = ctypes.c_int32
    lib.zkwire_abi_version.argtypes = []
    if lib.zkwire_abi_version() != _ABI_VERSION:
        log.warning('libzkwire ABI mismatch (version-named artifact '
                    'should make this impossible)')
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.zkwire_frame_scan.restype = ctypes.c_int32
    lib.zkwire_frame_scan.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        i32p, i32p, i32p]
    return lib


def _try_load() -> None:
    """Bind the on-disk artifact built from the current source, if
    there is one (one source hash + stat + dlopen).  Sets
    _lib/_load_failed; caller holds _lock."""
    global _lib, _load_failed
    if not os.path.exists(source_path()):
        return
    out = lib_path()
    if not os.path.exists(out):
        return
    try:
        _lib = _bind(out)
    except OSError as e:
        log.warning('libzkwire load failed: %s', e)
        _lib = None
    if _lib is None:
        _load_failed = True


def get_lib() -> ctypes.CDLL | None:
    """The bound library, or None if unavailable (yet).

    Non-blocking: when the artifact is missing the build runs on a
    daemon thread and this returns None until a later call finds the
    artifact ready."""
    global _builder
    if os.environ.get('ZKSTREAM_NO_NATIVE') == '1':
        return None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        _try_load()
        if _lib is not None or _load_failed:
            return _lib
        if _builder is None or not _builder.is_alive():
            _builder = threading.Thread(
                target=_build_or_latch, name='zkwire-build', daemon=True)
            _builder.start()
        return None


def _build_or_latch() -> None:
    """Background-build the C-ABI library; a failed compile latches
    ``_load_failed`` so later ``get_lib`` calls don't respawn gcc for
    the life of the process."""
    global _load_failed
    if build() is None:
        with _lock:
            _load_failed = True


def ensure_lib(timeout: float = 120.0) -> ctypes.CDLL | None:
    """Blocking variant for tests/tools: build synchronously and bind."""
    if os.environ.get('ZKSTREAM_NO_NATIVE') == '1':
        return None
    if build() is None:
        return None
    return get_lib()


# -- CPython-extension decoder (native/zkwire_ext.c) ------------------
#
# Separate artifact from the C-ABI scanner: it links against the
# interpreter ABI (Python.h), decodes whole accumulation buffers into
# packet dicts (framing + reply bodies in one C pass), and is loaded
# with the same version-named-artifact / background-build discipline.

_EXT_ABI_VERSION = 15

_ext = None
_ext_load_failed = False
_ext_builder: threading.Thread | None = None


def ext_source_path() -> str:
    return os.path.join(_root(), 'native', 'zkwire_ext.c')


def _ext_cc() -> list[str]:
    import sysconfig
    return ['gcc', '-O2', '-shared', '-fPIC', '-pthread',
            '-I', sysconfig.get_paths()['include']]


def ext_path() -> str:
    import sysconfig
    tag = sysconfig.get_config_var('SOABI') or 'abi3'
    return os.path.join(
        _root(), 'native', '_zkwire_ext.v%d.%s.%s.so'
        % (_EXT_ABI_VERSION, tag,
           _digest(ext_source_path(), _ext_cc())))


def build_ext() -> str | None:
    """Compile the extension unless the artifact for this source is
    already there; return path or None."""
    src = ext_source_path()
    if not os.path.exists(src):
        return None
    return _compile('native ext', _ext_cc(), src, ext_path())


#: opcode -> reply-body-layout enum shared with zkwire_ext.c (keep in
#: sync with records._RESP_READERS / _EMPTY_RESPONSES).
_EXT_LAYOUTS = {
    'SET_WATCHES': 0, 'SET_WATCHES2': 0, 'ADD_WATCH': 0, 'PING': 0,
    'SYNC': 0, 'DELETE': 0, 'CLOSE_SESSION': 0, 'AUTH': 0,
    'GET_CHILDREN': 1, 'GET_CHILDREN2': 2, 'CREATE': 3, 'GET_ACL': 4,
    'GET_DATA': 5, 'EXISTS': 6, 'SET_DATA': 6, 'NOTIFICATION': 7,
    'MULTI': 8,
}

#: opcode -> request-body-layout enum (keep in sync with
#: records._REQ_READERS): 0 empty, 1 path, 2 path+watch, 3 create,
#: 4 delete, 5 set_data, 6 set_watches, 7 multi, 8 add_watch,
#: 9 set_watches2.
_EXT_REQ_LAYOUTS = {
    'GET_CHILDREN': 2, 'GET_CHILDREN2': 2, 'GET_DATA': 2, 'EXISTS': 2,
    'CREATE': 3, 'DELETE': 4, 'GET_ACL': 1, 'SET_DATA': 5, 'SYNC': 1,
    'SET_WATCHES': 6, 'CLOSE_SESSION': 0, 'PING': 0, 'MULTI': 7,
    'ADD_WATCH': 8, 'SET_WATCHES2': 9,
}

#: Opcodes the spec tier decodes but the extension deliberately PUNTS
#: (decode_stream returns kind='UNSUPPORTED' at the frame boundary and
#: PacketCodec hands the rest of the buffer to the Python spec tier).
#: Empty since the MULTI layouts landed (the PR 12 carry closed):
#: every spec reader has a C layout in both directions; the punt
#: MACHINERY stays for the next variable-shape opcode.  The sync test
#: in tests/test_native_ext.py holds ``layouts | punts == spec
#: readers``; byte-identical MULTI A/B lives in tests/test_multi.py.
_EXT_PUNT_OPS = frozenset()


def ext_setup_args() -> tuple:
    """The argument tuple for ``_zkwire_ext.setup`` — shared by the
    loader and out-of-band harnesses (tools/asan_check.py) so a
    signature change cannot leave them disagreeing."""
    from ..protocol import records
    from ..protocol.consts import (
        CreateFlag,
        ErrCode,
        KeeperState,
        NotificationType,
        OpCode,
        Perm,
    )

    return (
        records.Stat, records.ACL, records.Id, Perm, CreateFlag,
        {int(e): e.name for e in ErrCode},
        {int(t): t.name for t in NotificationType},
        {int(s): s.name for s in KeeperState},
        dict(_EXT_LAYOUTS),
        {int(OpCode[name]): (name, layout)
         for name, layout in _EXT_REQ_LAYOUTS.items()},
        {int(o): o.name for o in OpCode},
        {e.name: int(e) for e in ErrCode},
        {t.name: int(t) for t in NotificationType},
        {s.name: int(s) for s in KeeperState},
        {o.name: int(o) for o in OpCode},
    )


def _bind_ext(path: str):
    import importlib.machinery
    import importlib.util

    loader = importlib.machinery.ExtensionFileLoader('_zkwire_ext', path)
    spec = importlib.util.spec_from_file_location(
        '_zkwire_ext', path, loader=loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    if mod.abi_version() != _EXT_ABI_VERSION:
        log.warning('zkwire_ext ABI mismatch')
        return None
    mod.setup(*ext_setup_args())
    return mod


def _try_load_ext() -> None:
    global _ext, _ext_load_failed
    if not os.path.exists(ext_source_path()):
        return
    out = ext_path()
    if not os.path.exists(out):
        return
    try:
        _ext = _bind_ext(out)
    except (OSError, ImportError) as e:
        log.warning('zkwire_ext load failed: %s', e)
        _ext = None
    if _ext is None:
        _ext_load_failed = True


def get_ext():
    """The bound extension module, or None if unavailable (yet).
    Non-blocking, same contract as :func:`get_lib`."""
    global _ext_builder
    if os.environ.get('ZKSTREAM_NO_NATIVE') == '1':
        return None
    with _lock:
        if _ext is not None or _ext_load_failed:
            return _ext
        _try_load_ext()
        if _ext is not None or _ext_load_failed:
            return _ext
        if _ext_builder is None or not _ext_builder.is_alive():
            _ext_builder = threading.Thread(
                target=_build_ext_or_latch, name='zkwire-ext-build',
                daemon=True)
            _ext_builder.start()
        return None


def _build_ext_or_latch() -> None:
    """Background-build the extension; latch failure like
    :func:`_build_or_latch`."""
    global _ext_load_failed
    if build_ext() is None:
        with _lock:
            _ext_load_failed = True


def ensure_ext():
    """Blocking variant for tests/tools: build synchronously and bind."""
    if os.environ.get('ZKSTREAM_NO_NATIVE') == '1':
        return None
    if build_ext() is None:
        return None
    return get_ext()


# -- C load generator (tools/loadgen.c) -------------------------------
#
# A standalone binary, not a shared library: it drives the real wire
# protocol over raw sockets (README "Load generation").  Same
# discipline as the other two artifacts:
# version- and source-hash-named output, atomic tmp+rename publish,
# graceful None when the host has no compiler so `make check`/tier-1
# never hard-fail on a codec-less image.

_LOADGEN_VERSION = 1


def loadgen_source_path() -> str:
    return os.path.join(_root(), 'tools', 'loadgen.c')


_LOADGEN_CC = ['gcc', '-O2', '-pthread']


def loadgen_path() -> str:
    return os.path.join(
        _root(), 'native', 'zkloadgen.v%d.%s'
        % (_LOADGEN_VERSION,
           _digest(loadgen_source_path(), _LOADGEN_CC)))


def build_loadgen() -> str | None:
    """Compile the load generator unless the binary for this source is
    already there; return its path or None.  Synchronous (tools and
    tests only, never the event loop)."""
    src = loadgen_source_path()
    if not os.path.exists(src):
        return None
    return _compile('loadgen', _LOADGEN_CC, src, loadgen_path())


class NativeFrameScanner:
    """ctypes facade over zkwire_frame_scan for one connection.

    ``scan`` reads the caller's accumulation buffer zero-copy (ctypes
    ``from_buffer`` on the bytearray) and returns ``(spans, resid,
    bad_at)``: (start, size) body spans, the cursor after the last
    complete frame, and the offset of an invalid length prefix (or
    None).  The caller must not mutate the bytearray during the call
    (single-threaded asyncio guarantees that here)."""

    __slots__ = ('_lib', '_cap', '_starts', '_sizes')

    def __init__(self, lib: ctypes.CDLL, cap: int = 256):
        self._lib = lib
        self._cap = cap
        self._starts = (ctypes.c_int32 * cap)()
        self._sizes = (ctypes.c_int32 * cap)()

    def scan(self, buf: bytearray, max_packet: int):
        n_total = len(buf)
        if n_total < 4:
            return [], 0, None
        arr = (ctypes.c_uint8 * n_total).from_buffer(buf)
        try:
            addr = ctypes.addressof(arr)
            spans: list[tuple[int, int]] = []
            base = 0
            while True:
                resid = ctypes.c_int32(0)
                n = self._lib.zkwire_frame_scan(
                    addr + base, n_total - base, max_packet, self._cap,
                    self._starts, self._sizes, ctypes.byref(resid))
                if n < 0:
                    bad = base + resid.value
                    return spans, bad, bad
                spans.extend((base + self._starts[i], self._sizes[i])
                             for i in range(n))
                base += resid.value
                if n < self._cap:
                    return spans, base, None
        finally:
            del arr  # release the buffer export before caller mutates
