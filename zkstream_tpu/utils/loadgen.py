"""Driver glue for the C load generator (tools/loadgen.c): the build
(utils/native.py's skip-when-no-compiler discipline) and the command
line.  The generator speaks the wire protocol over raw sockets from
epoll threads, so what it measures is the server and not a Python
client; its flags and exit codes are in README "Load generation".
"""

from __future__ import annotations

from . import native


def available() -> str | None:
    """Build (if needed) and return the binary path, or None when the
    host has no compiler."""
    return native.build_loadgen()


def argv(servers, sessions, *, duration=None, count=None, mix=None,
         pipeline=None, threads=None, ramp=None, idle_ping=None,
         arm_watch=False, fanout_sets=None, setwatches_storm=False,
         path=None, data=None, stdio_sync=False, src_addrs=None,
         session_timeout_ms=None, close_sessions=False,
         ensure_path=True, quiet=True, cached=False,
         cached_write_ms=None) -> list[str] | None:
    """The zkloadgen command line for one run; a keyword left at its
    default leaves the binary's own default (threads: min(cores, 8);
    pipeline: 16; ramp: unpaced; src_addrs: one loopback source
    address per ~20k sessions).  Returns None when the binary can't
    be built."""
    binary = available()
    if binary is None:
        return None
    cmd = [binary,
           '--servers', ','.join('%s:%d' % (h, p) for h, p in servers),
           '--sessions', str(int(sessions))]
    if duration is not None:
        cmd += ['--duration', str(float(duration))]
    if count is not None:
        cmd += ['--count', str(int(count))]
    if mix:
        cmd += ['--mix', mix]
    if pipeline is not None:
        cmd += ['--pipeline', str(int(pipeline))]
    if threads is not None:
        cmd += ['--threads', str(int(threads))]
    if ramp is not None:
        cmd += ['--ramp', str(float(ramp))]
    if idle_ping is not None:
        cmd += ['--idle-ping', str(float(idle_ping))]
    if arm_watch:
        cmd += ['--arm-watch']
    if fanout_sets:
        cmd += ['--fanout-sets', str(int(fanout_sets))]
    if setwatches_storm:
        cmd += ['--setwatches-storm']
    if path:
        cmd += ['--path', path]
    if data is not None:
        cmd += ['--data', str(int(data))]
    if stdio_sync:
        cmd += ['--stdio-sync']
    if src_addrs is not None:
        cmd += ['--src-addrs', str(int(src_addrs))]
    if session_timeout_ms is not None:
        cmd += ['--session-timeout', str(int(session_timeout_ms))]
    if close_sessions:
        cmd += ['--close-sessions']
    if not ensure_path:
        cmd += ['--no-ensure-path']
    if quiet:
        cmd += ['--quiet']
    if cached:
        cmd += ['--cached']
    if cached_write_ms is not None:
        cmd += ['--cached-write-ms', str(float(cached_write_ms))]
    return cmd
