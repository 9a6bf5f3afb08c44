"""The ensemble under test as OS processes: spawn, scrape, kill.

Each member is ``zkstream_tpu/server/member_worker.py`` started in a
process group of its own, with the parent-death signal set, so that no
exit path of the harness — a return, an exception, SIGTERM, even a
SIGKILL of the harness itself — leaves one behind.  The members never
import JAX and are spawned before the harness's first JAX call: a chip
belongs to one process.
"""

from __future__ import annotations

import asyncio
import ctypes
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

#: every run directory is ``<this side's TMPDIR>/zkbench-*``
RUN_PREFIX = 'zkbench-'

READY_S = 60.0
LEADER_S = 60.0

_PR_SET_PDEATHSIG = 1


def _die_with_parent() -> None:
    """In the child, before exec: a group of its own, and SIGKILL when
    the harness goes away however it goes."""
    os.setsid()
    ctypes.CDLL(None, use_errno=True).prctl(
        _PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0)


def free_ports(n: int) -> list[int]:
    """``n`` distinct ports the OS chose (bind to 0, read, close): the
    members must know each other's election ports before any exists."""
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(('127.0.0.1', 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def worker_path(root: str) -> str:
    return os.path.join(root, 'zkstream_tpu', 'server', 'member_worker.py')


def leftovers(root: str) -> list[int]:
    """Pids of members an earlier run ON THIS GROUND left alive: the
    command line names THIS checkout's member worker and a run
    directory under THIS side's temporary directory.  A member of any
    other checkout or ``TMPDIR`` (the driver runs parent and change on
    one machine) is not ours, is not looked at and is never signalled.
    ``setsid`` + ``PDEATHSIG`` + ``killpg`` leave none; this is the
    start-up check that it is so."""
    worker = worker_path(root).encode()
    ground = os.path.join(tempfile.gettempdir(), RUN_PREFIX).encode()
    found = []
    for pid in os.listdir('/proc'):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open('/proc/%s/cmdline' % (pid,), 'rb') as f:
                argv = f.read().split(b'\0')
        except OSError:
            continue
        if worker in argv and any(a.startswith(ground) for a in argv):
            found.append(int(pid))
    return found


def kill_leftovers(root: str) -> list[int]:
    pids = leftovers(root)
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + 5.0
    while pids and leftovers(root) and time.monotonic() < deadline:
        time.sleep(0.05)
    return pids


class Ensemble:
    """``voters`` member processes over WAL directories under
    ``run_dir``; ``env`` is added to the members' environment only
    (the degraded controls set the program's kill switches here)."""

    def __init__(self, root: str, run_dir: str, voters: int,
                 env: dict | None = None):
        self.worker = worker_path(root)
        self.run_dir = run_dir
        self.voters = voters
        self.env = dict(os.environ, **(env or {}))
        ports = free_ports(2 * voters)
        self.client_ports = ports[:voters]
        self.election_ports = ports[voters:]
        self.procs: list[subprocess.Popen] = []
        self.leader: int | None = None

    @property
    def addrs(self) -> list[tuple[str, int]]:
        return [('127.0.0.1', p) for p in self.client_ports]

    def spawn(self) -> None:
        for i in range(self.voters):
            wal = os.path.join(self.run_dir, 'm%d' % (i,))
            os.makedirs(wal, exist_ok=True)
            args = [sys.executable, self.worker, str(i), wal,
                    str(self.client_ports[i]),
                    str(self.election_ports[i])]
            args += ['%d:127.0.0.1:%d' % (j, self.election_ports[j])
                     for j in range(self.voters) if j != i]
            self.procs.append(subprocess.Popen(
                args, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=self.env, preexec_fn=_die_with_parent))

    async def wait_ready(self) -> None:
        loop = asyncio.get_running_loop()
        for i, p in enumerate(self.procs):
            line = await asyncio.wait_for(
                loop.run_in_executor(None, p.stdout.readline), READY_S)
            if not line.startswith('READY '):
                raise RuntimeError('member %d did not start: %r (exit %s)'
                                   % (i, line, p.poll()))

    async def find_leader(self) -> int:
        deadline = time.monotonic() + LEADER_S
        while time.monotonic() < deadline:
            for i in range(self.voters):
                try:
                    rows = await self.mntr(i)
                except (OSError, asyncio.TimeoutError, TimeoutError):
                    continue
                if rows.get('zk_member_role') == 'leader':
                    self.leader = i
                    return i
            await asyncio.sleep(0.1)
        raise RuntimeError('no member became leader within %.0f s'
                           % (LEADER_S,))

    async def admin(self, member: int, word: str,
                    timeout: float = 5.0) -> str:
        """One four-letter word over raw TCP."""
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection('127.0.0.1',
                                    self.client_ports[member]), timeout)
        try:
            writer.write(word.encode())
            await writer.drain()
            data = await asyncio.wait_for(reader.read(), timeout)
        finally:
            writer.close()
        return data.decode('utf-8', 'replace')

    async def mntr(self, member: int) -> dict:
        out = {}
        for line in (await self.admin(member, 'mntr')).splitlines():
            if '\t' in line:
                k, v = line.split('\t', 1)
                out[k] = v
        return out

    async def mntr_all(self) -> list[dict]:
        """Every member's rows; a member that does not answer is asked
        once more and then gives an empty dict (a per-layer reader then
        finds nothing to read, a guarantee check nothing to hold)."""
        rows = []
        for i in range(self.voters):
            for _attempt in range(2):
                try:
                    rows.append(await self.mntr(i))
                    break
                except (OSError, asyncio.TimeoutError, TimeoutError):
                    pass
            else:
                rows.append({})
        return rows

    def signal_others(self, member: int, sig: int) -> None:
        """``sig`` (SIGSTOP / SIGCONT) to every member but ``member``."""
        for i, p in enumerate(self.procs):
            if i != member and p.poll() is None:
                os.kill(p.pid, sig)

    def all_alive(self) -> bool:
        return bool(self.procs) and all(p.poll() is None
                                        for p in self.procs)

    def kill(self) -> None:
        """SIGKILL every member's group and wait for each to end."""
        for p in self.procs:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
            if p.stdout is not None:
                p.stdout.close()
        self.procs = []
