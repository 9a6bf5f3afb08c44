"""Worker process for the two-process multihost FLEET-PROXY test
(run via subprocess by tests/test_multihost.py; not collected).

Each process is one "host" of a 2-host cluster with 4 virtual CPU
devices: it joins jax.distributed, starts its OWN in-process ZK server
and 4 live clients, and serves them through one
``MultihostFleetIngest`` over the GLOBAL 8-device mesh — every tick is
a collective launch whose psum/pmax global stats cross the process
boundary.  Both workers stop at the same coordinated launch count and
print the fleet-global max zxid; the parent asserts the two processes
read back the SAME global value (proof the reduction crossed DCN).
"""

from __future__ import annotations

import asyncio
import os
import sys

STOP_AT = 600          # coordinated collective launch count
CHAOS_STOP_AT = 900    # chaos scenario: extra budget for kill/restart
LOCAL_CLIENTS = 4


async def _chaos_phase(proc_id: int, proxy, srv, clients):
    """The failure-mode phase (scenario='chaos', VERDICT r3 weak #6):

    - host 0 injects 3 host-side assembly failures mid-cadence while
      ops are in flight: each failed tick must still launch its
      collective (empty, aligned), so host 1's matching launches are
      never stranded and the ops complete one interval late;
    - host 0 then KILLS its local ZK server mid-cadence and restarts
      it on the same port with the same database: the cadence keeps
      launching through the outage, sessions resume, and ops complete
      again — while host 1 keeps serving its own fleet undisturbed.

    Both hosts still reach the same coordinated stop count; the parent
    asserts the global pmax matches across processes, and ``stop``'s
    launch/tick invariant (checked in-process) proves no launch was
    skipped.  Returns the restarted server (host 0) or the original.
    """
    from zkstream_tpu.server import ZKServer

    if proc_id != 0:
        # host 1: plain traffic while host 0 misbehaves — its ops must
        # be completely undisturbed by the other host's local failures
        for rnd in range(3):
            for i, c in enumerate(clients):
                data, _stat = await c.get('/p1-%d' % i)
                assert data == b'h1'
            await asyncio.sleep(0.2)
        return srv

    # -- host 0: injected assembly failures --
    fail = {'n': 3}
    orig = proxy._assemble_tick

    def boom():
        if fail['n'] > 0:
            fail['n'] -= 1
            raise RuntimeError('injected assembly failure')
        return orig()
    proxy._assemble_tick = boom
    datas = await asyncio.gather(*[c.get('/p0-%d' % i)
                                   for i, c in enumerate(clients)])
    assert [d for d, _s in datas] == [b'h0'] * LOCAL_CLIENTS
    assert fail['n'] == 0, 'assembly injection never exercised'
    assert proxy.launch_count == proxy.tick_count, (
        'assembly failure skipped a launch: %d launches, %d ticks'
        % (proxy.launch_count, proxy.tick_count))

    # -- host 0: server kill + restart (same port, same database) --
    db, port = srv.db, srv.port
    await srv.stop()
    await asyncio.sleep(0.1)        # several empty ticks while down
    srv = ZKServer(db=db, port=port)
    await srv.start()
    await asyncio.gather(*[c.wait_connected(timeout=30)
                           for c in clients])
    for i, c in enumerate(clients):
        data, _stat = await c.get('/p0-%d' % i)
        assert data == b'h0'        # same db: nodes survived the kill
    return srv


async def run(proc_id: int, scenario: str = 'basic') -> None:
    from zkstream_tpu import Client
    from zkstream_tpu.parallel import MultihostFleetIngest, make_mesh
    from zkstream_tpu.server import ZKServer

    stop_at = CHAOS_STOP_AT if scenario == 'chaos' else STOP_AT
    mesh = make_mesh(dp=8)          # global: 2 hosts x 4 devices
    proxy = MultihostFleetIngest(
        mesh=mesh, local_rows=LOCAL_CLIENTS, stream_len=2048,
        tick_interval=0.01, max_frames=4)
    srv = await ZKServer().start()
    # one aligned warm-up launch per host compiles the program before
    # any session clock runs
    proxy.warmup_tick()
    clients = [Client(address='127.0.0.1', port=srv.port,
                      ingest=proxy, session_timeout=30000)
               for _ in range(LOCAL_CLIENTS)]
    for c in clients:
        c.start()
    proxy.start()
    await asyncio.gather(*[c.wait_connected(timeout=30)
                           for c in clients])
    for i, c in enumerate(clients):
        path = await c.create('/p%d-%d' % (proc_id, i),
                              b'h%d' % proc_id)
        assert path == '/p%d-%d' % (proc_id, i)
    for i, c in enumerate(clients):
        data, stat = await c.get('/p%d-%d' % (proc_id, i))
        assert data == b'h%d' % proc_id and stat.version == 0
    assert proxy.ticks > 0
    if scenario == 'chaos':
        srv = await _chaos_phase(proc_id, proxy, srv, clients)
    local_max = max(c.session.last_zxid for c in clients)
    # let a few more collective ticks run so the global pmax has seen
    # BOTH hosts' final zxids, then stop at the coordinated count
    await asyncio.sleep(0.5)
    assert proxy.tick_count < stop_at, (
        'worker too slow: already past the coordinated stop count '
        '(%d >= %d)' % (proxy.tick_count, stop_at))
    # stop() also enforces launch_count == tick_count — the loud
    # divergence check the chaos scenario exists to exercise
    await proxy.stop(after_ticks=stop_at)
    assert proxy.fleet_max_zxid >= local_max
    g = proxy.global_stats
    assert g is not None
    print('FLEETWORKER_OK %d fleet_max_zxid=%d' %
          (proc_id, proxy.fleet_max_zxid), flush=True)
    await asyncio.gather(*[c.close() for c in clients])
    await srv.stop()


def main() -> int:
    proc_id = int(sys.argv[1])
    num_procs = int(sys.argv[2])
    coord = sys.argv[3]
    scenario = sys.argv[4] if len(sys.argv) > 4 else 'basic'

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from zkstream_tpu.utils.platform import force_cpu

    force_cpu(n_devices=4)

    from zkstream_tpu.parallel.multihost import initialize

    initialize(coordinator_address=coord, num_processes=num_procs,
               process_id=proc_id)
    asyncio.run(run(proc_id, scenario))
    return 0


if __name__ == '__main__':
    sys.exit(main())
