"""The path a LARGE write takes (``setData`` of 1 KiB .. 960 KiB, the
top just under ``jute.maxbuffer``), each step against a plain dictionary
model path -> (version, bytes):

- a 3-member in-process ensemble takes the writes through a follower
  and through the leader: versions, and the bytes read back from every
  member after ``sync``;
- the WAL replays them after a restart across several segment rolls,
  and says what it appended (``zk_wal_appended_bytes``, tick phases
  ``wal_append`` / ``wal_roll``);
- a forward batch over ``FORWARD_BATCH_BYTES`` is split and answered in
  order, and the leader says what it pushed (``repl_pushed_bytes``,
  tick phase ``repl_push``);
- a client request the kernel takes only part of is delivered intact,
  and the client's transport tier counts the partial flush.
"""

from __future__ import annotations

import asyncio
import random
import socket

import pytest

from helpers import mntr_rows
from test_replication import _rpc, _set, repl  # noqa: F401
from zkstream_tpu import Client
from zkstream_tpu.io import transport as transport_mod
from zkstream_tpu.protocol.consts import CreateFlag
from zkstream_tpu.protocol.records import OPEN_ACL_UNSAFE
from zkstream_tpu.server import ZKEnsemble, ZKServer
from zkstream_tpu.server import persist, replication
from zkstream_tpu.utils.metrics import TickLedger

SIZES = (1 << 10, 16 << 10, 256 << 10, 960 << 10)
PATHS = ['/doc%d' % (i,) for i in range(len(SIZES))]


def payload(seed: int, path: str, version: int, size: int) -> bytes:
    return random.Random('%d%s/%d' % (seed, path, version)).randbytes(size)


async def _client(port: int) -> Client:
    c = Client(address='127.0.0.1', port=port, session_timeout=30000)
    c.start()
    await c.wait_connected(timeout=10)
    return c


async def _read_all(ens, model) -> None:
    """After ``sync``, every member serves the model's bytes."""
    for srv in ens.servers:
        c = await _client(srv.port)
        try:
            for path, (version, data) in model.items():
                await c.sync(path)
                got, stat = await c.get(path)
                assert stat.version == version, (srv.port, path)
                assert stat.dataLength == len(got) == len(data)
                assert got == data, (srv.port, path)
        finally:
            await c.close()


def _phase_ms(rows: dict, phase: str) -> float:
    return float(rows.get('zk_tick_phase_ms_sum{phase="%s"}' % (phase,), 0))


@pytest.mark.timeout(120)
@pytest.mark.parametrize('seed', [5, 2 ** 31 + 5])
async def test_large_writes_through_follower_and_leader_and_replay(
        seed, tmp_path):
    wal_dir = str(tmp_path / 'wal')
    ens = await ZKEnsemble(3, wal_dir=wal_dir).start()
    model: dict = {}
    try:
        leader = await _client(ens.servers[0].port)
        follower = await _client(ens.servers[1].port)
        for path, size in zip(PATHS, SIZES):
            data = payload(seed, path, 0, size)
            await leader.create(path, data)
            model[path] = (0, data)
        # five rounds of rewrites, the writer alternating: ~6 MB a
        # round through a 4 MiB segment bound rolls several times
        for rnd in range(1, 6):
            for path, size in zip(PATHS, SIZES):
                data = payload(seed, path, rnd, size + 160 * rnd)
                c = follower if rnd % 2 else leader
                stat = await c.set(path, data)
                assert stat.version == rnd
                model[path] = (rnd, data)
        await leader.close()
        await follower.close()
        await _read_all(ens, model)
        wal = ens.db.wal
        written = sum(size + 160 * r for size in SIZES
                      for r in range(6))
        assert wal.appended_bytes > written
        assert wal.snapshots_taken + 1 >= wal.appended_bytes // (4 << 20)
        assert len(wal._closed_segments) + wal.snapshots_taken >= 1
        rows = await mntr_rows(ens.servers[0].port)
        assert int(rows['zk_wal_appended_bytes']) == wal.appended_bytes
        assert int(rows['zk_wal_snapshots']) == wal.snapshots_taken
        assert _phase_ms(rows, 'wal_append') > 0
        assert _phase_ms(rows, 'wal_roll') > 0
        # the new phases nest: a member's phases never sum past its
        # ticks' wall time
        total = sum(float(v) for k, v in rows.items()
                    if k.startswith('zk_tick_phase_ms_sum{'))
        assert total <= float(rows['zk_tick_ms_sum']) * 1.001 + 1.0
    finally:
        await ens.stop()
    # a fresh ensemble over the same directory: the same tree
    ens = await ZKEnsemble(3, wal_dir=wal_dir).start()
    try:
        await _read_all(ens, model)
    finally:
        await ens.stop()


def test_wal_record_bytes_are_what_the_spec_tier_writes(tmp_path):
    """The record of a large ``set_data`` / ``create`` — header, then
    body, no copy behind the header — is byte for byte the spec
    tier's, and its CRC the Python table walk's."""
    data = random.Random(9).randbytes(300 * 1024)
    acl = OPEN_ACL_UNSAFE
    entries = [('set_data', '/a', data, 7, 11), ('set_data', '/a', b'', 8, 12),
               ('create', '/b', data, acl, 0, 9, 13),
               ('create', '/c', b'', acl, 5, 10, 14)]
    for e in entries:
        assert persist.encode_entry(e) == persist._spec_encode_entry(e)
        assert persist.decode_entry(persist.encode_entry(e)) == e
    wal = persist.WriteAheadLog(str(tmp_path), sync='never')
    led = wal.ledger = TickLedger()
    for e in entries:
        wal.append(e)
    wal.close()
    want = sum(8 + len(persist.encode_entry(e)) for e in entries)
    assert wal.appended_bytes == want
    assert led._acc.get('wal_append', 0) > 0 and not led._stack
    scan = persist.scan_dir(str(tmp_path))
    assert [e for seg in scan.segments for _i, e in seg.records] == entries
    assert all(seg.status == "ok" for seg in scan.segments)
    body = persist.encode_entry(entries[0])
    assert persist.crc32c(body) == persist.software_crc32c(body)
    assert persist.crc32c(b'123456789') == 0xE3069283


def test_crc_binding_is_not_latched_on_the_python_walk(monkeypatch):
    """A member whose first append came before the extension's
    background build landed takes the C walk from the first append
    after it."""
    from zkstream_tpu.utils import native

    if native.ensure_ext() is None:
        pytest.skip('no C extension here (no compiler)')
    ext = native.get_ext()
    monkeypatch.setattr(persist, '_crc_impl', None)
    monkeypatch.setattr(native, 'get_ext', lambda: None)
    assert persist.crc32c(b'123456789') == 0xE3069283
    assert persist._crc_impl is None            # nothing latched
    monkeypatch.setattr(native, 'get_ext', lambda: ext)
    assert persist.crc32c(b'123456789') == 0xE3069283
    assert persist._crc_impl is ext.crc32c


@pytest.mark.timeout(120)
async def test_forward_batch_over_the_byte_cap_is_split_in_order(repl):  # noqa: F811
    """Six 960 KiB writes of one turn at the real
    ``FORWARD_BATCH_BYTES``: four in the first RPC, two in the second,
    every one answered in order; the leader books its pushes."""
    db, svc, connect = repl
    led = db.ledger = TickLedger()
    remote = await connect()
    db.create('/big', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    size = 960 << 10
    assert 4 * size <= replication.FORWARD_BATCH_BYTES < 5 * size
    bodies = [payload(3, '/big', v, size) for v in range(1, 7)]
    rpcs = remote.forward_rpcs
    results = await _rpc(remote.forward,
                         [_set('/big', b) for b in bodies])
    assert [s for s, _ in results] == ['ok'] * 6
    assert [p.version for _, p in results] == [1, 2, 3, 4, 5, 6]
    assert remote.forward_rpcs - rpcs == 2
    assert db.nodes['/big'].data == bodies[-1]
    # the mirror was sent every commit, once
    await asyncio.sleep(0.05)
    assert db.repl_pushed_bytes > 6 * size
    assert led._acc.get('repl_push', 0) > 0 or led.ticks > 0


@pytest.mark.timeout(120)
@pytest.mark.parametrize('backend', ['mmsg', 'uring'])
async def test_partial_client_write_delivers_the_request_intact(backend):
    """A 900 KB ``setData`` through a socket whose send buffer holds a
    fraction of it: the raw write is partial, the remainder goes
    through the asyncio transport, the server decodes the request
    intact — and the tier counts the partial flush and its bytes."""
    if not transport_mod.probe().available(backend):
        pytest.skip('no %s here' % (backend,))
    srv = await ZKServer().start()
    c = Client(address='127.0.0.1', port=srv.port, session_timeout=30000,
               transport=backend, max_spares=0)
    c.start()
    try:
        await c.wait_connected(timeout=10)
        sock = c.current_connection().transport.get_extra_info('socket')
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
        await c.create('/p', b'')
        model = {}
        for v in range(1, 4):
            data = payload(1, '/p', v, 900_000 + v)
            stat = await c.set('/p', data)
            model['/p'] = (stat.version, data)
            assert stat.version == v
            got, stat = await c.get('/p')
            assert got == data and stat.dataLength == len(data)
        tier = c.transport_tier
        assert tier.backend == backend
        assert tier.partial_flushes >= 1
        assert 0 < tier.requeued_bytes < 3 * 900_100
        assert tier.flushes >= tier.partial_flushes
        text = c.collector.expose()
        assert 'zookeeper_flush_partial_total{plane="client"}' in text
        assert ('zookeeper_flush_partial_requeued_bytes{plane="client"}'
                in text)
    finally:
        await c.close()
        await srv.stop()


def test_allocator_keeps_freed_memory_once_a_process():
    """A fleet's process and a member keep large freed blocks for the
    next burst (utils/alloc.py): glibc takes the three thresholds, the
    call is idempotent, and a ``FleetIngest`` makes it."""
    from zkstream_tpu.io.ingest import FleetIngest
    from zkstream_tpu.utils import alloc

    ingest = FleetIngest(placement='host', min_len=256)
    ingest.close()
    assert alloc._done is not None          # the ingest asked
    first = alloc.keep_freed_memory()
    assert alloc.keep_freed_memory() is first
    import platform
    if platform.libc_ver()[0] == 'glibc':
        assert first is True
