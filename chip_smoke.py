#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

The quickest proof that the system still starts on the accelerator: one
command, one process that owns the chip, exit 0 only if every phase
passed.  It drives the main path through the entry points a user would
call —

- a 3-voter ensemble of OS processes (server/election.py ``ProcMember``
  -> server/member_worker.py), WAL on (``ZKSTREAM_MEMBER_SYNC=tick``),
  quorum-commit on, every plane at its default; the members never
  import JAX and are spawned before this process's first JAX call;
- 8,192 znodes x 1,024 B (Hunt et al., ATC '10 section 5.1: 1 KiB reads
  and writes) under 32 parents of 256 children, from ``--seed``;
- 1,024 ``Client`` sessions in this process sharing one ``FleetIngest``
  forced onto the device (``bypass_bytes=0``, accelerator placement,
  no background warm), >= 64 ops per session in lock-step rounds
  plus one watched ``set`` that must deliver exactly one notification
  per session

— and checks what comes out by the repo's own means: the observation
lists equal those of the same seeded script through plain ``Client``s
on the jute spec tier (no ingest, ``use_native_codec=False``); every
acknowledged write is read back, after a ``sync``, from a different
member than took it; every tick ran the device program (no scalar,
warming or fragmentation-guard tick, no failed bucket, every executable
on the accelerator); the Pallas kernel, compiled by Mosaic, matches the
jnp pipeline bit for bit; and the C load generator, built from source,
runs clean against the same ensemble.

No accelerator, no run: unless ``--cpu-dry-run`` is given (toy sizes,
kernels in the Pallas interpreter, stamped ``"chip": false``; never
chosen automatically) the script exits non-zero, printing no result,
when the default JAX backend is not a TPU.  It neither sets nor trusts
``JAX_PLATFORMS``.

The last line of stdout is one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
— the device as JAX reports it.  The line before it is the full report
(one JSON object: every phase's counts, ``reduced``, ``"claim": null``).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import random
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the deployment as asked (ISSUE 21); ``reduced`` in the output lists
#: every key a run cut below these
FULL = {
    'voters': 3,
    'sessions': 1024,
    'parents': 32,
    'children': 256,
    'payload': 1024,
    'cycles': 4,                 # x 16 ops = 64 ops per session
    'max_frames': 8,
    'min_len': 4096,             # one length bucket carries every reply
    'loadgen_s': 2.0,
    # (streams, row bytes, frames) per kernel check
    'scan_pocket': (8192, 6144, 64),
    'scan_single': (64, 8192, 64),
    'tick_pocket': (4096, 4096, 32),        # Bp, L, max_frames
}

#: ``--cpu-dry-run``: the same phases at sizes the CPU backend and the
#: Pallas interpreter finish in seconds
TOY = {
    'voters': 3,
    'sessions': 12,
    'parents': 2,
    'children': 20,
    'payload': 64,
    'cycles': 1,
    'max_frames': 4,
    'min_len': 1024,
    'loadgen_s': 0.5,
    'scan_pocket': (16, 512, 8),
    'scan_single': (8, 512, 8),
    'tick_pocket': (8, 512, 8),
}

OP_TIMEOUT_MS = 180_000      # an inline bucket compile blocks the loop
SESSION_TIMEOUT_MS = 120_000


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------
# the seeded deployment
# ---------------------------------------------------------------------

def znode(root: str, i: int, cfg: dict) -> str:
    return '%s/p%02d/c%03d' % (root, i // cfg['children'],
                               i % cfg['children'])


def payload(seed: int, tag: str, n: int) -> bytes:
    return random.Random('%d/%s' % (seed, tag)).randbytes(n)


def owned(s: int, cfg: dict) -> int:
    """Session ``s`` owns (is the only writer of) this znode index;
    the stride spreads the owners over every parent."""
    total = cfg['parents'] * cfg['children']
    return s * (total // cfg['sessions'])


def unowned(s: int, k: int, seed: int, cfg: dict) -> int:
    """A znode no session ever writes — what a session may read through
    ANY member at any time and observe the same bytes (a read of
    another session's live writes is only as fresh as the serving
    member, by ZooKeeper's own semantics)."""
    total = cfg['parents'] * cfg['children']
    stride = total // cfg['sessions']
    rng = random.Random('%d/read/%d/%d' % (seed, s, k))
    i = rng.randrange(total)
    if i % stride == 0 and i // stride < cfg['sessions']:
        i += 1                  # owned: its neighbour never is
    return i


def _stat_obs(stat):
    """Stat fields that are the same across two runs of one script
    (times are wall-clock, zxids depend on interleaving) — the sense of
    tests/test_ingest.py."""
    return (stat.version, stat.cversion, stat.dataLength,
            stat.numChildren, stat.ephemeralOwner == 0)


async def load_tree(addr, root: str, seed: int, cfg: dict) -> float:
    """Create the arm's tree through one plain client; returns the
    seconds it took.  Every create here is an acknowledged write the
    readback phase reads back from the other members."""
    from zkstream_tpu import Client

    c = Client(servers=[addr], shuffle_backends=False,
               session_timeout=SESSION_TIMEOUT_MS,
               op_timeout=OP_TIMEOUT_MS)
    c.start()
    t0 = time.perf_counter()
    try:
        await c.wait_connected(timeout=30)
        await c.create(root, b'')
        await c.create(root + '/watched', b'armed')
        await asyncio.gather(*[
            c.create('%s/p%02d' % (root, p), b'')
            for p in range(cfg['parents'])])
        total = cfg['parents'] * cfg['children']
        for lo in range(0, total, 512):
            await asyncio.gather(*[
                c.create(znode(root, i, cfg),
                         payload(seed, 'z%d' % i, cfg['payload']))
                for i in range(lo, min(total, lo + 512))])
    finally:
        await c.close()
    return time.perf_counter() - t0


async def wait_tree_everywhere(addrs, root: str, cfg: dict) -> None:
    """Hold the arm until every member serves the whole tree: a session
    attached to a follower must not start against half a load."""
    from zkstream_tpu import Client

    last = znode(root, cfg['parents'] * cfg['children'] - 1, cfg)
    for addr in addrs:
        c = Client(servers=[addr], shuffle_backends=False,
                   session_timeout=SESSION_TIMEOUT_MS)
        c.start()
        try:
            await c.wait_connected(timeout=30)
            await c.sync(root)
            await c.stat(last)
        finally:
            await c.close()


# ---------------------------------------------------------------------
# one arm: the seeded script through ``sessions`` live clients
# ---------------------------------------------------------------------

async def run_arm(arm: str, addrs, root: str, seed: int, cfg: dict,
                  ingest=None) -> dict:
    """Run the script; returns the per-session observation lists, the
    writes each session had acknowledged, and the counts the device
    assertions need."""
    from zkstream_tpu import Client, CreateFlag, ZKError

    n = cfg['sessions']
    strip = len(root)
    native = None if ingest is not None else False
    clients = [Client(servers=[addrs[s % len(addrs)]],
                      shuffle_backends=False, ingest=ingest,
                      use_native_codec=native,
                      session_timeout=SESSION_TIMEOUT_MS,
                      op_timeout=OP_TIMEOUT_MS)
               for s in range(n)]
    for c in clients:
        c.start()
    obs: list[list] = [[] for _ in range(n)]
    acked = [{'sets': 0, 'data': None, 'kept': None, 'deleted': []}
             for _ in range(n)]
    counts = {'ops': 0, 'notifications': 0}
    loop_errors: list = []
    loop = asyncio.get_running_loop()
    prev_handler = loop.get_exception_handler()
    # a tick that raises (force-device refusal) surfaces here, not in
    # an awaiting op: collect it so the round fails at once
    loop.set_exception_handler(
        lambda _l, ctx: loop_errors.append(
            ctx.get('exception') or ctx.get('message')))
    t0 = time.perf_counter()
    try:
        await asyncio.gather(*[c.wait_connected(timeout=60)
                               for c in clients])
        if ingest is not None:
            for c in clients:
                check(c.current_connection().ingest is ingest,
                      '%s: a session is not draining through the '
                      'ingest' % (arm,))

        async def round_(name, fn):
            got = await asyncio.gather(*[fn(s, clients[s])
                                         for s in range(n)])
            check(not loop_errors, '%s: event loop error during round '
                  '%s: %r' % (arm, name, loop_errors[:1]))
            for s, o in enumerate(got):
                obs[s].append((name, o))
            counts['ops'] += n

        own = [znode(root, owned(s, cfg), cfg) for s in range(n)]
        parent = [p.rsplit('/', 1)[0] for p in own]
        eph: list = [None] * n

        async def get_path(c, path):
            data, stat = await c.get(path)
            return (data, _stat_obs(stat))

        for cyc in range(cfg['cycles']):
            k = [cyc * 4]

            async def r_get_own(s, c):
                return await get_path(c, own[s])

            def r_get_other(j):
                async def fn(s, c):
                    return await get_path(c, znode(
                        root, unowned(s, k[0] + j, seed, cfg), cfg))
                return fn

            async def r_exists_other(s, c):
                return _stat_obs(await c.stat(znode(
                    root, unowned(s, k[0] + 3, seed, cfg), cfg)))

            def r_set(tag):
                async def fn(s, c):
                    data = payload(seed, 'w%d/%d/%s' % (s, cyc, tag),
                                   cfg['payload'])
                    stat = await c.set(own[s], data)
                    acked[s]['sets'] += 1
                    acked[s]['data'] = data
                    return _stat_obs(stat)
                return fn

            async def r_create_seq(s, c):
                path = await c.create(
                    own[s] + '/e-', b'e%d' % cyc,
                    flags=CreateFlag.SEQUENTIAL | CreateFlag.EPHEMERAL)
                eph[s] = path
                return path[strip:]

            async def r_list_own(s, c):
                children, stat = await c.list(own[s])
                return (sorted(children), _stat_obs(stat))

            async def r_list_parent(s, c):
                children, stat = await c.list(parent[s])
                return (sorted(children), _stat_obs(stat))

            async def r_acl(s, c):
                return tuple(await c.get_acl(own[s]))

            async def r_delete(s, c):
                if cyc == cfg['cycles'] - 1:
                    # the last one stays, for the readback to find
                    acked[s]['kept'] = eph[s]
                    return ('kept', _stat_obs(await c.stat(eph[s])))
                await c.delete(eph[s], -1)
                acked[s]['deleted'].append(eph[s])
                return 'deleted'

            async def r_missing(s, c):
                try:
                    await c.get(own[s] + '/missing')
                except ZKError as e:
                    return e.code
                return 'no error'

            async def r_exists_own(s, c):
                return _stat_obs(await c.stat(own[s]))

            for name, fn in (
                    ('get-own', r_get_own),
                    ('get-other', r_get_other(0)),
                    ('exists-other', r_exists_other),
                    ('set', r_set('a')),
                    ('get-after-set', r_get_own),
                    ('create-seq', r_create_seq),
                    ('list-own', r_list_own),
                    ('list-parent', r_list_parent),
                    ('acl', r_acl),
                    ('delete', r_delete),
                    ('get-missing', r_missing),
                    ('exists-own', r_exists_own),
                    ('get-other-2', r_get_other(1)),
                    ('get-other-3', r_get_other(2)),
                    ('set-2', r_set('b')),
                    ('get-after-set-2', r_get_own)):
                await round_('%d/%s' % (cyc, name), fn)
        # -- the watched set: every session arms a data watch on one
        # znode, one session writes it, each session is notified once
        events: list[list] = [[] for _ in range(n)]
        watched = root + '/watched'
        for s, c in enumerate(clients):
            c.watcher(watched).on(
                'dataChanged',
                lambda data, *_a, _s=s: events[_s].append(bytes(data)))

        async def until(cond, what, timeout=120.0):
            deadline = time.monotonic() + timeout
            while not cond():
                check(not loop_errors, '%s: event loop error: %r'
                      % (arm, loop_errors[:1]))
                check(time.monotonic() < deadline,
                      '%s: timed out waiting for %s' % (arm, what))
                await asyncio.sleep(0.05)

        # arming a data watch on an existing znode emits once
        await until(lambda: all(len(e) >= 1 for e in events),
                    'every watch to arm')
        await asyncio.sleep(0.5)             # every re-arm has landed
        fired = payload(seed, 'fire', 32)
        await clients[0].set(watched, fired)
        await until(lambda: all(len(e) >= 2 for e in events),
                    'every session to be notified')
        await asyncio.sleep(0.5)             # and nothing fires twice
        counts['notifications'] = sum(
            1 for e in events for x in e[1:] if x == fired)
        for s in range(n):
            obs[s].append(('watch', tuple(events[s])))
        check(counts['notifications'] == n,
              '%s: the watched set delivered %d notifications, not %d'
              % (arm, counts['notifications'], n))
        check(all(len(e) == 2 for e in events),
              '%s: a session saw a watch fire more than once' % (arm,))
        session_ids = [c.session.session_id for c in clients]
        member_of = [s % len(addrs) for s in range(n)]
        secs = time.perf_counter() - t0

        # -- read every acknowledged write back while the sessions (and
        # so their ephemerals) live, each from ANOTHER member than the
        # one that took it, after a sync
        await read_back(arm, addrs, root, seed, cfg, own, acked,
                        session_ids, member_of, fired)
    finally:
        loop.set_exception_handler(prev_handler)
        await asyncio.gather(*[c.close() for c in clients],
                             return_exceptions=True)
    return {'obs': obs, 'counts': counts, 'secs': secs}


async def read_back(arm, addrs, root, seed, cfg, own, acked,
                    session_ids, member_of, fired) -> None:
    """The guarantee quorum-commit + WAL state: a write that was
    acknowledged is there on the other members too."""
    from zkstream_tpu import Client, ZKError

    m = len(addrs)
    readers = [Client(servers=[a], shuffle_backends=False,
                      session_timeout=SESSION_TIMEOUT_MS,
                      op_timeout=OP_TIMEOUT_MS) for a in addrs]
    for c in readers:
        c.start()
    try:
        await asyncio.gather(*[c.wait_connected(timeout=30)
                               for c in readers])
        await asyncio.gather(*[c.sync(root) for c in readers])

        async def one(s):
            c = readers[(member_of[s] + 1) % m]
            data, stat = await c.get(own[s])
            check(data == acked[s]['data'], '%s: session %d acked set '
                  'did not read back' % (arm, s))
            check(stat.version == acked[s]['sets'],
                  '%s: session %d version %d after %d acked sets'
                  % (arm, s, stat.version, acked[s]['sets']))
            kept = await c.stat(acked[s]['kept'])
            check(kept.ephemeralOwner == session_ids[s],
                  '%s: session %d ephemeral has the wrong owner'
                  % (arm, s))
            for path in acked[s]['deleted']:
                try:
                    await c.stat(path)
                except ZKError as e:
                    check(e.code == 'NO_NODE', '%s: %s' % (arm, e))
                else:
                    raise SmokeFailure('%s: acked delete of %s did not '
                                       'hold' % (arm, path))
        await asyncio.gather(*[one(s) for s in range(len(own))])

        # the load went through member 0: read it from the others
        # (owned znodes were overwritten since; checked above)
        written = {own[s] for s in range(len(own))}
        total = cfg['parents'] * cfg['children']

        async def loaded(i):
            path = znode(root, i, cfg)
            if path in written:
                return
            data, _stat = await readers[1 + i % (m - 1)].get(path)
            check(data == payload(seed, 'z%d' % i, cfg['payload']),
                  '%s: loaded znode %s did not read back' % (arm, path))
        for lo in range(0, total, 1024):
            await asyncio.gather(*[
                loaded(i) for i in range(lo, min(total, lo + 1024))])
        data, _stat = await readers[1].get(root + '/watched')
        check(data == fired, '%s: the watched set did not read back'
              % (arm,))
    finally:
        await asyncio.gather(*[c.close() for c in readers],
                             return_exceptions=True)


# ---------------------------------------------------------------------
# the device arm
# ---------------------------------------------------------------------

async def device_arm(addrs, root, seed, cfg, on_chip: bool,
                     reference: dict) -> dict:
    from zkstream_tpu.io.ingest import FleetIngest

    arm = 'ingest'      # the name its failures carry
    ingest = FleetIngest(
        max_frames=cfg['max_frames'], min_len=cfg['min_len'],
        placement='accelerator' if on_chip else 'host',
        bypass_bytes=0, warm='block')
    # every batch bucket a fleet of this size can produce, at the one
    # length bucket the script's replies fit: compiles are set-up time
    t0 = time.perf_counter()
    bp = 8
    while True:
        await ingest.prewarm(bp)
        if bp >= cfg['sessions']:
            break
        bp *= 2
    prewarm_s = time.perf_counter() - t0
    try:
        got = await run_arm(arm, addrs, root, seed, cfg, ingest=ingest)
    finally:
        ingest.close()

    check(got['obs'] == reference['obs'],
          '%s: observations differ from the jute-tier reference '
          '(first differing session: %s)' % (arm, next(
              (s for s, (a, b) in enumerate(zip(
                  got['obs'], reference['obs'])) if a != b), '?')))
    check(ingest.ticks > 0, '%s: no device tick ran' % (arm,))
    for name in ('ticks_scalar', 'ticks_warming', 'ticks_frag'):
        check(getattr(ingest, name) == 0, '%s: %s = %d, every tick '
              'must run the device program'
              % (arm, name, getattr(ingest, name)))
    failed = {k: b['error'] for k, b in ingest.buckets.items()
              if b['error']}
    check(not failed, '%s: buckets failed to compile: %r'
          % (arm, failed))
    want = 'tpu' if on_chip else 'cpu'
    wrong = {k: b['platform'] for k, b in ingest.buckets.items()
             if b['platform'] != want}
    check(not wrong, '%s: executables not on %s: %r'
          % (arm, want, wrong))
    check(ingest.placed['platform'] == want,
          '%s: ticks placed on %r' % (arm, ingest.placed))
    return {
        'sessions': cfg['sessions'],
        'ops': got['counts']['ops'],
        'notifications': got['counts']['notifications'],
        'ticks': ingest.ticks,
        'ticks_scalar': ingest.ticks_scalar,
        'ticks_warming': ingest.ticks_warming,
        'ticks_frag': ingest.ticks_frag,
        'frames': ingest.frames_routed,
        'buckets': len(ingest.buckets),
        'failed_buckets': 0,
        'impls': sorted({b['impl'] for b in ingest.buckets.values()}),
        'compile_s': round(sum(b['compile_s']
                               for b in ingest.buckets.values()), 3),
        'prewarm_s': round(prewarm_s, 3),
        'placed': ingest.placed,
        'script_s': round(got['secs'], 3),
        'equal_to_reference': True,
        'acked_writes_read_back_from_another_member': True,
    }


# ---------------------------------------------------------------------
# the kernels, called directly: a guard refusal raises
# ---------------------------------------------------------------------

def _same(tag: str, want, got) -> None:
    """Bit-for-bit equality of two NamedTuples of arrays."""
    import numpy as np

    for f in want._fields:
        a, b = getattr(want, f), getattr(got, f)
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              '%s: field %s differs between the kernel and jnp'
              % (tag, f))


# -- the kernels' corpus: mixed-opcode reply streams.  Every stream
# carries the same (opcode, width) sequence at the same byte offsets
# and random contents, so the builder stays vectorized --
_FRAMES = 64                 # frames per stream
_DATA_LEN = 256              # GET_DATA payload bytes
_CH2_N, _CH2_NAME = 8, 12    # GET_CHILDREN2: children x name bytes
_CH_N, _CH_NAME = 6, 10      # GET_CHILDREN (no Stat)
_ACL_N, _ACL_SCHEME, _ACL_ID = 2, 6, 24
_NOTIF_PATH = 20

#: Per-16-frame opcode pattern: GET_DATA-dominant, with children and
#: ACL lists, watch notifications, error and ping replies interleaved
#: so every plane of the decode carries live traffic.
_SLOT_PATTERN = (
    'data', 'data', 'children2', 'data', 'notif', 'data', 'acl',
    'data', 'data', 'children', 'data_err', 'data', 'data',
    'children2', 'ping', 'data')

_BODY_LEN = {
    'data': 16 + 4 + _DATA_LEN + 68,
    'data_err': 16,                       # error reply: header only
    'children2': 16 + 4 + _CH2_N * (4 + _CH2_NAME) + 68,
    'children': 16 + 4 + _CH_N * (4 + _CH_NAME),
    'acl': 16 + 4 + _ACL_N * (4 + 4 + _ACL_SCHEME + 4 + _ACL_ID) + 68,
    'notif': 16 + 4 + 4 + 4 + _NOTIF_PATH,
    'ping': 16,
}


def kernel_corpus(cfg: dict):
    """uint8 [rows, L]: framed streams of valid mixed-opcode replies —
    reply headers then per-opcode bodies (reference layouts:
    lib/zk-buffer.js:275-370,428-442) — from a fixed seed; each check
    cuts them to the row length its kernel program fits."""
    import numpy as np

    B = max(cfg['scan_pocket'][0], cfg['tick_pocket'][0])
    kinds = _SLOT_PATTERN * (_FRAMES // len(_SLOT_PATTERN))
    rng = np.random.RandomState(42)
    v = np.zeros((B, sum(4 + _BODY_LEN[k] for k in kinds)), np.uint8)

    def be(field, width, out):
        shifts = np.arange(8 * (width - 1), -1, -8, dtype=np.int64)
        out[...] = ((field[..., None] >> shifts) & 0xFF).astype(np.uint8)

    def ri(lo, hi):
        return rng.randint(lo, hi, (B,)).astype(np.int64)

    def full(x):
        return np.full((B,), x, np.int64)

    def ascii_bytes(n):
        return rng.randint(97, 123, (B, n), dtype=np.uint8)  # a-z

    def write_stat(off, mzxid, data_len=0, num_children=0):
        be(ri(1, 1 << 40), 8, v[:, off:off + 8])          # czxid
        be(mzxid, 8, v[:, off + 8:off + 16])              # mzxid
        be(ri(1, 1 << 41), 8, v[:, off + 16:off + 24])    # ctime
        be(ri(1, 1 << 41), 8, v[:, off + 24:off + 32])    # mtime
        be(ri(0, 1 << 10), 4, v[:, off + 32:off + 36])    # version
        be(ri(0, 1 << 10), 4, v[:, off + 36:off + 40])    # cversion
        be(ri(0, 1 << 10), 4, v[:, off + 40:off + 44])    # aversion
        # ephemeralOwner stays 0
        be(full(data_len), 4, v[:, off + 52:off + 56])    # dataLength
        be(full(num_children), 4, v[:, off + 56:off + 60])
        be(ri(1, 1 << 40), 8, v[:, off + 60:off + 68])    # pzxid

    # xids: sequential per stream from a random base, like the
    # connection FSM's allocator — a reply xid is unique in flight
    xbase = rng.randint(1, 1 << 19, (B,)).astype(np.int64)
    o = xi = 0
    for kind in kinds:
        be(full(_BODY_LEN[kind]), 4, v[:, o:o + 4])
        if kind == 'notif':
            xid, zxid, err = full(-1), full(-1), 0
        elif kind == 'ping':
            xid, zxid, err = full(-2), ri(1, 1 << 40), 0
        else:
            xid, zxid = xbase + xi, ri(1, 1 << 40)
            err = -101 if kind == 'data_err' else 0  # NO_NODE
            xi += 1
        be(xid, 4, v[:, o + 4:o + 8])
        be(zxid, 8, v[:, o + 8:o + 16])
        be(full(err), 4, v[:, o + 16:o + 20])
        p = o + 20                                  # payload start
        if kind == 'data':
            be(full(_DATA_LEN), 4, v[:, p:p + 4])
            v[:, p + 4:p + 4 + _DATA_LEN] = rng.randint(
                0, 256, (B, _DATA_LEN), dtype=np.uint8)
            write_stat(p + 4 + _DATA_LEN, zxid, data_len=_DATA_LEN)
        elif kind in ('children2', 'children'):
            n, w = ((_CH2_N, _CH2_NAME) if kind == 'children2'
                    else (_CH_N, _CH_NAME))
            be(full(n), 4, v[:, p:p + 4])
            c = p + 4
            for _k in range(n):
                be(full(w), 4, v[:, c:c + 4])
                v[:, c + 4:c + 4 + w] = ascii_bytes(w)
                c += 4 + w
            if kind == 'children2':
                write_stat(c, zxid, num_children=n)
        elif kind == 'acl':
            be(full(_ACL_N), 4, v[:, p:p + 4])
            c = p + 4
            for _k in range(_ACL_N):
                be(full(0x1F), 4, v[:, c:c + 4])    # perms: ALL
                be(full(_ACL_SCHEME), 4, v[:, c + 4:c + 8])
                v[:, c + 8:c + 8 + _ACL_SCHEME] = ascii_bytes(
                    _ACL_SCHEME)
                c += 8 + _ACL_SCHEME
                be(full(_ACL_ID), 4, v[:, c:c + 4])
                v[:, c + 4:c + 4 + _ACL_ID] = ascii_bytes(_ACL_ID)
                c += 4 + _ACL_ID
            write_stat(c, zxid)
        elif kind == 'notif':
            be(ri(1, 5), 4, v[:, p:p + 4])          # type: valid enum
            be(full(3), 4, v[:, p + 4:p + 8])       # SYNC_CONNECTED
            be(full(_NOTIF_PATH), 4, v[:, p + 8:p + 12])
            v[:, p + 12] = ord('/')
            v[:, p + 13:p + 12 + _NOTIF_PATH] = ascii_bytes(
                _NOTIF_PATH - 1)
        # 'ping' / 'data_err': header-only bodies, nothing more
        o += 4 + _BODY_LEN[kind]
    return v


def kernel_checks(cfg: dict, on_chip: bool, corpus) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from zkstream_tpu.ops.pipeline import (
        wire_pipeline_step,
        wire_pipeline_step_pallas,
    )

    interpret = not on_chip
    out: dict = {}

    def cut(B, L):
        buf = np.ascontiguousarray(corpus[:B, :L])
        return jnp.asarray(buf), jnp.full((B,), L, jnp.int32)

    for name in ('scan_pocket', 'scan_single'):
        B, L, F = cfg[name]
        buf, lens = cut(B, L)
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(
            lambda b, l, F=F: wire_pipeline_step_pallas(
                b, l, max_frames=F, block_rows=64,
                interpret=interpret))(buf, lens))
        secs = time.perf_counter() - t0
        want = jax.jit(lambda b, l, F=F: wire_pipeline_step(
            b, l, max_frames=F))(buf, lens)
        _same(name, want, got)
        frames = int(np.asarray(got.n_frames).sum())
        check(frames > 0, '%s decoded no frame' % (name,))
        out[name] = {'kernel': 'pallas_wire_scan', 'streams': B,
                     'row_bytes': L, 'max_frames': F, 'block_rows': 64,
                     'frames': frames, 'matches_jnp': True,
                     'first_call_s': round(secs, 3)}
    return out


async def tick_pocket_check(cfg: dict, on_chip: bool, corpus) -> dict:
    """One ingest tick bucket inside the auto-dispatch pocket: name the
    implementation it compiled to and hold its output to jnp."""
    import jax
    import numpy as np

    from zkstream_tpu.io.ingest import FleetIngest
    from zkstream_tpu.ops.pipeline import wire_pipeline_step

    Bp, L, F = cfg['tick_pocket']
    ingest = FleetIngest(max_frames=F, min_len=L,
                         placement='accelerator' if on_chip else 'host',
                         bypass_bytes=0, warm='block')
    await ingest.prewarm(Bp, L)
    (key, info), = ingest.buckets.items()
    check(key == (False, Bp, L), 'tick bucket %r' % (key,))
    batch = np.ascontiguousarray(corpus[:Bp, :L])
    lens = np.full((Bp,), L, np.int32)
    st = ingest._unpack(np.asarray(ingest._exec[key](batch, lens)))
    with jax.default_device(ingest._device):
        want = jax.jit(lambda b, l: wire_pipeline_step(
            b, l, max_frames=F))(batch, lens)
    for f in ('n_frames', 'resid', 'starts', 'sizes', 'xids', 'errs',
              'zxid_hi', 'zxid_lo'):
        check(np.array_equal(np.asarray(getattr(st, f)),
                             np.asarray(getattr(want, f))),
              'tick bucket: plane %s differs from jnp' % (f,))
    check(np.array_equal(np.asarray(st.bad).astype(bool),
                         np.asarray(want.bad)),
          'tick bucket: bad flags differ from jnp')
    if on_chip:
        check(info['impl'] == 'pallas', 'a pocket bucket compiled to '
              '%r on the chip' % (info['impl'],))
    return {'bucket': list(key), 'max_frames': F, 'impl': info['impl'],
            'platform': info['platform'],
            'compile_s': round(info['compile_s'], 3),
            'frames': int(np.asarray(st.n_frames).sum()),
            'matches_jnp': True}


# ---------------------------------------------------------------------
# the ensemble and the load generator
# ---------------------------------------------------------------------

def jax_free(pid: int) -> bool:
    """No JAX/XLA/libtpu object is mapped into process ``pid``."""
    with open('/proc/%d/maps' % (pid,)) as f:
        maps = f.read()
    return not any(x in maps for x in ('jaxlib', 'libtpu', 'xla_'))


async def run_loadgen(binary: str, addrs, cfg: dict) -> dict:
    from zkstream_tpu.utils import loadgen

    # reads and 1 KiB creates in the steady window; the watched SETs
    # are the three fan-out rounds' (a steady-window SET would fire
    # every armed session at once — a herd workload, not a smoke)
    cmd = loadgen.argv(
        addrs, cfg['sessions'], duration=cfg['loadgen_s'],
        mix='get=70,exists=20,create=10',
        data=cfg['payload'], arm_watch=True, fanout_sets=3,
        session_timeout_ms=SESSION_TIMEOUT_MS, close_sessions=True)
    check(cmd is not None and cmd[0] == binary,
          'loadgen command line: %r' % (cmd,))
    proc = await asyncio.create_subprocess_exec(
        *cmd, stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.PIPE)
    try:
        out, err = await asyncio.wait_for(proc.communicate(), 300)
    except BaseException:
        with contextlib.suppress(ProcessLookupError):
            proc.kill()
        raise
    check(proc.returncode == 0, 'loadgen exit %s: %s'
          % (proc.returncode, err.decode(errors='replace')[-400:]))
    s = json.loads(out.decode().strip().splitlines()[-1])
    check(all(v == 0 for v in s['errors'].values()),
          'loadgen errors: %r' % (s['errors'],))
    check(s['zxid']['floor_violations'] == 0,
          'loadgen saw a zxid go backwards')
    check(s['fanout']['delivered'] == s['fanout']['expected'],
          'loadgen fan-out %r' % (s['fanout'],))
    ops = sum(v['count'] for v in s['ops'].values())
    check(ops > 0, 'loadgen ran no op')
    return {'sessions': s['handshake']['connected'], 'ops': ops,
            'errors': s['errors'], 'fanout': s['fanout'],
            'floor_violations': s['zxid']['floor_violations'],
            'caps': s.get('caps')}


async def smoke(args, cfg: dict, reduced: dict) -> dict:
    from zkstream_tpu.server.election import (
        ProcMember,
        _scrape_mntr,
        allocate_ports,
        find_leader,
    )
    from zkstream_tpu.utils import native

    report: dict = {'seed': args.seed, 'reduced': reduced,
                    'deployment': {k: cfg[k] for k in (
                        'voters', 'sessions', 'parents', 'children',
                        'payload')}}
    report['deployment']['ops_per_session'] = 16 * cfg['cycles']
    report['deployment']['guarantees'] = (
        'WAL sync=tick, quorum-commit; an acknowledged write is read '
        'back from another member after sync')

    # -- what runs is built from the files git would commit ----------
    t0 = time.perf_counter()
    check(native.ensure_ext() is not None,
          'the C extension (native/zkwire_ext.c) did not build')
    binary = native.build_loadgen()
    check(binary is not None,
          'the load generator (tools/loadgen.c) did not build')
    report['native'] = {
        'ext': os.path.basename(native.ext_path()),
        'loadgen': os.path.basename(binary),
        'build_s': round(time.perf_counter() - t0, 3)}

    # -- the ensemble: OS processes, before this process's first JAX
    # call; WAL on, quorum on, every plane at its default
    tmp = tempfile.mkdtemp(prefix='zk-chip-smoke-')
    os.environ['ZKSTREAM_MEMBER_SYNC'] = 'tick'   # the members' WAL
    ports = allocate_ports(2 * cfg['voters'])
    members = [ProcMember(i, os.path.join(tmp, 'm%d' % i),
                          ports[2 * i], ports[2 * i + 1])
               for i in range(cfg['voters'])]
    try:
        check('jax' not in sys.modules,
              'jax was imported before the members were spawned')
        for m in members:
            os.makedirs(m.wal_dir, exist_ok=True)
            m.spawn(members)
        await asyncio.gather(*[m.wait_ready() for m in members])
        leader, epoch = await find_leader(members)
        addrs = [('127.0.0.1', m.client_port) for m in members]

        # -- the device, as JAX reports it ---------------------------
        if args.cpu_dry_run:
            from zkstream_tpu.utils.platform import force_cpu
            force_cpu(n_devices=1)
        import jax
        import jaxlib

        from zkstream_tpu.utils.platform import enable_compile_cache
        dev = jax.devices()[0]
        device = {'platform': dev.platform, 'kind': dev.device_kind,
                  'count': len(jax.devices())}
        on_chip = dev.platform == 'tpu'
        if not on_chip and not args.cpu_dry_run:
            raise SystemExit(
                'chip_smoke.py: no accelerator: JAX reports %r; '
                '--cpu-dry-run is the explicit toy-size CPU run'
                % (device,))
        report['device'] = device
        report['chip'] = on_chip
        try:
            import libtpu
            libtpu_version = libtpu.__version__
        except ImportError:
            libtpu_version = None
        report['versions'] = {
            'python': sys.version.split()[0], 'jax': jax.__version__,
            'jaxlib': jaxlib.__version__, 'libtpu': libtpu_version}
        cache_dir = enable_compile_cache()

        def cache_entries() -> int:
            return (len(os.listdir(cache_dir))
                    if os.path.isdir(cache_dir) else 0)
        report['compile_cache'] = {'dir': cache_dir,
                                   'entries_before': cache_entries()}
        say('# device: %s versions: %s cache: %s native: %s'
            % (json.dumps(device), json.dumps(report['versions']),
               json.dumps(report['compile_cache']),
               json.dumps(report['native'])))

        rows = []
        for m in members:
            r = await _scrape_mntr(m.client_port)
            rows.append({
                'member': m.member_id,
                'role': r.get('zk_member_role'),
                'transport': r.get('zk_transport_backend'),
                'ingress': r.get('zk_ingress_backend'),
                'wal_sync': r.get('zk_wal_sync'),
                'quorum_members': r.get('zk_quorum_members'),
                'jax_free': jax_free(m.proc.pid)})
        report['ensemble'] = {'leader': leader, 'epoch': epoch,
                              'members': rows}
        say('# ensemble: %s' % (json.dumps(report['ensemble']),))
        check(all(r['jax_free'] for r in rows),
              'a member process has JAX mapped')
        check(sum(r['role'] == 'leader' for r in rows) == 1,
              'the ensemble does not have exactly one leader')

        # -- reference: the jute spec tier, no ingest ----------------
        load_s = await load_tree(addrs[0], '/r', args.seed, cfg)
        await wait_tree_everywhere(addrs, '/r', cfg)
        reference = await run_arm('reference', addrs, '/r', args.seed,
                                  cfg)
        report['reference'] = {
            'codec': 'jute spec tier (use_native_codec=False), no '
                     'ingest',
            'ops': reference['counts']['ops'],
            'notifications': reference['counts']['notifications'],
            'load_s': round(load_s, 3),
            'script_s': round(reference['secs'], 3),
            'acked_writes_read_back_from_another_member': True}
        say('# reference: %s' % (json.dumps(report['reference']),))
        # the arms run the same script on trees of their own, so the
        # recorded paths differ only in the root that obs strips
        for sess in reference['obs']:
            check(len(sess) == 16 * cfg['cycles'] + 1,
                  'reference script length %d' % (len(sess),))

        await load_tree(addrs[0], '/d', args.seed, cfg)
        await wait_tree_everywhere(addrs, '/d', cfg)
        arm = report['ingest'] = await device_arm(
            addrs, '/d', args.seed, cfg, on_chip, reference)
        arm['cache_entries_after'] = cache_entries()
        say('# ingest: %s' % (json.dumps(arm),))
        report['compile_cache']['entries_after_ticks'] = cache_entries()
        report['compile_cache']['new_tick_entries'] = (
            report['compile_cache']['entries_after_ticks']
            - report['compile_cache']['entries_before'])

        # -- kernels --------------------------------------------------
        corpus = kernel_corpus(cfg)
        report['kernels'] = kernel_checks(cfg, on_chip, corpus)
        report['kernels']['interpret'] = not on_chip
        report['kernels']['tick_pocket'] = await tick_pocket_check(
            cfg, on_chip, corpus)
        say('# kernels: %s' % (json.dumps(report['kernels']),))
        report['compile_cache']['entries_after'] = cache_entries()

        # -- loadgen, last and short ---------------------------------
        report['loadgen'] = await run_loadgen(binary, addrs, cfg)
        say('# loadgen: %s' % (json.dumps(report['loadgen']),))
        check(all(m.alive() for m in members),
              'a member died during the run')
    finally:
        for m in members:
            m.kill()
        shutil.rmtree(tmp, ignore_errors=True)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--seed', type=int, default=21)
    ap.add_argument('--cpu-dry-run', action='store_true',
                    help='toy sizes on the CPU backend, kernels in the '
                         'Pallas interpreter; stamps "chip": false')
    args = ap.parse_args()
    cfg = dict(TOY if args.cpu_dry_run else FULL)
    reduced = {k: {'asked': FULL[k], 'ran': cfg[k]}
               for k in FULL if cfg[k] != FULL[k]}
    t0 = time.perf_counter()
    try:
        report = asyncio.run(smoke(args, cfg, reduced))
    except SmokeFailure as e:
        print('chip_smoke.py: FAILED: %s' % (e,), file=sys.stderr)
        return 1
    # the full report, then - last - the verdict: exactly ``ok`` and
    # ``device``, which is all the driver's check reads
    device = report['device']
    print(json.dumps({
        'ok': True, **report,
        'wall_s': round(time.perf_counter() - t0, 1), 'claim': None,
    }), flush=True)
    print(json.dumps({'ok': True, 'device': {
        'platform': str(device['platform']), 'kind': str(device['kind']),
        'count': int(device['count'])}}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
