"""Mesh-aware fleet ingest: the runtime consumer of the sharded plane.

:class:`zkstream_tpu.io.ingest.FleetIngest` batches a live connection
fleet's receive streams into one decode dispatch per event-loop tick;
this subclass runs that tick's program **dp-sharded over a device
mesh** via ``shard_map`` — the runtime twin of
:func:`zkstream_tpu.parallel.sharded.sharded_wire_step` (which is the
tested unit) — and reduces fleet-global session statistics with XLA
collectives on the way:

- per-stream planes stay ``P('dp', None)``-sharded end to end: each
  device decodes the connections of its shard, and the host reads back
  one packed array exactly as in the single-device ingest;
- the fleet-wide reductions — total frames / replies / notifications /
  pings / errors and the **fleet max zxid** (the resume checkpoint a
  multi-host session manager persists, the distributed analogue of
  lib/zk-session.js:229-235) — run as ``psum`` / unsigned-64 ``pmax``
  collectives over the ``dp`` axis inside the same dispatch, and ride
  back appended to the packed array: zero extra readbacks.

On a multi-host pod slice the same class works over a global mesh with
per-host connection slots (see parallel/multihost.py); the integration
tests drive it on the virtual 8-device CPU mesh with live in-process
connections (tests/test_mesh_ingest.py), and ``__graft_entry__``'s
``dryrun_multichip`` executes it as part of the driver's multi-chip
validation.
"""

from __future__ import annotations

import numpy as np

from ..io.ingest import FleetIngest
from ..ops.bytesops import i64pair_to_int
from .mesh import make_mesh

#: appended global columns: frames, replies, notifications, pings,
#: errors, max_zxid_hi, max_zxid_lo
_N_GLOBALS = 7


class MeshFleetIngest(FleetIngest):
    """FleetIngest whose tick program is dp-sharded over ``mesh``.

    Args:
      mesh: a ``(dp, sp)`` mesh (default: all devices on the dp axis).
      **kw: forwarded to :class:`FleetIngest`.  ``bypass_bytes``
        defaults to 0 here — a mesh proxy exists to run the device
        plane, not to bypass it.
    """

    def __init__(self, mesh=None, **kw):
        kw.setdefault('bypass_bytes', 0)
        # a mesh proxy exists to run the device plane — and the guard's
        # single-core cost model does not describe a real accelerator
        kw.setdefault('frag_guard', False)
        super().__init__(**kw)
        self.mesh = mesh if mesh is not None else make_mesh()
        #: fleet-global stats of the LAST device tick (None before the
        #: first); scalar/warming ticks do not update it.
        self.global_stats: dict | None = None
        self._adding = False    # inside a tick of several dispatches
        #: running fleet-wide maximum zxid over all device ticks — the
        #: checkpoint a proxy-level session manager would persist.
        self.fleet_max_zxid = 0

    # the mesh decides placement; the latency probe is meaningless here
    def _resolve_placement(self) -> None:
        dev = self.mesh.devices.flat[0]
        self.placed = {'platform': dev.platform,
                       'device_kind': dev.device_kind, 'rtt_ms': None}

    def bind_metrics(self, collector, prefix: str = '') -> None:
        super().bind_metrics(collector, prefix)
        collector.gauge(
            prefix + 'zkstream_fleet_max_zxid',
            lambda: self.fleet_max_zxid,
            'fleet-global max zxid (pmax over the mesh) — the '
            'proxy-level session resume checkpoint')

    def _bucket(self, n_streams: int, nbytes: int) -> tuple:
        bodies, Bp, L = super()._bucket(n_streams, nbytes)
        dp = self.mesh.shape['dp']
        # the batch axis must divide over dp shards
        Bp = max(Bp, dp)
        Bp = ((Bp + dp - 1) // dp) * dp
        return bodies, Bp, L

    def _step_fn(self, _bodies=False):
        fn = self._fn
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..ops.bytesops import u64pair_reduce_max
        from .sharded import _u64_axis_max

        def local(buf, lens):
            st, ints = self._trace_step(buf, lens)
            lh, ll = u64pair_reduce_max(st.max_zxid_hi, st.max_zxid_lo)
            gh, gl = _u64_axis_max(lh, ll, 'dp')
            g = jnp.stack([
                lax.psum(jnp.sum(st.n_frames), 'dp'),
                lax.psum(jnp.sum(st.n_replies), 'dp'),
                lax.psum(jnp.sum(st.n_notifications), 'dp'),
                lax.psum(jnp.sum(st.n_pings), 'dp'),
                lax.psum(jnp.sum(st.n_errors), 'dp'),
                gh, gl])
            # replicated globals ride appended to each local row: the
            # packed readback stays one array, zero extra transfers
            return jnp.concatenate(
                [ints, jnp.broadcast_to(g, (ints.shape[0],
                                            _N_GLOBALS))], axis=1)

        fn = self._fn = jax.jit(shard_map(
            local, mesh=self.mesh,
            in_specs=(P('dp', None), P('dp')),
            out_specs=P('dp', None)))
        return fn

    def _finish(self, flight, sp) -> None:
        # a tick of several size classes is several collective
        # launches: its stats are theirs added up
        self.global_stats = None
        self._adding = True
        try:
            super()._finish(flight, sp)
        finally:
            self._adding = False

    def _unpack(self, ints):
        g = ints[0, -_N_GLOBALS:]
        stats = {
            'total_frames': int(g[0]),
            'total_replies': int(g[1]),
            'total_notifications': int(g[2]),
            'total_pings': int(g[3]),
            'total_errors': int(g[4]),
            'max_zxid': i64pair_to_int(g[5], g[6]),
        }
        last = self.global_stats
        if self._adding and last is not None:
            stats = {k: (max if k == 'max_zxid' else int.__add__)(v, last[k])
                     for k, v in stats.items()}
        self.global_stats = stats
        self.fleet_max_zxid = max(self.fleet_max_zxid, stats['max_zxid'])
        return super()._unpack(ints[:, :-_N_GLOBALS])


class MultihostFleetIngest(MeshFleetIngest):
    """Multi-controller fleet proxy: every host of a pod slice serves
    its own live connections through ONE globally sharded tick program.

    The single-host ingest ticks when bytes arrive; that cannot work
    multi-controller — a ``shard_map`` program over a global mesh is a
    collective launch, so every process must launch the same program
    the same number of times.  This class therefore runs on a **fixed
    cadence with fixed shapes**:

    - capacity is static: ``local_rows`` connection slots per host,
      each up to ``stream_len`` buffered bytes per tick (a longer
      backlog carries over — the decode consumes whole frames and
      leaves the remainder buffered);
    - a timer fires every ``tick_interval`` seconds and ALWAYS
      dispatches, even with every slot empty (empty rows decode zero
      frames) — no data-dependent control flow, so the SPMD launch
      counts stay aligned across hosts with at most one interval of
      skew;
    - each host assembles only its own rows
      (:func:`~zkstream_tpu.parallel.multihost.host_local_wire_batch`
      — no cross-host stream bytes, ICI/DCN carries just the psum/pmax
      scalars) and reads back only its addressable shards;
    - the fleet-global stats (total frames, fleet max zxid — the
      resume checkpoint of the WHOLE pod's session population) reduce
      across all hosts inside the dispatch.

    Lifecycle: ``start()`` begins the cadence; ``await
    stop(after_ticks=N)`` stops once N total ticks have run — stopping
    must be coordinated (same N everywhere), because a host that
    stops launching strands the others' collectives; that is the
    multi-controller contract, not a quirk of this class.

    Driven two-process in tests/test_multihost.py
    (multihost_fleet_worker.py) and single-process in
    tests/test_mesh_ingest.py.
    """

    def __init__(self, mesh=None, local_rows: int = 8,
                 stream_len: int = 4096,
                 tick_interval: float = 0.005, **kw):
        import jax

        kw.setdefault('min_len', stream_len)
        super().__init__(mesh=mesh, **kw)
        dp = self.mesh.shape['dp']
        global_rows = local_rows * jax.process_count()
        if global_rows % dp:
            raise ValueError(
                'local_rows=%d x %d processes = %d global rows must '
                'divide over the dp axis (%d)' %
                (local_rows, jax.process_count(), global_rows, dp))
        self.local_rows = local_rows
        self.stream_len = stream_len
        self.tick_interval = tick_interval
        self.tick_count = 0
        #: collective launches actually dispatched; == tick_count
        #: unless a dispatch itself failed (host-side assembly failures
        #: fall back to an empty aligned launch and so keep the two
        #: equal).  ``stop`` checks the invariant loudly.
        self.launch_count = 0
        self._rows: dict[int, int] = {}       # id(conn) -> row
        self._free = list(range(local_rows - 1, -1, -1))
        self._timer = None
        self._stop_at: int | None = None
        #: monotonic time of the last capacity warning; overflow warns
        #: at most once per interval so churn at saturation neither
        #: floods the log nor runs silent (one latch forever would)
        self._warned_capacity_at = float('-inf')

    # event-driven scheduling is disabled: the cadence launches ticks
    def _schedule(self) -> None:
        pass

    def register(self, conn, lane=None, sink=None) -> None:
        # Never raise here: register runs inside the connection FSM's
        # state-entry handler, and an exception there would strand a
        # half-wired connection.  Overflow connections get no row —
        # the cadence drains them through the scalar codec instead.
        if self._free:
            self._rows[id(conn)] = self._free.pop()
        else:
            import time
            now = time.monotonic()
            if now - self._warned_capacity_at >= 30.0:
                self._warned_capacity_at = now
                self.log.warning(
                    'MultihostFleetIngest capacity exceeded '
                    '(local_rows=%d); overflow connections are served '
                    'by the scalar drain — size the proxy for the '
                    'host\'s connection budget', self.local_rows)
        super().register(conn, lane, sink)

    def unregister(self, conn) -> None:
        row = self._rows.pop(id(conn), None)
        if row is not None:
            self._free.append(row)
        super().unregister(conn)

    def start(self) -> None:
        """Begin the tick cadence on the running loop."""
        import asyncio

        if self._timer is None:
            self._timer = asyncio.get_running_loop().create_task(
                self._cadence())

    def warmup_tick(self) -> None:
        """Run ONE aligned collective tick synchronously — call it the
        same number of times on every host before ``start()`` to pay
        the XLA compile outside any session's clock."""
        self._mh_tick()

    async def prewarm(self, n_streams: int,
                      nbytes: int | None = None) -> None:
        raise NotImplementedError(
            'MultihostFleetIngest compiles one fixed-shape GLOBAL '
            'program; use warmup_tick() — the same number of times on '
            'every host — instead of the per-bucket prewarm')

    async def stop(self, after_ticks: int | None = None) -> None:
        """Stop the cadence.  With ``after_ticks`` (the coordinated
        form — pass the SAME value on every host) the cadence runs out
        to exactly that launch count and exits by itself, so every
        process ends with identical collective launch counts; without
        it the timer is cancelled immediately (single-process use)."""
        import asyncio

        if self._timer is None:
            return
        if after_ticks is not None:
            if self.tick_count > after_ticks:
                # the alignment contract is already broken — failing
                # loudly beats stranding the other hosts' collectives
                raise RuntimeError(
                    'stop(after_ticks=%d) but %d ticks already ran; '
                    'launch counts would diverge across hosts'
                    % (after_ticks, self.tick_count))
            self._stop_at = after_ticks
            await self._timer
        else:
            self._timer.cancel()
            try:
                await self._timer
            except asyncio.CancelledError:
                pass
        self._timer = None
        if self.launch_count != self.tick_count:
            # a dispatch failed somewhere along the run: this host
            # launched fewer collectives than its cadence counted, so
            # the other hosts' matching collectives are stranded —
            # surface it here rather than letting them hang silently
            raise RuntimeError(
                'collective launch divergence: %d launches for %d '
                'ticks — a dispatch failed mid-cadence; the other '
                'hosts\' launch counts no longer match this one'
                % (self.launch_count, self.tick_count))

    async def _cadence(self) -> None:
        import asyncio

        while self._stop_at is None or self.tick_count < self._stop_at:
            await asyncio.sleep(self.tick_interval)
            if self._stop_at is not None \
                    and self.tick_count >= self._stop_at:
                # stop() landed mid-sleep after the loop check: one
                # more tick here would exceed the coordinated launch
                # count and strand the other hosts' collectives
                break
            try:
                self._mh_tick()
            except Exception:
                # keep launching: a dead cadence on one host strands
                # every other host's collectives (their readbacks
                # block), turning one local error into a fleet-wide
                # stall.  Pre-dispatch host-side errors fall back to
                # an empty aligned launch inside _mh_tick; what
                # reaches here is a failed dispatch (or an empty
                # launch that itself failed) or a routing/delivery
                # error after the dispatch — either way the cadence
                # continues and ``stop``'s launch/tick invariant says
                # whether alignment held.
                self.log.exception('multihost tick failed; '
                                   'cadence continues')

    def _local_view(self, arr):
        """This process's rows of a dp-sharded global array, in row
        order (the inverse of host_local_wire_batch's placement)."""
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards],
                              axis=0)

    def _assemble_tick(self):
        """Host-side tick assembly: copy each rowed connection's
        buffered bytes into the fixed-shape local batch.  Returns
        (batch, lens, active, overflow)."""
        batch = np.zeros((self.local_rows, self.stream_len), np.uint8)
        lens = np.zeros((self.local_rows,), np.int32)
        active = {}
        overflow = []
        for cid, slot in list(self._slots.items()):
            conn, buf, _lane = slot
            if not buf or not conn.is_in_state('connected'):
                continue
            row = self._rows.get(cid)
            if row is None:          # over capacity: scalar-drained
                overflow.append((conn, buf))
                continue
            n = min(len(buf), self.stream_len)
            batch[row, :n] = np.frombuffer(memoryview(buf)[:n],
                                           np.uint8)
            lens[row] = n
            active[row] = slot
        return batch, lens, active, overflow

    def _mh_tick(self) -> None:
        from .multihost import host_local_wire_batch

        self.tick_count += 1
        try:
            batch, lens, active, overflow = self._assemble_tick()
            fn = self._step_fn()
            gbuf, glens = host_local_wire_batch(self.mesh, batch, lens)
        except Exception:
            # A pre-dispatch host-side failure (assembly, tracing, or
            # the device placement of the local shards) must not skip
            # the collective launch — the other hosts' matching
            # launches would strand.  Retry the whole pre-dispatch
            # path with an EMPTY batch: nothing was consumed, so the
            # buffered bytes are intact and the next healthy tick
            # delivers them one interval late.  If even the empty
            # placement fails, the launch is genuinely impossible —
            # the error propagates and ``stop``'s launch/tick check
            # reports the divergence.
            self.log.exception('multihost tick pre-dispatch failed; '
                               'launching an empty aligned tick')
            batch = np.zeros((self.local_rows, self.stream_len),
                             np.uint8)
            lens = np.zeros((self.local_rows,), np.int32)
            active, overflow = {}, []
            fn = self._step_fn()
            gbuf, glens = host_local_wire_batch(self.mesh, batch, lens)
        # the launch itself is unconditional — collective alignment.
        # Global stats read back on every tick (they carry the OTHER
        # hosts' traffic too).
        ints = fn(gbuf, glens)
        self.launch_count += 1
        st = self._unpack(self._local_view(ints))
        for conn, buf in overflow:
            if id(conn) in self._slots:
                self._deliver_scalar(conn, buf)
        if not active:
            return
        self.ticks += 1

        streams, rows = [], []
        for row, slot in active.items():
            conn, buf, _lane = slot
            if (int(st.n_frames[row]) == 0 and not bool(st.bad[row])
                    and int(st.resid[row]) == 0
                    and len(buf) >= self.stream_len):
                # a single frame larger than stream_len can never fit
                # a fixed-shape tick: drain this stream through the
                # scalar codec (which has no length bound) instead of
                # re-dispatching the same prefix forever — unless an
                # earlier delivery's callback tore the connection down
                # mid-tick (unregister already restored its bytes to
                # the codec)
                if id(conn) in self._slots:
                    self._deliver_scalar(conn, buf)
                continue
            streams.append(slot)
            rows.append(row)
        self._route_batch(streams, rows, st)
