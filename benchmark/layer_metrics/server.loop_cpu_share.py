"""How much of the LEADER process's CPU its event loop's thread took:
the ``zk_loop_cpu_ms`` delta (``time.thread_time()`` of the thread
that answers the scrape) over the ``zk_process_cpu_ms`` delta (every
thread), both cumulative ``mntr`` rows.  The rest is other threads'
(fsync, black box, collector).  None against a program without the
row."""


def read(run):
    loop_ms = run.mntr_delta(run.leader, 'zk_loop_cpu_ms')
    cpu_ms = run.mntr_delta(run.leader, 'zk_process_cpu_ms')
    if loop_ms is None or not cpu_ms or cpu_ms <= 0:
        return None
    return 100.0 * loop_ms / cpu_ms
