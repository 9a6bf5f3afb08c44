"""The leader's WAL fsyncs in the window per write acknowledged in it:
the group-commit shape (1 = an fsync per write)."""

def read(run):
    fsyncs = run.mntr_delta(run.leader, 'zk_wal_fsyncs')
    writes = run.result.get('counters', {}).get('writes_acked')
    if fsyncs is None or not writes:
        return None
    return fsyncs / writes
