"""Acks the leader released in the window without a confirmed quorum
(``zk_quorum_degraded``, after its 250 ms wait).  Should read 0."""

def read(run):
    return run.mntr_delta(run.leader, 'zk_quorum_degraded')
