"""Commit-log entries the leader shipped in the window per push message
it handed a mirror's transport (``zk_repl_pushed_commits`` /
``zk_repl_pushes``, cumulative ``mntr`` rows of the leader): the group
one push carries — what a forwarded batch, or one turn of the leader's
loop, committed (1 = a push, and an ack, a commit and a mirror).  None
against a program without the rows."""


def read(run):
    pushes = run.mntr_delta(run.leader, 'zk_repl_pushes')
    commits = run.mntr_delta(run.leader, 'zk_repl_pushed_commits')
    if pushes is None or commits is None:
        return None         # a program without the rows: nothing to read
    return commits / pushes if pushes else None
