"""``repl.commits_per_push``: the leader's two cumulative ``mntr`` rows
(``zk_repl_pushed_commits`` over ``zk_repl_pushes``) subtract to the
window's group size; a program without the rows, a member that did not
answer and a window without a push give None; and the toy write cell,
traced, prints the entry — well over one commit a push, because two
thirds of its writers arrive at the leader in batches."""

import tempfile

import harness
import pytest
from conftest import entries, entry
from test_runs import members_alive, rehearse

READER = 'repl.commits_per_push'


def read(run):
    (m,) = entries(READER)
    return harness._load_module('layer_metrics', m['name']).read(run)


def run_with(before: list, after: list, leader: int = 1) -> harness.Run:
    run = harness.Run()
    run.window_s = 20.0
    run.leader = leader
    run.mntr_before, run.mntr_after = before, after
    return run


def test_the_entry_is_the_write_cells_alone():
    (m,) = entries(READER)
    assert m['workloads'] == ['hunt3_1k.write']
    assert (m['layer'], m['moves'], m['better'], m['source']) == (
        'replication', 'write_p95_ms', 'higher', 'program_counter')
    assert harness.reader_path('layer_metrics', m['name']).endswith(
        READER + '.py')


def test_the_window_is_after_minus_before_on_the_leader():
    follower = {'zk_forward_rpcs': '10', 'zk_forward_writes': '54'}
    # set-up pushed 1,100 commits one a message; the window's 2,000
    # messages carried 10,800
    before = [follower, {'zk_repl_pushes': '1100',
                         'zk_repl_pushed_commits': '1100'}, follower]
    after = [follower, {'zk_repl_pushes': '3100',
                        'zk_repl_pushed_commits': '11900'}, follower]
    assert read(run_with(before, after)) == pytest.approx(5.4)
    # only the leader's rows are read
    assert read(run_with(before, after, leader=0)) is None


@pytest.mark.parametrize('before, after', [
    # the parent's program: bytes, but neither row
    ([{'zk_repl_pushed_bytes': '5'}] * 3,
     [{'zk_repl_pushed_bytes': '9'}] * 3),
    # one row of the two
    ([{'zk_repl_pushes': '1'}] * 3, [{'zk_repl_pushes': '4'}] * 3),
    # a window in which nothing was pushed
    ([{'zk_repl_pushes': '7', 'zk_repl_pushed_commits': '9'}] * 3,
     [{'zk_repl_pushes': '7', 'zk_repl_pushed_commits': '9'}] * 3),
    # members that did not answer, or none at all
    ([{}, {}, {}], [{}, {}, {}]),
    ([], []),
])
def test_nothing_to_read_gives_none(before, after):
    assert read(run_with(before, after)) is None


def test_toy_write_cell_traced_prints_a_group_over_one():
    cell = 'hunt3_1k.write'
    name = entry(READER, cell)
    with tempfile.TemporaryDirectory(prefix='benchtest-') as tmp:
        r, out = rehearse(tmp, '--one', cell, '--seed', str(2 ** 31 + 42),
                          '--seconds', '3', '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    got = {k: v['value'] for k, v in out['metrics'].items()}
    assert got[name] > 1.0, got[name]
    # a push never carries more than the followers' batches and the
    # leader's own turns collected
    assert got[name] <= 48
    assert got[entry('quorum.degraded_releases', cell)] == 0
    assert not members_alive()
