"""Agent periodic events (parity: sky/skylet/events.py roster) and the
compute-vs-storage credential split (parity: sky/check.py:81)."""
import os
import time

import requests as requests_lib

from tests.test_api_server import api_server, _mk_local_task  # noqa: F401


def test_log_gc_prunes_old_job_logs(tmp_home, monkeypatch):
    monkeypatch.setenv('SKYTPU_AGENT_LOG_RETENTION_HOURS', '0')
    from skypilot_tpu.agent import events as events_lib
    from skypilot_tpu.agent import job_queue
    from skypilot_tpu.utils import db_utils
    jid = job_queue.submit('gc1', {'run': 'echo x'})
    job_queue.set_status(jid, job_queue.JobStatus.RUNNING)
    job_queue.set_status(jid, job_queue.JobStatus.SUCCEEDED, 0)
    log_dir = job_queue.log_dir(jid)
    os.makedirs(log_dir, exist_ok=True)
    with open(os.path.join(log_dir, 'run-0.log'), 'w') as f:
        f.write('old')
    # Age the job: finished an hour ago.
    db_utils.execute(job_queue.db_path(),
                     'UPDATE jobs SET ended_at=? WHERE job_id=?',
                     (time.time() - 3600, jid))
    assert events_lib.gc_job_logs() == 1
    assert not os.path.exists(log_dir)
    # Fresh/unfinished jobs are untouched.
    jid2 = job_queue.submit('gc2', {'run': 'echo y'})
    os.makedirs(job_queue.log_dir(jid2), exist_ok=True)
    assert events_lib.gc_job_logs() == 0
    assert os.path.exists(job_queue.log_dir(jid2))


def test_event_loop_runs_roster(tmp_home, monkeypatch):
    monkeypatch.setenv('SKYTPU_AGENT_EVENT_INTERVAL', '0.1')
    from skypilot_tpu.agent import autostop as autostop_lib
    from skypilot_tpu.agent import events as events_lib
    loop = events_lib.EventLoop(
        autostop_lib.ClusterIdentity(None, None, None, None), time.time())
    names = [n for n, _ in loop.events]
    assert names == ['autostop', 'log-gc', 'log-ship']
    fired = []
    loop.events.append(('probe', lambda: fired.append(1)))
    loop.events.append(('boom', lambda: 1 / 0))   # isolated failure
    loop.start()
    deadline = time.time() + 5
    while not fired and time.time() < deadline:
        time.sleep(0.05)
    loop.stop()
    assert fired, 'event loop never ticked'


def test_scheduler_outlives_a_store_error(tmp_home, monkeypatch):
    """A read the store refuses (the scheduler's thread and the event
    loop's open a fresh jobs.db at the same moment) does not end the
    scheduler: with it gone every later job would stay PENDING and its
    launch wait for ever."""
    import sqlite3
    from skypilot_tpu.agent import job_queue
    from skypilot_tpu.agent import server as agent_server
    calls = []

    def next_pending():
        calls.append(1)
        if len(calls) == 1:
            raise sqlite3.OperationalError('database is locked')

    monkeypatch.setattr(job_queue, 'next_pending', next_pending)
    sched = agent_server.AgentScheduler()
    sched.start()
    deadline = time.time() + 10
    while len(calls) < 2 and time.time() < deadline:
        time.sleep(0.05)
    alive = sched._thread.is_alive()
    sched.stop()
    assert len(calls) >= 2 and alive


def test_check_reports_storage_split(api_server):
    checks = requests_lib.get(f'{api_server}/check').json()
    for name, info in checks.items():
        assert 'enabled' in info
        assert 'storage' in info and 'enabled' in info['storage']
