"""Agent HTTP server + FIFO scheduler (parity: skylet daemon + gRPC
services + JobSchedulerEvent, sky/skylet/skylet.py:46-75, events.py:69).

JSON over HTTP on localhost (aiohttp); reached through an SSH tunnel on
real clusters.  Endpoints:

  GET  /health                 {ok, idle_seconds, autostop}
  POST /jobs/submit            {name, spec} -> {job_id}
  GET  /jobs                   [{job_id, name, status, ...}]
  GET  /jobs/{id}              job record
  POST /jobs/{id}/cancel
  GET  /jobs/{id}/logs?phase=run&rank=0&offset=N   raw log bytes
  POST /autostop               {idle_minutes, down}  (persisted + enforced
                               by agent/autostop.py AutostopEvent)
"""
from __future__ import annotations

import asyncio
import json
import os
import threading
import time
from typing import Any, Dict, Optional

from aiohttp import web

from skypilot_tpu.agent import autostop as autostop_lib
from skypilot_tpu.agent import gang, job_queue


class AgentScheduler:
    """FIFO: one gang job at a time (parity: FIFOScheduler,
    job_lib.py:353)."""

    def __init__(self) -> None:
        self._current: Optional[gang.GangJob] = None
        self._current_id: Optional[int] = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def cancel(self, job_id: int) -> bool:
        with self._lock:
            if self._current_id == job_id and self._current is not None:
                self._current.cancel()
                job_queue.set_status(job_id,
                                     job_queue.JobStatus.CANCELLED, 130)
                return True
        job = job_queue.get(job_id)
        if job and not job['status'].is_terminal():
            job_queue.set_status(job_id, job_queue.JobStatus.CANCELLED)
            return True
        return False

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                job = job_queue.next_pending()
            except Exception:  # pylint: disable=broad-except
                # The store can refuse a read (this thread and the event
                # loop's open a fresh jobs.db at the same moment).  The
                # scheduler has to outlive that: with it gone every job
                # submitted later stays PENDING and its launch waits for
                # ever.
                job = None
            if job is None:
                self._stop.wait(1.0)
                continue
            job_id = job['job_id']
            log_dir = job_queue.log_dir(job_id)
            g = gang.GangJob(job_id, job['spec'], log_dir)
            with self._lock:
                self._current, self._current_id = g, job_id
            # Re-check after claiming: a cancel may have landed between
            # dequeue and the claim above.
            fresh = job_queue.get(job_id)
            if fresh and fresh['status'] is job_queue.JobStatus.CANCELLED:
                with self._lock:
                    self._current = self._current_id = None
                continue

            def cb(status, rc, job_id=job_id):
                job_queue.set_status(job_id, status, rc)

            try:
                gang.run_gang_job(job_id, job['spec'], log_dir, cb, job=g)
            except Exception as e:  # pylint: disable=broad-except
                job_queue.set_status(job_id, job_queue.JobStatus.FAILED, 1)
                with open(os.path.join(log_dir, 'agent-error.log'), 'a',
                          encoding='utf-8') as f:
                    f.write(f'{e}\n')
            finally:
                # Ship finished-job logs to the configured external
                # store (no-op when logs.store is unset; never raises).
                from skypilot_tpu import logs as logs_lib
                logs_lib.ship_job_logs(
                    os.environ.get('SKYTPU_CLUSTER_NAME'), job_id,
                    log_dir)
                with self._lock:
                    self._current = self._current_id = None


def _job_json(job: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(job)
    out['status'] = job['status'].value
    out.pop('spec', None)
    return out


def make_app(scheduler: Optional[AgentScheduler] = None,
             identity: Optional[autostop_lib.ClusterIdentity] = None
             ) -> web.Application:
    sched = scheduler or AgentScheduler()
    sched.start()
    app = web.Application()
    app['scheduler'] = sched
    started_at = time.time()
    identity = identity or autostop_lib.ClusterIdentity(
        None, None, None, None)
    from skypilot_tpu.agent import events as events_lib
    event_loop = events_lib.EventLoop(identity, started_at)
    event_loop.start()
    app['events'] = event_loop

    async def _stop_event(_app):
        event_loop.stop()
        sched.stop()

    app.on_cleanup.append(_stop_event)

    async def health(request):
        import skypilot_tpu
        return web.json_response({
            'ok': True,
            'version': skypilot_tpu.__version__,
            'idle_seconds': autostop_lib.idle_seconds(started_at),
            'autostop': autostop_lib.get_config(),
        })

    async def submit(request):
        body = await request.json()
        job_id = job_queue.submit(body.get('name'), body['spec'])
        return web.json_response({'job_id': job_id})

    async def jobs(request):
        return web.json_response(
            [_job_json(j) for j in job_queue.list_jobs()])

    async def job_get(request):
        job = job_queue.get(int(request.match_info['job_id']))
        if job is None:
            return web.json_response({'error': 'not found'}, status=404)
        return web.json_response(_job_json(job))

    async def cancel(request):
        ok = request.app['scheduler'].cancel(
            int(request.match_info['job_id']))
        return web.json_response({'cancelled': ok})

    async def logs(request):
        import re
        job_id = int(request.match_info['job_id'])
        phase = request.query.get('phase', 'run')
        rank = request.query.get('rank', '0')
        # Path components: reject traversal attempts outright.
        if not re.fullmatch(r'[A-Za-z0-9_-]+', phase) or \
                not re.fullmatch(r'[0-9]+', rank):
            return web.json_response({'error': 'bad phase/rank'},
                                     status=400)
        offset = int(request.query.get('offset', '0'))
        path = os.path.join(job_queue.log_dir(job_id),
                            f'{phase}-{rank}.log')
        if not os.path.exists(path):
            return web.Response(body=b'', status=200)
        with open(path, 'rb') as f:
            f.seek(offset)
            return web.Response(body=f.read())

    async def autostop(request):
        body = await request.json()
        autostop_lib.set_config(int(body.get('idle_minutes', -1)),
                                bool(body.get('down', False)))
        return web.json_response({'ok': True})

    app.router.add_get('/health', health)
    app.router.add_post('/jobs/submit', submit)
    app.router.add_get('/jobs', jobs)
    app.router.add_get('/jobs/{job_id}', job_get)
    app.router.add_post('/jobs/{job_id}/cancel', cancel)
    app.router.add_get('/jobs/{job_id}/logs', logs)
    app.router.add_post('/autostop', autostop)
    return app


def main() -> None:
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument('--port', type=int, default=8790)
    parser.add_argument('--host', default='127.0.0.1')
    # Cluster identity: lets the AutostopEvent address this cluster
    # through the provision dispatch API (see agent/autostop.py).
    parser.add_argument('--cluster-name', default=None)
    parser.add_argument('--cloud', default=None)
    parser.add_argument('--region', default=None)
    parser.add_argument('--zone', default=None)
    args = parser.parse_args()
    if args.cluster_name:
        # Visible to the job runner thread (log shipping destination).
        os.environ['SKYTPU_CLUSTER_NAME'] = args.cluster_name
    identity = autostop_lib.ClusterIdentity(args.cluster_name, args.cloud,
                                            args.region, args.zone)
    web.run_app(make_app(identity=identity), host=args.host, port=args.port,
                print=lambda *a: None)


if __name__ == '__main__':
    main()
