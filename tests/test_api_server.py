"""API server + SDK + CLI tests with an in-process server
(model: reference tests/test_api.py + mock_client_requests fixture)."""
import threading
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestServer

from skypilot_tpu.agent.job_queue import JobStatus


@pytest.fixture
def api_server(tmp_home, enable_all_clouds, monkeypatch):
    """Real aiohttp server on a random port, in a background thread."""
    import asyncio
    from skypilot_tpu.server.app import make_app
    # Background daemons off: their jittered ticks (status refresh,
    # controller re-adoption) would race deliberately-staged test state.
    monkeypatch.setenv('SKYTPU_DAEMONS', '0')

    loop = asyncio.new_event_loop()
    server_holder = {}

    def run():
        asyncio.set_event_loop(loop)
        server = TestServer(make_app())
        loop.run_until_complete(server.start_server())
        server_holder['server'] = server
        server_holder['port'] = server.port
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # Under six workers beside JAX tests the server has taken more than
    # 10 s to come up (ROADMAP C10): the wait ends when the port is there.
    deadline = time.time() + 60
    while 'port' not in server_holder and time.time() < deadline:
        time.sleep(0.05)
    url = f'http://127.0.0.1:{server_holder["port"]}'
    monkeypatch.setenv('SKYTPU_API_SERVER', url)
    yield url
    asyncio.run_coroutine_threadsafe(
        server_holder['server'].close(), loop).result(timeout=10)
    loop.call_soon_threadsafe(loop.stop)
    # In-process jobs/serve controller threads must not outlive this
    # test's $HOME (they would mutate the next test's DBs).
    from skypilot_tpu.jobs import controller as jobs_controller
    from skypilot_tpu.serve import controller as serve_controller
    jobs_controller.stop_all_controllers()
    serve_controller.stop_all_controllers()


def _mk_local_task(run='echo api-hello'):
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.task import Task
    t = Task('apitask', run=run)
    t.set_resources(Resources.from_yaml_config({'infra': 'local'}))
    return t


def test_health_and_check(api_server):
    from skypilot_tpu.client import sdk
    assert sdk.api_info()['status'] == 'healthy'
    checks = sdk.check()
    assert checks['local']['enabled']


def test_launch_via_sdk_end_to_end(api_server):
    from skypilot_tpu.client import sdk
    request_id = sdk.launch(_mk_local_task(), 'apie2e')
    result = sdk.get(request_id)
    assert result['cluster_name'] == 'apie2e'
    job_id = result['job_id']
    # Per-request memory accounting: the worker recorded its peak RSS.
    rec = sdk._get(f'/requests/{request_id}')
    assert rec.get('peak_rss_kb') and rec['peak_rss_kb'] > 0
    # poll queue until terminal
    deadline = time.time() + 30
    while time.time() < deadline:
        jobs = sdk.queue('apie2e')
        rec = next(j for j in jobs if j['job_id'] == job_id)
        if JobStatus(rec['status']).is_terminal():
            break
        time.sleep(0.3)
    assert rec['status'] == 'SUCCEEDED'
    # status via REST
    records = sdk.status()
    assert records[0]['name'] == 'apie2e'
    assert records[0]['status'] == 'UP'
    # logs via streaming endpoint
    import io
    buf = io.StringIO()
    sdk.tail_logs('apie2e', job_id, follow=False, out=buf)
    assert 'api-hello' in buf.getvalue()
    # cost report + down
    assert sdk.cost_report()[0]['name'] == 'apie2e'
    sdk.get(sdk.down('apie2e'))
    assert sdk.status() == []


def test_failed_request_surfaces_error(api_server):
    from skypilot_tpu import exceptions
    from skypilot_tpu.client import sdk
    t = _mk_local_task()
    with pytest.raises(exceptions.ApiServerError) as err:
        sdk.get(sdk.exec_(t, 'missing-cluster'))
    assert 'does not exist' in str(err.value)


def test_accelerators_endpoint(api_server):
    from skypilot_tpu.client import sdk
    accs = sdk.accelerators('v5p')
    assert accs and all('v5p' in k for k in accs)


def test_requests_persisted(api_server, tmp_home):
    from skypilot_tpu.client import sdk
    from skypilot_tpu.server import requests_db
    request_id = sdk.launch(_mk_local_task(), 'persist1')
    sdk.get(request_id)
    rec = requests_db.get(request_id)
    assert rec is not None
    assert rec['status'].value == 'SUCCEEDED'
    sdk.get(sdk.down('persist1'))


def test_cli_entrypoints(api_server, tmp_path):
    from click.testing import CliRunner
    from skypilot_tpu.client.cli import cli
    runner = CliRunner()
    # accelerators listing straight through REST
    result = runner.invoke(cli, ['accelerators', 'v6e'])
    assert result.exit_code == 0, result.output
    assert 'tpu-v6e-8' in result.output
    # check
    result = runner.invoke(cli, ['check'])
    assert result.exit_code == 0
    assert 'local: enabled' in result.output
    # launch a YAML task end-to-end
    yaml_path = tmp_path / 'task.yaml'
    yaml_path.write_text(
        'name: cliyaml\nresources:\n  infra: local\nrun: echo from-cli\n')
    result = runner.invoke(cli, ['launch', str(yaml_path), '-c', 'clic'])
    assert result.exit_code == 0, result.output
    assert 'from-cli' in result.output
    result = runner.invoke(cli, ['status'])
    assert 'clic' in result.output
    result = runner.invoke(cli, ['down', 'clic', '--yes'])
    assert result.exit_code == 0, result.output


def test_payload_validation_400(api_server):
    # Garbage bodies are 400s with a message, never 500 KeyErrors.
    import requests
    r = requests.post(f'{api_server}/launch', data='not json')
    assert r.status_code == 400
    assert 'JSON' in r.json()['error']
    r = requests.post(f'{api_server}/launch', json={'bogus': 1})
    assert r.status_code == 400
    assert 'task' in r.json()['error']
    r = requests.post(f'{api_server}/down', json={})
    assert r.status_code == 400
    r = requests.post(f'{api_server}/cancel',
                      json={'cluster_name': 'c', 'job_id': 'NaN'})
    assert r.status_code == 400


def test_bearer_auth(tmp_home, enable_all_clouds, monkeypatch):
    monkeypatch.setenv('SKYTPU_API_TOKEN', 'sekrit')
    import asyncio
    from aiohttp.test_utils import TestClient, TestServer
    from skypilot_tpu.server.app import make_app

    async def drive():
        client = TestClient(TestServer(make_app()))
        await client.start_server()
        try:
            r = await client.get('/api/health')      # exempt
            assert r.status == 200
            r = await client.get('/status')
            assert r.status == 401
            r = await client.get('/status', headers={
                'Authorization': 'Bearer wrong'})
            assert r.status == 401
            r = await client.get('/status', headers={
                'Authorization': 'Bearer sekrit'})
            assert r.status == 200
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(drive())


def test_request_cancellation(api_server):
    # A hung LONG request (stuck provision analog) is killed by
    # POST /requests/{id}/cancel and its worker slot freed.
    import requests
    from skypilot_tpu.task import Task
    from skypilot_tpu.resources import Resources
    t = Task('hang', run='echo hi')
    t.setup = 'sleep 600'       # wedges the worker mid-setup
    t.set_resources(Resources.from_yaml_config({'infra': 'local'}))
    r = requests.post(f'{api_server}/launch',
                      json={'task': t.to_yaml_config(),
                            'cluster_name': 'hangc'})
    request_id = r.json()['request_id']
    # Wait for the worker process to pick it up.
    deadline = time.time() + 60
    while time.time() < deadline:
        rec = requests.get(f'{api_server}/requests/{request_id}').json()
        if rec['status'] == 'RUNNING':
            break
        time.sleep(0.5)
    assert rec['status'] == 'RUNNING', rec
    r = requests.post(f'{api_server}/requests/{request_id}/cancel')
    assert r.status_code == 200
    deadline = time.time() + 15
    while time.time() < deadline:
        rec = requests.get(f'{api_server}/requests/{request_id}').json()
        if rec['status'] == 'CANCELLED':
            break
        time.sleep(0.3)
    assert rec['status'] == 'CANCELLED'
    # cancelling a finished request is a 409
    r = requests.post(f'{api_server}/requests/{request_id}/cancel')
    assert r.status_code == 409
    # cleanup: the half-provisioned local cluster may exist; down it
    requests.post(f'{api_server}/down', json={'cluster_name': 'hangc'})


def test_managed_jobs_over_rest(api_server, monkeypatch):
    """jobs launch -> queue -> logs -> terminal SUCCEEDED, all via REST.

    The controller threads run inside the API-server process
    (consolidation mode); the client only ever polls REST.
    """
    monkeypatch.setenv('SKYTPU_JOBS_POLL_INTERVAL', '0.25')
    import io

    from skypilot_tpu.client import sdk
    result = sdk.get(sdk.jobs_launch(_mk_local_task('echo managed-rest'),
                                     name='mjrest'))
    job_id = result['job_id']
    deadline = time.time() + 60
    status = None
    while time.time() < deadline:
        recs = [r for r in sdk.jobs_queue() if r['job_id'] == job_id]
        assert recs, 'job missing from queue'
        status = recs[0]['status']
        if status in ('SUCCEEDED', 'FAILED', 'FAILED_SETUP',
                      'FAILED_NO_RESOURCE', 'FAILED_CONTROLLER',
                      'CANCELLED'):
            break
        time.sleep(0.3)
    assert status == 'SUCCEEDED', status
    out = io.StringIO()
    sdk.jobs_tail_logs(job_id, follow=False, out=out)
    assert 'managed-rest' in out.getvalue()
    # cancel of a finished job is a clean no-op over REST too
    assert sdk.jobs_cancel(job_id) is False


def test_serve_over_rest(api_server, monkeypatch):
    """serve up -> READY behind the LB -> proxied request -> down, all
    via REST + CLI (controller + LB run inside the API-server process)."""
    monkeypatch.setenv('SKYTPU_SERVE_TICK_INTERVAL', '0.25')
    import urllib.request

    from click.testing import CliRunner
    from skypilot_tpu.client import sdk
    from skypilot_tpu.client.cli import cli

    run_cmd = ('python3 -c "import http.server, os\n'
               'class H(http.server.BaseHTTPRequestHandler):\n'
               '    def do_GET(self):\n'
               '        self.send_response(200)\n'
               '        self.send_header(\'Content-Length\', \'2\')\n'
               '        self.end_headers()\n'
               '        self.wfile.write(b\'ok\')\n'
               '    def log_message(self, *a): pass\n'
               'http.server.HTTPServer((\'127.0.0.1\', '
               'int(os.environ[\'SKYTPU_SERVE_REPLICA_PORT\'])), '
               'H).serve_forever()"')
    task = _mk_local_task(run_cmd)
    task.service = {'readiness_probe': {'path': '/',
                                        'initial_delay_seconds': 30,
                                        'timeout_seconds': 2},
                    'replicas': 1}
    result = sdk.get(sdk.serve_up(task, 'restsvc'))
    assert result['name'] == 'restsvc'
    endpoint = result['endpoint']
    deadline = time.time() + 60
    status = None
    while time.time() < deadline:
        svcs = sdk.serve_status(['restsvc'])
        assert svcs, 'service missing from status'
        status = svcs[0]['status']
        if status in ('READY', 'FAILED', 'SHUTDOWN'):
            break
        time.sleep(0.3)
    assert status == 'READY', status
    with urllib.request.urlopen(endpoint + '/x', timeout=5) as resp:
        assert resp.status == 200
        assert resp.read() == b'ok'
    # CLI status renders the replica table.
    runner = CliRunner()
    out = runner.invoke(cli, ['serve', 'status'])
    assert out.exit_code == 0, out.output
    assert 'restsvc' in out.output and 'READY' in out.output
    # replica logs over REST
    import io
    buf = io.StringIO()
    sdk.serve_replica_logs('restsvc', 1, follow=False, out=buf)
    sdk.get(sdk.serve_down('restsvc'))
    deadline = time.time() + 60
    while time.time() < deadline:
        svcs = sdk.serve_status(['restsvc'])
        if svcs and svcs[0]['status'] == 'SHUTDOWN':
            break
        time.sleep(0.3)
    assert sdk.serve_status(['restsvc'])[0]['status'] == 'SHUTDOWN'
