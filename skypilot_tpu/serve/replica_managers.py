"""Replica manager: launches, probes, and replaces replica clusters
(capability parity: sky/serve/replica_managers.py:731
SkyPilotReplicaManager — launch via execution.launch, readiness probing
:571-654, preemption handling :1073).

Each replica is an ordinary cluster launched through the same
execution.launch path users get, with the workload told where to listen
via SKYTPU_SERVE_REPLICA_PORT.  Preemption is detected exactly like
managed jobs: reconcile the state DB against cloud truth
(backend_utils.refresh_cluster_status), then delete the stale slice and
let the autoscaler's next tick replace it.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import requests as requests_lib

from skypilot_tpu import execution
from skypilot_tpu import global_user_state
from skypilot_tpu import sky_logging
from skypilot_tpu import task as task_lib
from skypilot_tpu.backends import TpuVmBackend
from skypilot_tpu.backends import backend_utils
from skypilot_tpu.global_user_state import ClusterStatus
from skypilot_tpu.serve import serve_state
from skypilot_tpu.serve.serve_state import ReplicaStatus
from skypilot_tpu.serve.service_spec import ServiceSpec
from skypilot_tpu.serve.spot_placer import SpotPlacer
from skypilot_tpu.utils import common_utils

logger = sky_logging.init_logger(__name__)

# Consecutive probe failures before READY -> NOT_READY.
_NOT_READY_THRESHOLD = 3
# Consecutive probe failures before a NOT_READY replica is replaced.
_REPLACE_THRESHOLD = 12
# TTL backstop for the cached ready view: serve_state's mutation
# counter invalidates exactly for same-process writes, but a writer in
# ANOTHER process (Postgres control plane, a second controller) is
# invisible to it, so a cached view is additionally re-queried after
# this many seconds.  0 disables caching outright.
_READY_VIEW_TTL_S = float(os.environ.get('SKYTPU_READY_VIEW_TTL_S',
                                         '0.5'))

ENV_REPLICA_PORT = 'SKYTPU_SERVE_REPLICA_PORT'
ENV_REPLICA_ID = 'SKYTPU_SERVE_REPLICA_ID'
ENV_SERVICE_NAME = 'SKYTPU_SERVE_SERVICE_NAME'
ENV_REPLICA_TENSOR = 'SKYTPU_SERVE_TENSOR'
ENV_REPLICA_MAX_PROMPT = 'SKYTPU_SERVE_MAX_PROMPT_LEN'
ENV_REPLICA_KV_PAGE = 'SKYTPU_SERVE_KV_PAGE_SIZE'
ENV_REPLICA_KV_PAGES = 'SKYTPU_SERVE_KV_PAGES'
ENV_REPLICA_PREFIX_CACHE = 'SKYTPU_SERVE_PREFIX_CACHE'
ENV_REPLICA_KV_DTYPE = 'SKYTPU_SERVE_KV_DTYPE'
ENV_REPLICA_SPEC_NGRAM = 'SKYTPU_SERVE_SPEC_NGRAM'
# Disaggregated serving: the replica's pool role (prefill | decode),
# read by the inference server as its --role default.
ENV_REPLICA_ROLE = 'SKYTPU_SERVE_ROLE'


class ReplicaManager:

    def __init__(self, service_name: str, spec: ServiceSpec,
                 task: task_lib.Task,
                 spot_placer: Optional[SpotPlacer] = None,
                 version: int = 1) -> None:
        self.service_name = service_name
        self.spec = spec
        self.task = task
        self.spot_placer = spot_placer
        self.version = version
        self.backend = TpuVmBackend()
        self._launch_threads: Dict[int, threading.Thread] = {}
        # replica_id -> consecutive probe failures
        self._probe_failures: Dict[int, int] = {}
        self._lock = threading.Lock()
        # (replicas_version, monotonic_at, rows) — see _replica_rows.
        self._view_cache: Optional[Tuple[int, float, List[dict]]] = None

    def set_template(self, spec: ServiceSpec, task: task_lib.Task,
                     version: int) -> None:
        """Adopt a new service version (`serve update`): every replica
        launched from here on runs the new task; rollout_step drains
        the old ones."""
        self.spec = spec
        self.task = task
        self.version = version

    # ----- naming -------------------------------------------------------------
    def _cluster_name(self, replica_id: int) -> str:
        return f'serve-{self.service_name}-{replica_id}'

    # ----- scale up -----------------------------------------------------------
    def _next_is_spot(self, role: Optional[str] = None) -> bool:
        """Spot-or-on-demand for the next replica (reference: autoscaler
        ondemand fallback, sky/serve/autoscalers.py).

        Disaggregated pools decide per pool: the disaggregation
        spec's use_spot_prefill/use_spot_decode flags drive placement
        directly (ThunderServe's cost lever — decode replicas hold
        only transferred KV, so their preemptions re-plan cheaply).

        Otherwise on-demand when: the task isn't spot at all; the first
        base_ondemand_fallback_replicas slots aren't covered by live
        on-demand replicas; or dynamic_ondemand_fallback is on and every
        known zone has recently preempted us (spot capacity demonstrably
        gone — bridge on on-demand until it returns)."""
        if role is not None and self.spec.disaggregation is not None:
            return self.spec.disaggregation.use_spot(role)
        if not self.task.any_resources.use_spot:
            return False
        live = serve_state.get_replicas(self.service_name)
        ondemand_live = sum(1 for r in live if not r['is_spot'])
        if ondemand_live < self.spec.base_ondemand_fallback_replicas:
            return False
        if self.spec.dynamic_ondemand_fallback and \
                self.spot_placer is not None and \
                not self.spot_placer.active_zones() and \
                self.spot_placer.preempted_zones():
            return False
        return True

    def _next_role(self) -> Optional[str]:
        """Pool for the next replica when the caller did not name one
        (initial bring-up, rollout surge): fill the prefill pool to
        its base size first — the LB cannot route disaggregated
        traffic without it — then decode.  Counts only THIS version's
        replicas: a rolling update surges a whole new generation, and
        counting the draining generation's prefill replicas would
        surge every new replica as decode, leaving the new generation
        with no prefill pool at all once the old one drains."""
        d = self.spec.disaggregation
        if d is None:
            return None
        live = serve_state.get_replicas(self.service_name)
        n_prefill = sum(1 for r in live
                        if r.get('role') == 'prefill' and
                        r['version'] >= self.version)
        return 'prefill' if n_prefill < d.prefill_replicas else 'decode'

    def scale_up(self, n: int, role: Optional[str] = None) -> None:
        for _ in range(n):
            replica_role = role if role is not None else self._next_role()
            replica_id = serve_state.next_replica_id(self.service_name)
            is_spot = self._next_is_spot(replica_role)
            zone = None
            if is_spot and self.spot_placer is not None:
                zone = self.spot_placer.select()
            serve_state.add_replica(
                self.service_name, replica_id,
                self._cluster_name(replica_id),
                is_spot=is_spot, zone=zone, version=self.version,
                role=replica_role)
            th = threading.Thread(
                target=self._launch_replica,
                args=(replica_id, zone, is_spot, replica_role),
                name=f'serve-launch-{self.service_name}-{replica_id}',
                daemon=True)
            with self._lock:
                self._launch_threads[replica_id] = th
            th.start()

    def _replica_task(self, replica_id: int, port: int,
                      zone: Optional[str], is_spot: bool,
                      role: Optional[str] = None) -> task_lib.Task:
        task = task_lib.Task.from_yaml_config(self.task.to_yaml_config())
        task.service = None  # the replica runs the workload, not a service
        envs = {
            ENV_REPLICA_PORT: str(port),
            ENV_REPLICA_ID: str(replica_id),
            ENV_SERVICE_NAME: self.service_name,
        }
        if role is not None:
            # Disaggregated pool role: the inference server reads this
            # as its --role default (prefill replicas push KV pages,
            # decode replicas accept /v1/kv_adopt).
            envs[ENV_REPLICA_ROLE] = role
        if self.spec.tensor_parallel > 1:
            # The inference server reads this as its --tensor default:
            # the replica's engine shards over that many chips.
            envs[ENV_REPLICA_TENSOR] = str(self.spec.tensor_parallel)
        if self.spec.max_prompt_len is not None:
            # --max-prompt-len default: admission cap for long prompts
            # (chunked prefill serves anything up to the model limit).
            envs[ENV_REPLICA_MAX_PROMPT] = str(self.spec.max_prompt_len)
        if self.spec.kv_page_size is not None:
            # --kv-page-size default: paged KV cache + (by default)
            # the radix prefix cache on each replica's engine.
            envs[ENV_REPLICA_KV_PAGE] = str(self.spec.kv_page_size)
        if self.spec.kv_pages is not None:
            # --kv-pages default: pool size — THIS is where the
            # HBM-per-slot reservation actually shrinks.
            envs[ENV_REPLICA_KV_PAGES] = str(self.spec.kv_pages)
        if self.spec.prefix_cache is not None:
            envs[ENV_REPLICA_PREFIX_CACHE] = \
                str(int(self.spec.prefix_cache))
        if self.spec.kv_dtype is not None:
            # --kv-dtype default: int8 page quantization — halves the
            # per-token KV read on every replica's decode path.
            envs[ENV_REPLICA_KV_DTYPE] = self.spec.kv_dtype
        if self.spec.speculation is not None:
            # --spec-ngram default: self-speculative draft length k.
            envs[ENV_REPLICA_SPEC_NGRAM] = str(self.spec.speculation)
        task.update_envs(envs)
        res = task.any_resources
        overrides = {}
        if res.use_spot and not is_spot:
            overrides['use_spot'] = False  # on-demand fallback replica
        if zone is not None:
            overrides['infra'] = (
                f'{res.cloud}/{zone.rsplit("-", 1)[0]}/{zone}'
                if res.cloud else zone)
        if overrides:
            task.set_resources(res.copy(**overrides))
        return task

    def _pick_port(self) -> int:
        res = self.task.any_resources
        if res.cloud == 'local' or res.cloud is None:
            # Replicas share this host; every one needs its own port.
            return common_utils.find_free_port()
        if res.ports:
            return int(str(res.ports[0]).split('-')[0])
        return 8080

    def _launch_replica(self, replica_id: int, zone: Optional[str],
                        is_spot: bool,
                        role: Optional[str] = None) -> None:
        cluster = self._cluster_name(replica_id)
        port = self._pick_port()
        try:
            task = self._replica_task(replica_id, port, zone, is_spot,
                                      role)
            job_id, handle = execution.launch(
                task, cluster, detach_run=True, quiet_optimizer=True,
                policy_operation='serve')
            url = f'http://{handle.head_ip}:{port}'
            serve_state.set_replica_endpoint(self.service_name, replica_id,
                                             url, job_id)
            # Guarded: if the replica was terminated while we were
            # provisioning (scale-down or serve down racing the launch),
            # do not resurrect it — tear the fresh cluster down instead.
            if not serve_state.set_replica_status_if(
                    self.service_name, replica_id,
                    ReplicaStatus.PROVISIONING, ReplicaStatus.STARTING):
                logger.info(f'Service {self.service_name!r}: replica '
                            f'{replica_id} was terminated mid-provision; '
                            f'tearing its cluster down.')
                self._teardown_cluster(cluster)
                if self.spot_placer is not None:
                    self.spot_placer.handle_termination(zone)
                return
            logger.info(f'Service {self.service_name!r}: replica '
                        f'{replica_id} provisioned at {url}')
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'Service {self.service_name!r}: replica '
                           f'{replica_id} failed to provision: {e}')
            serve_state.set_replica_status_if(
                self.service_name, replica_id, ReplicaStatus.PROVISIONING,
                ReplicaStatus.FAILED)
            self._teardown_cluster(cluster)
            if self.spot_placer is not None:
                self.spot_placer.handle_termination(zone)

    # ----- scale down / terminate ---------------------------------------------
    def scale_down(self, n: int, role: Optional[str] = None) -> None:
        """Terminate n replicas, least-useful first: non-ready before
        ready, then newest first (reference scales down newest).
        `role` restricts the cut to one disaggregated pool — the
        per-pool autoscaler shrinks decode without touching
        prefill and vice versa."""
        replicas = serve_state.get_replicas(self.service_name)
        if role is not None:
            replicas = [r for r in replicas if r.get('role') == role]
        order = sorted(
            replicas,
            key=lambda r: (r['status'] is ReplicaStatus.READY,
                           -r['replica_id']))
        for rec in order[:n]:
            self.terminate_replica(rec['replica_id'])

    def terminate_replica(self, replica_id: int,
                          preempted: bool = False) -> None:
        rec = serve_state.get_replica(self.service_name, replica_id)
        if rec is None or rec['status'].is_terminal():
            return
        serve_state.set_replica_status(self.service_name, replica_id,
                                       ReplicaStatus.SHUTTING_DOWN)
        self._teardown_cluster(rec['cluster_name'])
        final = (ReplicaStatus.PREEMPTED if preempted
                 else ReplicaStatus.SHUTDOWN)
        serve_state.set_replica_status(self.service_name, replica_id,
                                       final)
        if preempted:
            from skypilot_tpu.server import metrics as metrics_lib
            metrics_lib.inc_counter('skytpu_serve_replica_preemptions_total',
                                    service=self.service_name)
        if self.spot_placer is not None and rec['is_spot']:
            if preempted:
                self.spot_placer.handle_preemption(rec['zone'])
            else:
                self.spot_placer.handle_termination(rec['zone'])
        self._probe_failures.pop(replica_id, None)

    def terminate_all(self) -> None:
        for rec in serve_state.get_replicas(self.service_name):
            self.terminate_replica(rec['replica_id'])

    # ----- rolling update -----------------------------------------------------
    def rollout_step(self) -> bool:
        """One tick of a rolling update; True while old-version
        replicas remain (the controller suspends autoscaling then).

        Surge-then-drain: launch new-version replicas up to the
        rollout target (max of min_replicas and either generation's
        live count — stateless, so a controller re-adopted mid-rollout
        just continues), then terminate old replicas at most as fast
        as new ones turn READY, so the LB never goes empty.
        """
        live = serve_state.get_replicas(self.service_name)
        old = [r for r in live if r['version'] < self.version]
        if not old:
            return False
        new = [r for r in live if r['version'] >= self.version]
        target = max(self.spec.min_replicas, len(old), len(new))
        if len(new) < target:
            logger.info(
                f'Service {self.service_name!r}: rolling update to '
                f'v{self.version} — surging {target - len(new)} new '
                f'replica(s) ({len(old)} old remain).')
            self.scale_up(target - len(new))
        ready_new = sum(1 for r in new
                        if r['status'] is ReplicaStatus.READY)
        ready_old = sum(1 for r in old
                        if r['status'] is ReplicaStatus.READY)
        # Drain budget = READY capacity SURPLUS above target (counting
        # both generations) — not the raw new-READY count, which would
        # re-spend the same new replicas every tick and drain below
        # target (or to zero) while later replacements are still
        # starting.
        budget = max(0, ready_new + ready_old - target)
        # Oldest first; non-READY old replicas cost no availability and
        # are drained immediately.
        for rec in sorted(old, key=lambda r: r['replica_id']):
            if rec['status'] is not ReplicaStatus.READY:
                self.terminate_replica(rec['replica_id'])
                continue
            if budget > 0:
                budget -= 1
                logger.info(
                    f'Service {self.service_name!r}: draining '
                    f'v{rec["version"]} replica {rec["replica_id"]} '
                    f'({ready_new} v{self.version} replica(s) READY).')
                self.terminate_replica(rec['replica_id'])
        return True

    def _teardown_cluster(self, cluster_name: str) -> None:
        record = global_user_state.get_cluster(cluster_name)
        if record is None:
            return
        try:
            self.backend.teardown(record['handle'], terminate=True)
        except Exception as e:  # pylint: disable=broad-except
            logger.warning(f'teardown of replica cluster '
                           f'{cluster_name!r} failed: {e}')
            if global_user_state.get_cluster(cluster_name) is not None:
                global_user_state.remove_cluster(cluster_name)

    # ----- probing / reconciliation -------------------------------------------
    def _probe_url(self, url: str) -> bool:
        probe = self.spec.readiness_probe
        target = url.rstrip('/') + probe.path
        try:
            if probe.post_data is not None:
                resp = requests_lib.post(target, json=probe.post_data,
                                         timeout=probe.timeout_seconds)
            else:
                resp = requests_lib.get(target,
                                        timeout=probe.timeout_seconds)
            return 200 <= resp.status_code < 300
        except requests_lib.RequestException:
            return False

    def probe_and_reconcile(self, now: float) -> None:
        """One controller tick: detect preemptions, probe readiness,
        replace replicas that failed their probes for too long."""
        for rec in serve_state.get_replicas(self.service_name):
            rid = rec['replica_id']
            status = rec['status']
            if status is ReplicaStatus.PROVISIONING or \
                    status is ReplicaStatus.SHUTTING_DOWN:
                continue
            # Cloud-truth reconcile first: a preempted slice must be
            # deleted and replaced, not probed.
            cl_status = backend_utils.refresh_cluster_status(
                rec['cluster_name'])
            if cl_status is not ClusterStatus.UP:
                logger.warning(
                    f'Service {self.service_name!r}: replica {rid} '
                    f'cluster lost (status={cl_status}); replacing.')
                self.terminate_replica(rid, preempted=True)
                continue
            # Workload exited? A dead server process is a failure even if
            # the cluster is healthy.
            if rec['cluster_job_id'] is not None and \
                    self._job_failed(rec):
                logger.warning(f'Service {self.service_name!r}: replica '
                               f'{rid} workload exited; replacing.')
                self.terminate_replica(rid)
                serve_state.set_replica_status(self.service_name, rid,
                                               ReplicaStatus.FAILED)
                continue
            ok = rec['url'] is not None and self._probe_url(rec['url'])
            if ok:
                self._probe_failures[rid] = 0
                if status is not ReplicaStatus.READY:
                    serve_state.set_replica_status(
                        self.service_name, rid, ReplicaStatus.READY)
                    logger.info(f'Service {self.service_name!r}: replica '
                                f'{rid} READY')
                continue
            failures = self._probe_failures.get(rid, 0) + 1
            self._probe_failures[rid] = failures
            if status is ReplicaStatus.STARTING:
                # Grace is judged in PROBE ATTEMPTS as well as wall
                # clock: the replica must have actually been probed as
                # often as an unstarved clock would have allowed.  Under
                # host CPU starvation (heavily loaded CI box) controller
                # ticks stretch, attempts accumulate slowly and the
                # window stretches with the machine — a wall-clock-only
                # deadline replaces perfectly healthy-but-slow replicas,
                # and each replacement adds churn that makes the
                # starvation worse.
                from skypilot_tpu.serve import controller as controller_m
                delay = self.spec.readiness_probe.initial_delay_seconds
                # Worst-case cost of one failed attempt is a full probe
                # TIMEOUT plus the tick; dividing by the tick alone would
                # demand more attempts than an unstarved host can make
                # within the delay (black-holed endpoints would then sit
                # unreplaced for timeout/tick times longer than asked).
                per_attempt = (controller_m._tick_interval() +  # pylint: disable=protected-access
                               self.spec.readiness_probe.timeout_seconds)
                expected_attempts = max(
                    3, int(delay / max(per_attempt, 0.05)))
                if (now - rec['launched_at'] > delay and
                        failures >= expected_attempts):
                    logger.warning(
                        f'Service {self.service_name!r}: replica {rid} '
                        f'never became ready within initial delay '
                        f'({failures} failed probes); replacing.')
                    self.terminate_replica(rid)
                    serve_state.set_replica_status(
                        self.service_name, rid, ReplicaStatus.FAILED)
                continue
            if failures >= _REPLACE_THRESHOLD:
                logger.warning(f'Service {self.service_name!r}: replica '
                               f'{rid} failed {failures} probes; '
                               f'replacing.')
                self.terminate_replica(rid)
                serve_state.set_replica_status(self.service_name, rid,
                                               ReplicaStatus.FAILED)
            elif failures >= _NOT_READY_THRESHOLD and \
                    status is ReplicaStatus.READY:
                serve_state.set_replica_status(self.service_name, rid,
                                               ReplicaStatus.NOT_READY)

    def _job_failed(self, rec: dict) -> bool:
        record = global_user_state.get_cluster(rec['cluster_name'])
        if record is None:
            return False
        client = self.backend._agent_client(record['handle'])  # pylint: disable=protected-access
        try:
            job = client.get_job(rec['cluster_job_id'])
        except Exception:  # pylint: disable=broad-except
            return False  # transient agent hiccup; the probe decides
        finally:
            client.close()
        if job is None:
            return False
        from skypilot_tpu.agent.job_queue import JobStatus
        return JobStatus(job['status']).is_terminal()

    # ----- views --------------------------------------------------------------
    def _replica_rows(self) -> List[dict]:
        """Cached live-replica snapshot backing the read-only views
        (ready_replicas / num_live).

        These views are hammered: the controller's decision loop calls
        them several times per tick, and without the snapshot every
        call re-queries the full replicas table.  The snapshot is keyed on
        serve_state.replicas_version() (exact invalidation: any
        replica write in this process bumps it) plus the
        SKYTPU_READY_VIEW_TTL_S backstop for out-of-process writers.
        Callers must not mutate the returned rows."""
        from skypilot_tpu.server import metrics as metrics_lib
        version = serve_state.replicas_version()
        cached = self._view_cache
        if (_READY_VIEW_TTL_S > 0 and cached is not None and
                cached[0] == version and
                time.monotonic() - cached[1] <= _READY_VIEW_TTL_S):
            metrics_lib.inc_counter(
                'skytpu_serve_ready_view_cache_total', result='hit')
            return cached[2]
        metrics_lib.inc_counter(
            'skytpu_serve_ready_view_cache_total', result='miss')
        rows = serve_state.get_replicas(self.service_name)
        self._view_cache = (version, time.monotonic(), rows)
        return rows

    def ready_urls(self) -> List[str]:
        return [url for _, url, _ in self.ready_replicas()]

    def ready_replicas(self) -> List[Tuple[int, str, Optional[str]]]:
        """(replica_id, url, role) triples for READY replicas — the LB
        labels per-replica metric series, federates /metrics, and
        splits disaggregated pools from these (role None =
        monolithic)."""
        return [
            (r['replica_id'], r['url'], r.get('role'))
            for r in self._replica_rows()
            if r['status'] is ReplicaStatus.READY and r['url']
        ]

    def num_live(self, role: Optional[str] = None) -> int:
        return sum(
            1 for r in self._replica_rows()
            if r['status'].counts_toward_target() and
            (role is None or r.get('role') == role))
