"""Optimizer — cost/time placement search (parity: sky/optimizer.py).

Same contract as the reference `Optimizer.optimize(dag, minimize=COST|TIME)`
(sky/optimizer.py:71): for every task, enumerate concrete launchable
candidates across enabled clouds (`_fill_in_launchable_resources`,
reference :1319), estimate per-candidate cost and run time, then pick the
globally optimal assignment.  Chain DAGs use exact DP over (task, candidate)
states with inter-task egress edge costs (reference :429); general DAGs use
exact branch-and-bound over the same state space (the reference uses a pulp
ILP, :490 — pulp is not in this environment; DAGs are small enough for an
exact search with an admissible bound).

TPU-native twist: TIME minimization uses the slice's aggregate bf16 FLOP/s
from the accelerator registry to scale estimated runtimes, so `minimize=TIME`
naturally prefers bigger/newer slices, and a `$/1M-tokens`-style efficiency
metric (cost x time) is reported in the comparison table.
"""
from __future__ import annotations

import collections
import enum
from typing import Dict, List, Optional, Tuple

from skypilot_tpu import clouds as clouds_lib
from skypilot_tpu import dag as dag_lib
from skypilot_tpu import exceptions
from skypilot_tpu import resources as resources_lib
from skypilot_tpu import task as task_lib
from skypilot_tpu.utils import common_utils
from skypilot_tpu.utils import ux_utils

_DEFAULT_RUNTIME_S = 3600.0  # assumed run time when the task gives none


class OptimizeTarget(enum.Enum):
    COST = 'cost'
    TIME = 'time'
    # $/effective-FLOP: hourly cost divided by delivered bf16 compute
    # (aggregate peak x assumed MFU).  For a fixed training workload
    # this ranks placements exactly like $/1M-tokens — the
    # model-dependent tokens/FLOP factor is a constant across
    # candidates — so it is the cost-per-token objective without
    # needing the model size (SURVEY §7's north-star metric).
    COST_PER_FLOP = 'cost_per_flop'


# Fraction of peak the optimizer assumes a tuned workload achieves: a
# planning constant (BASELINE.json's north-star bar), not a measurement.
ASSUMED_MFU = 0.45


def effective_tflops(candidate: 'resources_lib.Resources',
                     num_nodes: int = 1) -> Optional[float]:
    """Delivered bf16 TFLOP/s of a placement (peak x assumed MFU), or
    None for non-TPU candidates."""
    tpu = candidate.tpu
    if tpu is None:
        return None
    # TpuType.bf16_tflops is ONE slice's aggregate (per-chip x chips);
    # multislice (xN) requests deliver N slices per logical node.
    return tpu.bf16_tflops * ASSUMED_MFU * num_nodes * tpu.num_slices


def cost_per_million_tokens(candidate: 'resources_lib.Resources',
                            hourly_cost: float,
                            params_billion: float,
                            num_nodes: int = 1,
                            mfu: float = ASSUMED_MFU) -> Optional[float]:
    """Training $/1M tokens for a dense model of `params_billion`
    parameters at `mfu` (6·N FLOPs/token), on this placement (public
    what-if helper for planning)."""
    tpu = candidate.tpu
    if tpu is None or params_billion <= 0:
        return None
    flops_per_s = tpu.bf16_tflops * 1e12 * mfu * num_nodes * tpu.num_slices
    tokens_per_s = flops_per_s / (6.0 * params_billion * 1e9)
    return hourly_cost / 3600.0 / tokens_per_s * 1e6


def _blocked(candidate: resources_lib.Resources,
             blocked_resources: Optional[List[resources_lib.Resources]]
             ) -> bool:
    """A candidate is blocked if it matches any blocked entry on every field
    the entry pins (the failover engine blocks zones/regions this way)."""
    if not blocked_resources:
        return False
    for b in blocked_resources:
        if b.cloud is not None and b.cloud != candidate.cloud:
            continue
        if b.region is not None and b.region != candidate.region:
            continue
        if b.zone is not None and b.zone != candidate.zone:
            continue
        if (b.accelerator_name is not None and
                b.accelerator_name != candidate.accelerator_name):
            continue
        return True
    return False


def _hourly_cost_memo(memo: Optional[dict]):
    """Candidate→$/hr with memoization (Resources is hashable); the catalog
    scan behind hourly_cost is pandas-filter-per-call, so one optimize pass
    should price each candidate exactly once."""
    memo = memo if memo is not None else {}

    def cost(candidate: resources_lib.Resources) -> float:
        if candidate not in memo:
            memo[candidate] = clouds_lib.get_cloud(
                candidate.cloud).hourly_cost(candidate)
        return memo[candidate]

    return cost


def fill_in_launchable_resources(
    task: task_lib.Task,
    blocked_resources: Optional[List[resources_lib.Resources]] = None,
    cost_memo: Optional[dict] = None,
) -> Dict[resources_lib.Resources, List[resources_lib.Resources]]:
    """Per requested Resources, concrete launchable candidates (cheapest
    first) across enabled clouds (reference: sky/optimizer.py:1319)."""
    enabled = clouds_lib.enabled_clouds()
    if not enabled:
        raise exceptions.NoCloudAccessError(
            'No cloud is enabled. Configure GCP credentials or use '
            "infra: local.")
    out: Dict[resources_lib.Resources,
              List[resources_lib.Resources]] = collections.OrderedDict()
    for request in task.resources:
        candidates: List[resources_lib.Resources] = []
        for cloud in enabled:
            if request.cloud is not None and request.cloud != cloud.NAME:
                continue
            if (request.use_spot and not cloud.supports(
                    clouds_lib.CloudCapability.SPOT)):
                continue
            if (task.num_nodes > 1 and not cloud.supports(
                    clouds_lib.CloudCapability.MULTI_NODE)):
                continue
            candidates.extend(cloud.get_feasible_resources(request))
        candidates = [
            c for c in candidates if not _blocked(c, blocked_resources)
        ]
        cost = _hourly_cost_memo(cost_memo)
        candidates.sort(key=lambda c: cost(c) * task.num_nodes)
        out[request] = candidates
    return out


def _estimate_runtime_s(task: task_lib.Task,
                        candidate: resources_lib.Resources) -> float:
    """Estimated run seconds on this candidate.

    If the task provides `estimated_runtime_s`, it is interpreted as the run
    time on the *smallest* feasible slice; candidates with more aggregate
    bf16 FLOP/s scale it down proportionally (ideal-scaling assumption, same
    simplification the reference makes with its per-accelerator time
    estimator hooks).
    """
    base = task.estimated_runtime_s or _DEFAULT_RUNTIME_S
    tpu = candidate.tpu
    if tpu is None or task.estimated_runtime_s is None:
        return base
    # Normalize against the least-capable requested slice.
    min_tflops = None
    for req in task.resources:
        if req.tpu is not None:
            tflops = req.tpu.bf16_tflops * req.tpu.num_slices
            min_tflops = tflops if min_tflops is None else min(
                min_tflops, tflops)
    if not min_tflops:
        return base
    return base * min_tflops / (tpu.bf16_tflops * tpu.num_slices)


def _egress_cost(src: Optional[resources_lib.Resources],
                 dst: resources_lib.Resources,
                 num_gb: float) -> float:
    """Edge cost for moving `num_gb` from src's placement to dst's
    (reference egress model: sky/optimizer.py:75-105)."""
    if src is None or num_gb <= 0:
        return 0.0
    if src.cloud == dst.cloud:
        if src.region == dst.region:
            return 0.0
        return 0.01 * num_gb  # intra-cloud cross-region
    return clouds_lib.get_cloud(src.cloud).egress_cost(num_gb)


class Optimizer:
    """Chooses the best concrete placement for every task in a DAG."""

    @classmethod
    def optimize(
        cls,
        dag: dag_lib.Dag,
        minimize: OptimizeTarget = OptimizeTarget.COST,
        blocked_resources: Optional[List[resources_lib.Resources]] = None,
        quiet: bool = False,
    ) -> dag_lib.Dag:
        dag.validate()
        if dag.is_chain():
            cls._optimize_chain(dag, minimize, blocked_resources)
        else:
            cls._optimize_general(dag, minimize, blocked_resources)
        if not quiet:
            cls.print_optimized_plan(dag, minimize)
        return dag

    # ----- candidate scoring -------------------------------------------------
    @classmethod
    def _candidates_with_metrics(
        cls, task: task_lib.Task,
        blocked_resources: Optional[List[resources_lib.Resources]],
    ) -> List[Tuple[resources_lib.Resources, float, float, float]]:
        """[(candidate, cost_$, time_s, hourly_$)] for all feasible
        placements."""
        memo: dict = {}
        per_request = fill_in_launchable_resources(task, blocked_resources,
                                                   cost_memo=memo)
        hourly_of = _hourly_cost_memo(memo)
        out = []
        for _, candidates in per_request.items():
            for c in candidates:
                time_s = _estimate_runtime_s(task, c)
                cost = hourly_of(c) * task.num_nodes * time_s / 3600.0
                out.append((c, cost, time_s, hourly_of(c)))
        if not out:
            raise exceptions.ResourcesUnavailableError(
                f'No launchable resources satisfy task {task.name!r}: '
                f'{[str(r) for r in task.resources]}'
                + (f' (blocked: {len(blocked_resources)})'
                   if blocked_resources else ''))
        return out

    @staticmethod
    def _objective(minimize: OptimizeTarget, task: task_lib.Task,
                   cand: resources_lib.Resources, cost: float,
                   time_s: float, hourly: float) -> float:
        if minimize is OptimizeTarget.TIME:
            return time_s
        if minimize is OptimizeTarget.COST_PER_FLOP:
            eff = effective_tflops(cand, task.num_nodes)
            if eff is not None:
                return hourly * task.num_nodes / eff
            if any(r.is_tpu for r in task.resources):
                # Mixed TPU/CPU candidate sets must not compare
                # incomparable units: a CPU placement delivers no
                # training FLOPs, so it can never win this objective.
                return float('inf')
            # Pure non-TPU task: $ decides.
            return cost
        return cost

    # ----- chain DP ----------------------------------------------------------
    @classmethod
    def _optimize_chain(
        cls, dag: dag_lib.Dag, minimize: OptimizeTarget,
        blocked_resources: Optional[List[resources_lib.Resources]],
    ) -> None:
        """Exact DP over (task, candidate) with egress edge costs
        (reference: sky/optimizer.py:429 `_optimize_by_dp`)."""
        tasks = dag.topological_order()
        if not tasks:
            return
        all_cands = [
            cls._candidates_with_metrics(t, blocked_resources) for t in tasks
        ]
        # dp[i][j] = (best objective to schedule tasks[:i+1] with tasks[i] on
        # candidate j, parent index)
        dp: List[List[Tuple[float, int]]] = []
        first = []
        for cand, cost, time_s, hourly in all_cands[0]:
            first.append((cls._objective(minimize, tasks[0], cand, cost,
                                         time_s, hourly), -1))
        dp.append(first)
        for i in range(1, len(tasks)):
            out_gb = getattr(tasks[i - 1], 'estimated_output_gb', None) or 0.0
            row = []
            for cand, cost, time_s, hourly in all_cands[i]:
                node_obj = cls._objective(minimize, tasks[i], cand, cost,
                                          time_s, hourly)
                best = (float('inf'), -1)
                for j, (prev_obj, _) in enumerate(dp[i - 1]):
                    prev_cand = all_cands[i - 1][j][0]
                    egress = _egress_cost(prev_cand, cand, out_gb)
                    # Egress is $; it only composes with the $ objective.
                    obj = prev_obj + node_obj + (
                        egress if minimize is OptimizeTarget.COST else 0.0)
                    if obj < best[0]:
                        best = (obj, j)
                row.append(best)
            dp.append(row)
        # Backtrack.
        last = min(range(len(dp[-1])), key=lambda j: dp[-1][j][0])
        for i in range(len(tasks) - 1, -1, -1):
            tasks[i].best_resources = all_cands[i][last][0]
            last = dp[i][last][1]

    # Expansion cap for the exact search: beyond this the incumbent
    # (greedy) assignment is kept.  DAGs here are small (the reference's
    # pulp ILP solves the same shape, sky/optimizer.py:490); the cap is
    # a safety net against pathological candidate fan-out, not a tuning
    # knob.
    _BNB_MAX_EXPANSIONS = 2_000_000

    @classmethod
    def _optimize_general(
        cls, dag: dag_lib.Dag, minimize: OptimizeTarget,
        blocked_resources: Optional[List[resources_lib.Resources]],
    ) -> None:
        """Exact search for non-chain DAGs: branch-and-bound over
        per-task candidate sets with egress edge costs.

        The reference solves this placement as a pulp ILP
        (sky/optimizer.py:490-543); pulp is not in this environment, and
        the DAGs are small, so an exact DFS with an admissible lower
        bound (remaining tasks' best node objectives; egress >= 0) finds
        the same optimum.  Seeded with the per-task greedy incumbent so
        pruning bites immediately; candidates are explored best-node-
        objective-first.
        """
        tasks = dag.topological_order()
        if not tasks:
            return
        index_of = {t: i for i, t in enumerate(tasks)}
        # Edges as (src_idx, dst_idx, out_gb); egress composes with the
        # $ objective only (chain DP does the same).
        charge_egress = minimize is OptimizeTarget.COST
        edges = []
        if charge_egress:
            for u, v in dag.graph.edges:
                out_gb = getattr(u, 'estimated_output_gb', None) or 0.0
                if out_gb > 0:
                    edges.append((index_of[u], index_of[v], out_gb))
        in_edges: List[List[Tuple[int, float]]] = [[] for _ in tasks]
        for src, dst, gb in edges:
            in_edges[dst].append((src, gb))

        # Per task: candidates sorted by node objective (ascending).
        cands: List[List[Tuple[resources_lib.Resources, float]]] = []
        for t in tasks:
            scored = [(c, cls._objective(minimize, t, c, cost, time_s,
                                         hourly))
                      for c, cost, time_s, hourly in
                      cls._candidates_with_metrics(t, blocked_resources)]
            scored.sort(key=lambda x: x[1])
            cands.append(scored)
        # Admissible remaining-cost bound: best node objective per
        # not-yet-assigned suffix (egress is non-negative).
        suffix_min = [0.0] * (len(tasks) + 1)
        for i in range(len(tasks) - 1, -1, -1):
            suffix_min[i] = suffix_min[i + 1] + cands[i][0][1]

        # Greedy incumbent (the previous fallback behavior).
        best_assign = [0] * len(tasks)
        best_obj = 0.0
        for i in range(len(tasks)):
            best_obj += cands[i][0][1]
            for src, gb in in_edges[i]:
                best_obj += _egress_cost(cands[src][best_assign[src]][0],
                                         cands[i][0][0], gb)

        assign = [0] * len(tasks)
        expansions = 0

        def dfs(i: int, partial: float) -> None:
            nonlocal best_obj, best_assign, expansions
            if expansions > cls._BNB_MAX_EXPANSIONS:
                return
            if i == len(tasks):
                if partial < best_obj:
                    best_obj = partial
                    best_assign = list(assign)
                return
            for j, (cand, node_obj) in enumerate(cands[i]):
                expansions += 1
                obj = partial + node_obj
                for src, gb in in_edges[i]:
                    obj += _egress_cost(cands[src][assign[src]][0], cand,
                                        gb)
                if obj + suffix_min[i + 1] >= best_obj:
                    # Candidates are node-objective-sorted, but egress
                    # varies per candidate — later ones can still win,
                    # so prune this branch only, not the whole level.
                    continue
                assign[i] = j
                dfs(i + 1, obj)
            assign[i] = 0

        dfs(0, 0.0)
        for i, t in enumerate(tasks):
            t.best_resources = cands[i][best_assign[i]][0]

    # ----- reporting ---------------------------------------------------------
    @classmethod
    def print_optimized_plan(cls, dag: dag_lib.Dag,
                             minimize: OptimizeTarget) -> None:
        rows = []
        total_cost = 0.0
        for t in dag.tasks:
            best = t.best_resources
            if best is None:
                continue
            hourly = clouds_lib.get_cloud(best.cloud).hourly_cost(best)
            time_s = _estimate_runtime_s(t, best)
            cost = hourly * t.num_nodes * time_s / 3600.0
            total_cost += cost
            tpu = best.tpu
            chips = tpu.num_chips if tpu else '-'
            eff = effective_tflops(best, t.num_nodes)
            eff_col = (f'${hourly * t.num_nodes / (eff / 1000):.2f}'
                       if eff else '-')
            rows.append([
                t.name or '-', str(best.infra),
                best.accelerator_name or best.instance_type or 'cpu',
                str(chips), f'{t.num_nodes}',
                f'${hourly * t.num_nodes:.2f}',
                eff_col,
                common_utils.readable_time_duration(time_s),
                f'${cost:.2f}',
            ])
        header = ['TASK', 'INFRA', 'ACCELERATOR', 'CHIPS', 'NODES',
                  '$/HR', '$/EFF-PFLOPS-HR', 'EST.TIME', 'EST.COST']
        title = (f'Optimizer target: {minimize.value}  '
                 f'(plan total: ${total_cost:.2f})')
        ux_utils.print_table(header, rows, title=title)
