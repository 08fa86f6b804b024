"""A training cell: `Trainer.run` fed seeded rows, the first three steps
followed by the plain reference, and the window timed at its boundaries."""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import families
from benchmarks.harness import traffic, weights
from benchmarks.harness.serve import (Tracer, counters, memory_peak_bytes,
                                      note)

CHECK_STEPS = 3


class WindowClosed(Exception):
    """Raised by the feed in place of the next batch once the time is up."""


class Feed:
    """The trainer's data iterator: seeded rows, kept for the reference
    while the first steps run, and a clock that ends the window."""

    def __init__(self, batches):
        self._batches = batches
        self.kept = []
        self.close_at = None
        self.hooks = []                 # [(clock time, callable)], sorted

    def __iter__(self):
        return self

    def __next__(self):
        now = time.perf_counter()
        while self.hooks and self.hooks[0][0] <= now:
            self.hooks.pop(0)[1]()
        if self.close_at is not None and now >= self.close_at:
            raise WindowClosed
        batch = next(self._batches)
        if self.close_at is None:
            self.kept.append(batch)
        return batch


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))(
            tree)
    return {k: float(v) for k, v in weights.flat(
        jax.device_get(norms)).items()}


def _adam_mu(opt_state):
    import jax
    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, 'mu')):
        if hasattr(node, 'mu'):
            return node.mu
    raise SystemExit('no Adam moments in the optimizer state')


def build_trainer(family, config, dims, seed, devices, first_batch):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    t = config['train']
    mesh = build_mesh(plan_mesh(len(devices), fsdp=t['fsdp']), devices)
    model = families.need(
        family, 'train_model', 'a training mix needs the module that '
        '`Trainer` is handed')(dims, config, mesh, first_batch.shape[1])
    trainer = Trainer(model, mesh, jax.random.PRNGKey(0), first_batch,
                      TrainConfig(**t['optimizer']))
    # The benchmark's own seeded weights in the trainer's place and
    # shardings: the reference makes the same ones again from the seed.
    make = jax.jit(lambda k: family.make_params(k, dims, jnp.float32),
                   out_shardings=trainer.shardings.params)
    trainer.state = trainer.state.replace(params=make(weights.seed_key(seed)))
    delta = jax.jit(
        lambda p, k: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p,
            family.make_params(k, dims, jnp.float32)),
        in_shardings=(trainer.shardings.params, None))
    return trainer, delta


def run_cell(*, family, config, mix, dims, seed, seconds, traced, devices,
             control=False, step_wrapper=None):
    """Returns (ctx for the readers, result fields).  `step_wrapper` is for
    the test that breaks the timed path underneath."""
    import jax
    import jax.numpy as jnp

    rows = int(mix['sequences_per_step'])
    feed = Feed(traffic.train_batches(mix, seed, dims.vocab, rows))
    first = np.zeros((rows, int(mix['seq_len'])), np.int32)  # a shape only
    t = time.perf_counter()
    trainer, delta_fn = build_trainer(family, config, dims, seed, devices,
                                      first)
    t = note('trainer built, seeded weights in place', t)
    if step_wrapper is not None:
        trainer.train_step = step_wrapper(trainer.train_step)
    opt = config['train']['optimizer']
    program = {'losses': []}
    failed = 0

    def first_steps_log(m):
        program['losses'].append(float(m['loss']))
        if len(program['losses']) == 1:
            mu = _leaf_norms(_adam_mu(trainer.state.opt_state))
            program['grad_norms'] = {k: v / (1.0 - opt['b1'])
                                     for k, v in mu.items()}

    trainer.run(feed, num_steps=CHECK_STEPS, log_every=1,
                log_fn=first_steps_log)
    program['delta_norms'] = {k: float(v) for k, v in weights.flat(
        jax.device_get(delta_fn(trainer.state.params,
                                weights.seed_key(seed)))).items()}
    t = note('first three steps (compile) and their norms', t)
    stamps = []
    tracer = Tracer() if traced else None
    before = counters()
    t_open = time.perf_counter()
    feed.close_at = t_open + seconds
    if tracer:
        at = t_open + float(mix.get('trace_at_share', 0.4)) * seconds
        feed.hooks = [(at, tracer.start),
                      (at + float(mix.get('trace_s', 3.0)), tracer.stop)]
    try:
        trainer.run(feed, num_steps=10**9, log_every=1,
                    log_fn=lambda m: stamps.append(time.perf_counter()))
    except WindowClosed:
        pass
    except Exception as e:  # pylint: disable=broad-except
        print(f'a step raised: {e!r}')
        failed += 1
    after = counters()
    peak = memory_peak_bytes(devices)
    inside = [s for s in stamps if s <= t_open + seconds]
    tokens_per_step = rows * int(mix['seq_len'])
    rate = ((len(inside) - 1) * tokens_per_step / (inside[-1] - inside[0])
            if len(inside) > 1 else None)
    trace = tracer.collect() if tracer else None
    kept = feed.kept[:CHECK_STEPS]
    del trainer, delta_fn
    gc.collect()
    jax.clear_caches()
    t = note('window', t)
    reference = family.reference(dims, seed, jnp.float32).first_steps(
        kept, opt, devices)
    note('reference, three steps', t)
    verdict, correct = compare(program, reference, config['check'])
    if control:
        low = family.reference(
            dims, seed, jnp.float32, config['check']['control']).first_steps(
                kept, opt, devices)
        print(f'control ({config["check"]["control"]}):')
        verdict['control'], _ = compare(low, reference, config['check'])
    ctx = {
        'samples': {'train_tokens_per_s': [rate] if rate else []},
        'spans': {}, 'records': [], 'trace': trace, 'trace_span': None,
        'counters': {k: after.get(k, 0.0) - before.get(k, 0.0)
                     for k in after},
        'memory_peak_bytes': peak,
    }
    info = {'correct': bool(correct and not failed and rate),
            'attempted': len(stamps) + failed, 'failed': failed,
            't_open': t_open, 'check': verdict}
    return ctx, info


def worst_leaf_gap(program: dict, reference: dict) -> dict:
    """The gap between the program's norm and the reference's, leaf by
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger; the worst leaf, and (steadier from seed to seed)
    the mean over the leaves."""
    median = float(np.median(list(reference.values())))
    gaps = {name: abs(program[name] - ref) / max(ref, median)
            for name, ref in reference.items()}
    where = max(gaps, key=gaps.get)
    return {'gap': gaps[where], 'leaf': where,
            'mean': float(np.mean(list(gaps.values())))}


def compare(program: dict, reference: dict, limits: dict):
    """Each number compared, printed beside its limit."""
    loss_gaps = [abs(a - b) / abs(b) for a, b in
                 zip(program['losses'], reference['losses'])]
    grad = worst_leaf_gap(program['grad_norms'], reference['grad_norms'])
    delta = worst_leaf_gap(program['delta_norms'], reference['delta_norms'])
    verdict = {
        'loss_rel_gap': max(loss_gaps) if len(loss_gaps) == CHECK_STEPS
        else float('inf'),
        'grad_norm_gap': grad['gap'], 'grad_norm_leaf': grad['leaf'],
        'grad_norm_mean_gap': grad['mean'],
        'delta_norm_gap': delta['gap'], 'delta_norm_leaf': delta['leaf'],
        'delta_norm_mean_gap': delta['mean'],
        'losses': program['losses'], 'reference_losses': reference['losses'],
    }
    ok = True
    verdict['limits'] = {}
    for name, limit_key in (('loss_rel_gap', 'loss_rel_limit'),
                            ('grad_norm_gap', 'grad_norm_limit'),
                            ('grad_norm_mean_gap', 'grad_norm_mean_limit'),
                            ('delta_norm_gap', 'delta_norm_limit')):
        value, limit = verdict[name], limits[limit_key]
        verdict['limits'][name] = limit
        print(f'correct: {name} {value} (limit {limit})')
        ok = ok and bool(np.isfinite(value)) and value <= limit
    return verdict, ok
