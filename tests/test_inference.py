"""Decode-engine tests: continuous batching must reproduce naive
full-forward greedy generation exactly (same argmax tokens), including
when requests are admitted mid-flight into a running decode batch."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
from served_utils import declaring

CFG = LLAMA_CONFIGS['tiny']


@pytest.fixture(scope='module')
def model_and_params():
    model = Llama(CFG)
    params = init_params(model, jax.random.PRNGKey(0))['params']
    return model, params


def naive_greedy(model, params, prompt_ids, n_new):
    """Reference: full forward over the growing sequence each step."""
    ids = list(prompt_ids)
    for _ in range(n_new):
        logits = model.apply({'params': params},
                             jnp.asarray([ids], jnp.int32))
        ids.append(int(jnp.argmax(logits[0, -1])))
    return ids[len(prompt_ids):]


def test_engine_matches_naive_greedy(model_and_params):
    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8, 16)))
    prompt = [5, 17, 3, 42, 9]
    want = naive_greedy(model, params, prompt, 8)
    req = engine.submit(prompt, 8)
    while req.finished_at is None:
        engine.step()
    assert req.tokens() == want


def test_engine_continuous_batching_staggered(model_and_params):
    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8, 16)))
    p1, p2 = [1, 2, 3], [7, 8, 9, 10, 11, 12]
    want1 = naive_greedy(model, params, p1, 10)
    want2 = naive_greedy(model, params, p2, 6)
    r1 = engine.submit(p1, 10)
    # Let r1 decode a few tokens before admitting r2 into the other slot.
    for _ in range(3):
        engine.step()
    r2 = engine.submit(p2, 6)
    while r1.finished_at is None or r2.finished_at is None:
        engine.step()
    assert r1.tokens() == want1
    assert r2.tokens() == want2


def test_engine_batched_admission_burst(model_and_params):
    """A burst of requests admitted in one step() — mixed buckets, odd
    group sizes (exercises the power-of-two padding rows) — must each
    reproduce naive greedy exactly."""
    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=8, prefill_buckets=(8, 16),
                                       steps_per_call=2))
    prompts = [[1, 2, 3],                      # bucket 8
               [4, 5, 6, 7, 8],                # bucket 8
               [9, 10, 11],                    # bucket 8 (group of 3)
               list(range(20, 30)),            # bucket 16
               [13, 14, 15, 16, 17, 18, 19, 20, 21]]   # bucket 16
    wants = [naive_greedy(model, params, p, 6) for p in prompts]
    reqs = [engine.submit(p, 6) for p in prompts]
    # All five must be admitted by the FIRST step (burst admission).
    engine.step()
    assert sum(s is not None for s in engine._slots) == 5
    while any(r.finished_at is None for r in reqs):
        engine.step()
    assert [r.tokens() for r in reqs] == wants


def test_engine_slot_reuse_no_kv_leak(model_and_params):
    # A request admitted into a previously-used slot must generate
    # exactly what it would in a fresh engine (insert overwrites the
    # whole slot cache).
    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=1, prefill_buckets=(8,)))
    first = engine.submit([4, 4, 4, 4, 4, 4, 4, 4], 5)
    while first.finished_at is None:
        engine.step()
    prompt = [9, 1, 9]
    want = naive_greedy(model, params, prompt, 5)
    second = engine.submit(prompt, 5)
    while second.finished_at is None:
        engine.step()
    assert second.tokens() == want


def test_engine_eos_and_max_len(model_and_params):
    model, params = model_and_params
    want = naive_greedy(model, params, [3, 1], 12)
    # Pick an eos whose FIRST occurrence is mid-stream so the stop point
    # is unambiguous; fall back to never-stopping if generation is cyclic.
    stop_at = next((i for i in range(1, len(want))
                    if want[i] not in want[:i]), None)
    eos = want[stop_at] if stop_at is not None else -1
    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=1, prefill_buckets=(8,), eos_id=eos))
    req = engine.submit([3, 1], 12)
    while req.finished_at is None:
        engine.step()
    got = req.tokens()
    if stop_at is not None:
        assert got == want[:stop_at + 1]   # stops ON the eos token
    else:
        assert got == want
    # max_seq_len cap: prompt + new capped to model max (128)
    req2 = engine.submit([3, 1], 10_000)
    assert req2.max_new_tokens == CFG.max_seq_len - 2


def test_engine_threaded_loop(model_and_params):
    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8,)))
    engine.start()
    try:
        want = naive_greedy(model, params, [2, 4, 6], 5)
        reqs = [engine.submit([2, 4, 6], 5) for _ in range(4)]
        outs = [r.tokens() for r in reqs]
        assert all(o == want for o in outs)
    finally:
        engine.stop()


@pytest.mark.parametrize('arriving', [3, 4])
def test_idle_loop_gathers_requests_that_arrive_together(
        model_and_params, monkeypatch, arriving):
    """Requests that reach an idle engine one look apart are prefilled as
    one group (fewer than the slots: once the queue stops growing; as many
    as the slots: at once), not as the one the first look saw and the
    rest a whole prefill behind it.  The loop's own idle sleep submits
    them, so every look finds the queue one longer."""
    from skypilot_tpu.inference import engine as engine_lib

    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=4, prefill_buckets=(8,)))
    groups, reqs = [], []
    admit_group = engine._admit_group
    monkeypatch.setattr(engine, '_admit_group', lambda bucket, group: (
        groups.append(len(group)), admit_group(bucket, group))[1])
    sleep = time.sleep

    def sleep_and_submit(seconds):
        if len(reqs) < arriving and threading.current_thread() is engine._thread:
            reqs.append(engine.submit([2, 4, 6], 3))
        sleep(seconds)

    monkeypatch.setattr(engine_lib.time, 'sleep', sleep_and_submit)
    engine.start()
    try:
        deadline = time.time() + 60
        while len(reqs) < arriving and time.time() < deadline:
            sleep(0.01)
        assert all(len(r.tokens()) == 3 for r in reqs)
    finally:
        engine.stop()
    assert groups == [arriving]


def test_engine_rejects_oversized_prompt(model_and_params):
    model, params = model_and_params
    # Model max_seq_len 128: buckets beyond it are dropped at init and a
    # prompt >= cache length is rejected up front (not a loop-thread
    # crash later).
    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=1, prefill_buckets=(8, 512)))
    assert engine.cfg.prefill_buckets == (8,)
    with pytest.raises(ValueError):
        engine.submit(list(range(200)), 4)


def test_engine_crash_fails_requests_and_health(model_and_params):
    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=1, prefill_buckets=(8,)))
    engine._decode = None   # force a crash inside step()
    engine.start()
    try:
        req = engine.submit([1, 2], 4)
        assert req.tokens() == []          # failed, not hung
        assert not engine.healthy
        with pytest.raises(RuntimeError):
            engine.submit([1, 2], 4)       # dead engine rejects submits
    finally:
        engine.stop()


def test_http_server_completions(model_and_params):
    from aiohttp.test_utils import TestClient, TestServer
    import asyncio

    from skypilot_tpu.inference.server import build_app, encode_bytes

    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8, 16)))
    engine.start()

    async def drive():
        client = TestClient(TestServer(build_app(engine)))
        await client.start_server()
        try:
            r = await client.get('/health')
            assert r.status == 200
            r = await client.post('/v1/completions',
                                  json={'prompt': 'hi', 'max_tokens': 4})
            assert r.status == 200
            body = await r.json()
            assert len(body['ids']) == 4
            assert body['usage']['prompt_tokens'] == 2
            assert body['usage']['ttft_ms'] is not None
            r = await client.post('/v1/completions', json={'bogus': 1})
            assert r.status == 400
        finally:
            await client.close()

    try:
        asyncio.new_event_loop().run_until_complete(drive())
    finally:
        engine.stop()

    want = naive_greedy(model, params, encode_bytes('hi'), 4)
    # HTTP path produced real engine tokens
    assert want  # sanity: reference generation nonempty


def test_serve_trained_checkpoint(tmp_path, monkeypatch):
    """Train -> checkpoint -> serve restores the TRAINED weights.

    The reference's serve flow is checkpoint-convert-then-serve
    (examples/tpu/v6e/README.md:100-118); here the replica restores the
    orbax checkpoint directly.  Covers both a local path and a gs://
    path over the fake-GCS boundary, and proves the replica serves the
    trained tree (leaf-exact restore, != random init); engine-vs-naive
    decode parity is covered by the engine tests above.
    """
    from skypilot_tpu.inference.weights import load_serving_params
    from skypilot_tpu.parallel.mesh import MeshPlan, build_mesh
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    mesh = build_mesh(MeshPlan(1, 8, 1))
    model = Llama(CFG)
    sample = jnp.zeros((8, 32), jnp.int32)
    ckpt_dir = tmp_path / 'ckpt'
    trainer = Trainer(model, mesh, jax.random.PRNGKey(0), sample,
                      TrainConfig(learning_rate=1e-2, warmup_steps=1,
                                  total_steps=4),
                      checkpoint_dir=str(ckpt_dir))

    def batches():
        key = jax.random.PRNGKey(1)
        while True:
            key, sub = jax.random.split(key)
            yield jax.random.randint(sub, (8, 32), 0, CFG.vocab_size)

    trainer.run(batches(), 3)
    trainer.save_checkpoint()
    trainer._ckpt_mgr.close()
    trained = jax.device_get(trainer.state.params)

    # Local-path restore returns exactly the trained tree.
    restored = load_serving_params(str(ckpt_dir))
    assert (jax.tree.structure(restored) == jax.tree.structure(trained))
    for got, want in zip(jax.tree.leaves(restored),
                         jax.tree.leaves(trained), strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    # The trained tree is not the random init the old server fell back to.
    rand = init_params(model, jax.random.PRNGKey(0))['params']
    diffs = [not np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)
             for a, b in zip(jax.tree.leaves(restored),
                             jax.tree.leaves(rand))]
    assert any(diffs)

    # gs:// restore through the fake-GCS boundary (bucket -> replica).
    monkeypatch.setenv('SKYTPU_FAKE_GCS_ROOT', str(tmp_path / 'gcs'))
    from skypilot_tpu.data import storage as storage_lib
    bucket = storage_lib.GcsStore('ckpts')
    bucket.create()
    bucket.sync_up(str(ckpt_dir), 'run1')
    params_gs = load_serving_params('gs://ckpts/run1')
    assert (jax.tree.structure(params_gs) == jax.tree.structure(trained))
    for got, want in zip(jax.tree.leaves(params_gs),
                         jax.tree.leaves(trained), strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    # The engine decodes with the restored weights end-to-end.  (Exact
    # engine-vs-naive token equality is asserted elsewhere on random
    # init; a briefly-trained tiny model has near-tie logits where the
    # two numeric paths may argmax apart, so only completion shape and
    # determinism are asserted here.)
    engine = DecodeEngine(model, params_gs,
                          EngineConfig(n_slots=1, prefill_buckets=(8,)))
    prompt = [5, 17, 3]
    req = engine.submit(prompt, 6)
    while req.finished_at is None:
        engine.step()
    first = req.tokens()
    assert len(first) == 6
    req2 = engine.submit(prompt, 6)
    while req2.finished_at is None:
        engine.step()
    assert req2.tokens() == first  # greedy decode is deterministic


def test_load_serving_params_missing(tmp_path):
    from skypilot_tpu.inference.weights import load_serving_params
    with pytest.raises(FileNotFoundError):
        load_serving_params(str(tmp_path / 'empty'))


def test_engine_pipelined_matches_sync_step(model_and_params):
    """step_pipelined (dispatch k+1 before syncing k) must emit exactly
    the tokens the synchronous step() path does — same executables, same
    state evolution, only host scheduling differs; the one-call retire
    lag discards garbage rows, never real ones.  (Comparing against a
    differently-COMPILED reference is deliberately avoided here: one
    bf16 ULP of fusion-order noise flips argmax in the tiny
    random-weight model.)"""
    model, params = model_and_params

    def run(step_attr):
        engine = DecodeEngine(model, params,
                              EngineConfig(n_slots=2, steps_per_call=3,
                                           prefill_buckets=(8, 16)))
        reqs = [engine.submit([1, 2, 3], 8),
                engine.submit([7, 8, 9, 10], 6)]
        step = getattr(engine, step_attr)
        for _ in range(200):
            step()
            if all(r.finished_at is not None for r in reqs):
                break
        return [r.tokens() for r in reqs]

    assert run('step_pipelined') == run('step')


def test_engine_pipelined_slot_reuse_backlog(model_and_params):
    """4 requests through 2 slots under pipelining: every request
    completes with exactly its max_new tokens (eos off), and two runs
    are bit-identical (no scheduling nondeterminism)."""
    model, params = model_and_params

    def run():
        engine = DecodeEngine(model, params,
                              EngineConfig(n_slots=2, steps_per_call=3,
                                           prefill_buckets=(8, 16)))
        prompts = [[1, 2, 3], [7, 8, 9, 10], [4, 4, 4, 4, 4], [11, 12]]
        lens = [10, 6, 5, 7]
        reqs = [engine.submit(p, n) for p, n in zip(prompts, lens)]
        for _ in range(400):
            engine.step_pipelined()
            if all(r.finished_at is not None for r in reqs):
                break
        return [r.tokens() for r in reqs], lens

    toks, lens = run()
    for got, n in zip(toks, lens):
        assert len(got) == n
    assert run()[0] == toks


def test_engine_pipelined_threaded_loop(model_and_params):
    """The serving loop thread (which now runs step_pipelined) completes
    staggered submissions with correct tokens."""
    model, params = model_and_params
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, steps_per_call=2,
                                       prefill_buckets=(8, 16)))
    engine.start()
    try:
        p1, p2 = [1, 2, 3], [7, 8, 9, 10, 11, 12]
        want1 = naive_greedy(model, params, p1, 6)
        r1 = engine.submit(p1, 6)
        import time as time_lib
        time_lib.sleep(0.2)
        want2 = naive_greedy(model, params, p2, 4)
        r2 = engine.submit(p2, 4)
        assert r1.tokens() == want1
        assert r2.tokens() == want2
    finally:
        engine.stop()


def test_engine_empty_slots_count_from_zero_and_held_ones_do_not(
        model_and_params):
    """The decode program is told which slots hold a request, by the
    host's view at dispatch, and counts an empty slot's length from
    zero.  Under the pipelined loop: a slot retired and later re-admitted
    yields the tokens a fresh engine gives; a slot admitted in one
    iteration (its insert queued behind the call in flight) is held in
    the next call, not zeroed by it; the device's lengths stay what the
    host mirrors (`_Slot.device_length`) at every dispatch."""
    model, params = model_and_params
    config = EngineConfig(n_slots=3, steps_per_call=3,
                          prefill_buckets=(8, 16))
    steps = config.steps_per_call
    prompts = {'a': ([1, 2, 3], 4), 'b': ([7, 8, 9, 10], 16),
               'c': ([4, 4, 4, 4, 4], 7), 'd': ([11, 12], 5)}

    def alone(name):
        engine = DecodeEngine(model, params, config)
        req = engine.submit(*prompts[name])
        while req.finished_at is None:
            engine.step()
        return req.tokens()

    engine = DecodeEngine(model, params, config)
    reqs = {}
    zeroed = admitted_then_held = 0

    def iterate():
        nonlocal zeroed, admitted_then_held
        empty = [i for i, s in enumerate(engine._slots) if s is None]
        fresh = [i for i, s in enumerate(engine._slots)
                 if s is not None and s.device_length == s.length
                 and s.first_pending]
        engine.step_pipelined()
        lens = np.asarray(engine._lens_d)
        dispatched = engine._inflight is not None
        for i, slot in enumerate(engine._slots):
            if slot is not None:
                assert lens[i] == slot.device_length, (i, lens)
        if dispatched:
            for i in empty:
                if engine._slots[i] is None:
                    assert lens[i] == steps, (i, lens)
                    zeroed += 1
            for i in fresh:     # admitted in the iteration before this call
                if engine._slots[i] is not None:
                    assert lens[i] == engine._slots[i].length + steps
                    admitted_then_held += 1

    reqs['a'] = engine.submit(*prompts['a'])
    reqs['b'] = engine.submit(*prompts['b'])
    while reqs['a'].finished_at is None:
        iterate()
    iterate()                       # a's slot stands empty for a call
    reqs['c'] = engine.submit(*prompts['c'])      # ... and is taken again
    iterate()
    reqs['d'] = engine.submit(*prompts['d'])
    for _ in range(200):
        iterate()
        if all(r.finished_at is not None for r in reqs.values()):
            break
    assert zeroed and admitted_then_held >= 3
    for name, req in reqs.items():
        assert req.tokens() == alone(name), name


def test_decode_step_reads_nothing_of_a_row_that_is_not_live(
        model_and_params):
    """`live` through `model.apply` (what `decode` passes for `held`): a
    live row's logits and cache are what they are without it, to the
    bit; the other row attends to nothing (zeros from attention),
    not to its own freshly written row."""
    model, params = model_and_params
    _, state = model.apply(
        {'params': params}, jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]]),
        decode=True, mutable=['cache'])

    def step(**live):
        return model.apply(
            {'params': params, 'cache': state['cache']},
            jnp.asarray([[9], [9]]), positions=jnp.asarray([[4], [0]]),
            decode=True, mutable=['cache'], **live)

    logits, after = step()
    told, after_told = step(live=jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(told[0]), np.asarray(logits[0]))
    assert not np.array_equal(np.asarray(told[1]), np.asarray(logits[1]))
    assert np.isfinite(np.asarray(told)).all()
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a[0], b[0]),
                 after_told['cache'], after['cache'])


def _by_kind(family):
    """The registry's series of `family` by their `kind` label."""
    import re
    from skypilot_tpu.server import metrics as metrics_lib
    series = re.compile(rf'^{family}\{{kind="(\w+)"\}} (\S+)$')
    return {m.group(1): float(m.group(2))
            for m in map(series.match, metrics_lib.render().splitlines())
            if m}


def _kv_positions():
    return _by_kind('skytpu_engine_decode_kv_positions_total')


def test_engine_counts_kv_positions_held_and_fetched(model_and_params):
    """`skytpu_engine_decode_kv_positions_total` over a known schedule:
    `held` is slots x max_seq_len x steps a call; `fetched` the same
    where the attention reads every slot whole (here, the CPU), and whole
    tiles up to each step's row where a kernel's block length is known
    (set by hand: the test's steering, not an option).  A slot that
    holds no request is `empty` where the model's step is told which
    rows are live (Llama), and counts from zero into `fetched` where it
    is not: the two sum to the same."""
    model, params = model_and_params
    config = EngineConfig(n_slots=2, steps_per_call=3,
                          prefill_buckets=(8, 16))
    engine = DecodeEngine(model, params, config)
    assert engine._kv_block is None
    before = _kv_positions()
    req = engine.submit([1, 2, 3, 4, 5], 7)
    engine.step()
    engine._flush_loop_seconds()
    whole = 2 * CFG.max_seq_len * 3
    after = _kv_positions()
    assert after['held'] - before.get('held', 0.0) == whole
    assert after['fetched'] - before.get('fetched', 0.0) == whole

    engine._kv_block = 4
    engine.step()          # lengths 8 and (empty) 0 at the call's start
    engine._flush_loop_seconds()
    assert req.finished_at is not None
    last = _kv_positions()
    assert last['held'] - after['held'] == whole
    # Rows 9, 10, 11 of the held slot: 3 tiles each; the empty one
    # fetches nothing, where it would have fetched a tile a step (rows
    # 1, 2, 3).
    assert engine._takes_live
    assert last['fetched'] - after['fetched'] == 3 * 3 * 4
    assert last['empty'] - after.get('empty', 0.0) == 3 * 1 * 4
    assert after.get('empty', 0.0) == before.get('empty', 0.0)

    # A model whose step does not take the signal: the empty slots' tiles
    # are fetched, and counted so.
    untold = DecodeEngine(
        declaring(Llama, decode_takes_live=False, decode_kv_block=4)(CFG),
        params, config)
    assert not untold._takes_live and untold._kv_block == 4
    untold._count_kv_positions(np.array([8, 0]), np.array([True, False]))
    untold._flush_loop_seconds()
    counted = _kv_positions()
    assert counted['fetched'] - last['fetched'] == (3 * 3 + 3 * 1) * 4
    assert counted['empty'] == last['empty']


def test_a_model_without_prefill_rows_keeps_the_prefill_program_it_had(
        model_and_params):
    """`prefill_insert` for a model that takes a group's rows whole (no
    `prefill_rows`: Llama here) lowers to the text of the program it was
    before a wave's insert went group by group: the rows in one pass, the
    sample, then one scatter of every row's cache (the parent's function,
    written out below; the parent's own lowered text was compared by hand
    for Llama, SDAR and Solar-Open2: PERF.md section 6, PR 43)."""
    model, params = model_and_params
    assert model.served().prefill_rows is None
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=4, prefill_buckets=(16,), steps_per_call=2))

    def prefill_insert(params, big_cache, last_toks, lens, tokens, lengths,
                       slots, valid, rng):
        n, p = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(p)[None, :], (n, p))
        logits, cache = model.apply(
            {'params': params}, tokens, positions=positions, decode=True,
            lengths=lengths, mutable=['cache'])
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        firsts = jnp.argmax(last, axis=-1)
        firsts = jnp.where(valid.astype(bool), firsts, firsts[0])
        big_cache = jax.tree_util.tree_map(
            lambda big, small: big.at[slots].set(small), big_cache,
            cache['cache'])
        return (big_cache, last_toks.at[slots].set(firsts),
                lens.at[slots].set(lengths))

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    rows = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    vec = jax.ShapeDtypeStruct((4,), jnp.int32)
    args = (shapes(params), shapes(engine._cache), shapes(engine._last_d),
            shapes(engine._lens_d), rows, vec, vec, vec, shapes(engine._rng))
    assert jax.jit(engine._prefill_raw).lower(*args).as_text() == \
        jax.jit(prefill_insert).lower(*args).as_text()


def test_a_wave_goes_into_the_cache_group_by_group(model_and_params):
    """A model that declares `prefill_rows` r: a prefill of N > r rows
    inserts each group of r rows at its slots as the group ends, the same
    cache, tokens and lengths as the rows in one pass; padding rows
    (replicas of row 0) write row 0's slot again with row 0's values,
    whichever group they fall in."""
    model, params = model_and_params

    TwoRows = declaring(Llama, prefill_rows=2)

    def served(model):
        engine = DecodeEngine(model, params, EngineConfig(
            n_slots=8, prefill_buckets=(16,), steps_per_call=2))
        rng = np.random.default_rng(3)
        tokens = rng.integers(1, CFG.vocab_size, (8, 16)).astype(np.int32)
        lengths = np.array([9, 16, 3, 12, 7, 9, 9, 9], np.int32)
        slots = np.array([5, 2, 7, 0, 3, 5, 5, 5], np.int32)   # 3 padding
        tokens[5:] = tokens[0]
        valid = np.array([1, 1, 1, 1, 1, 0, 0, 0], np.int32)
        return jax.jit(engine._prefill_raw)(
            params, engine._cache, engine._last_d, engine._lens_d,
            jnp.asarray(tokens), jnp.asarray(lengths), jnp.asarray(slots),
            jnp.asarray(valid), engine._rng)

    whole, grouped = served(model), served(TwoRows(CFG))
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(grouped)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert [int(n) for n in grouped[2]] == [12, 0, 16, 7, 0, 9, 0, 3]


def _prefill_rows_total():
    found = _by_kind('skytpu_engine_prefill_rows_total')
    return found.get('admitted', 0.0), found.get('run', 0.0)


@pytest.mark.parametrize('rows_at_once,group', [
    (1, 1), (1, 3), (1, 2), (1, 8), (4, 1), (4, 3), (4, 5), (4, 8)])
def test_a_prefill_program_reads_how_many_rows_it_was_handed(
        model_and_params, rows_at_once, group):
    """A model that declares `prefill_rows`: groups of 1, 3, `prefill_rows`
    + 1 and `n_slots` rows through the ONE program a bucket give the
    tokens, the inserted cache and the lengths of the programs compiled a
    power of two of rows (the same weights without `prefill_rows`), and
    the device runs the admitted rows alone: `prefill_rows` at a time and
    what is left over a row at a time, nothing past them."""
    from skypilot_tpu.inference import engine as engine_mod
    from skypilot_tpu.server import tracing
    model, params = model_and_params
    kind = declaring(Llama, prefill_rows=rows_at_once)
    rng = np.random.default_rng(group)
    prompts = [rng.integers(1, CFG.vocab_size, int(n)).tolist()
               for n in rng.integers(2, 17, group)]

    def served(model, pinned):
        # (A copy: the layout pass donates the tree it is handed.)
        engine = DecodeEngine(model, jax.tree.map(jnp.copy, params),
                              EngineConfig(n_slots=8, prefill_buckets=(16,),
                                           steps_per_call=2))
        if pinned:                     # the TPU path, as tests/test_phases.py
            engine._optimize_layouts()
            engine.prewarm()
        before = _prefill_rows_total()
        requests = [engine.submit(p, 5) for p in prompts]
        engine.step()                   # one group, then a decode call
        after = _prefill_rows_total()
        state = jax.tree.map(np.asarray, (engine._cache, engine._lens_d))
        for _ in range(50):
            if all(r.finished_at is not None for r in requests):
                break
            engine.step()
        return (engine, [r.tokens() for r in requests], state,
                (after[0] - before[0], after[1] - before[1]))

    def prefill_compiles():
        return [e['attrs'] for e in tracing.events_for(
            engine_mod.SETUP_REQUEST_ID)
            if e['name'] == 'engine.setup.compile' and
            e['attrs']['kind'] == 'prefill']

    _, want, state, padded = served(model, pinned=False)
    compiled_before = len(prefill_compiles())
    engine, got, loop_state, ran = served(kind(CFG), pinned=True)
    assert got == want and all(len(t) == 5 for t in got)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(loop_state)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # The rows the device ran: the group's, where the padding was run too.
    assert ran == (group, group)
    assert padded == (group, 1 << (group - 1).bit_length())
    # One program a bucket, compiled by prewarm() and by nothing after it.
    assert engine._prewarm_sizes() == [8]
    assert {key: fn.as_text().split(',', 1)[0].split()[-1]
            for key, fn in engine._prefill_compiled.items()} == {
                (16, 8): 'jit_prefill_insert_b16_n8'}
    assert prefill_compiles()[compiled_before:] == [
        {'kind': 'prefill', 'bucket': 16, 'rows': 8}]
    calls = [e['attrs']['carried'] for e in tracing.events_for('engine-loop')
             if e['name'] == 'engine.call' and e['attrs']['carried']]
    assert calls[-1] == [{'kind': 'prefill', 'bucket': 16, 'rows': group,
                          'held': group}]


def test_rows_past_the_group_are_never_computed(model_and_params):
    """What the counter's `run` says, seen on the device: five rows of
    eight admitted at `prefill_rows` 4 go through the model as one group
    of four and one row alone; the three rows behind them, here with
    prompts and slots of their own, leave their slots' cache untouched
    (the program compiled for eight rows would have written them)."""
    model, params = model_and_params
    rng = np.random.default_rng(11)
    tokens = rng.integers(1, CFG.vocab_size, (8, 16)).astype(np.int32)
    lengths = rng.integers(2, 17, 8).astype(np.int32)
    slots = np.array([5, 2, 7, 0, 3, 1, 4, 6], np.int32)
    valid = np.array([1, 1, 1, 1, 1, 0, 0, 0], np.int32)

    def served(model):
        engine = DecodeEngine(model, params, EngineConfig(
            n_slots=8, prefill_buckets=(16,), steps_per_call=2))
        return jax.jit(engine._prefill_raw)(
            params, engine._cache, engine._last_d, engine._lens_d,
            jnp.asarray(tokens), jnp.asarray(lengths), jnp.asarray(slots),
            jnp.asarray(valid), engine._rng)

    whole = served(model)
    looped = served(declaring(Llama, prefill_rows=4)(CFG))
    for a, b in zip(jax.tree.leaves(whole[0]), jax.tree.leaves(looped[0])):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a[slots[:5]], b[slots[:5]], atol=1e-5)
        assert np.abs(a[slots[5:]]).max() > 0.0
        assert not b[slots[5:]].any()
    np.testing.assert_array_equal(np.asarray(whole[1])[slots[:5]],
                                  np.asarray(looped[1])[slots[:5]])
