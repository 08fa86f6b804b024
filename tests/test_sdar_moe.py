"""SDAR-MoE on the serving path, at a tiny size on the CPU, against the
plain reference (benchmarks/reference/sdar_moe_ref.py): generation by
diffusion over blocks of positions, so that the engine's step is a pass
over a block a slot and no longer a token a slot; the mask by blocks in
prefill; the decode kernel with a block's rows folded beside the grouped
query heads; a softmax router over experts that are all held.

Sizes (the family's rehearsal size): hidden 64, 4 query heads of 16 over 2
KV heads, 2 layers, 8 experts of width 32 with 2 a token, vocabulary 256,
blocks of 4; float32 weights from the family's seed, so that the program
and the reference differ by rounding order only.
"""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import families  # noqa: E402
from benchmarks.harness import check, manifest, weights  # noqa: E402
from benchmarks.reference import sdar_moe_ref  # noqa: E402
from skypilot_tpu.inference import engine as engine_lib  # noqa: E402
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from skypilot_tpu.models import moe as moe_lib  # noqa: E402
from skypilot_tpu.models.sdar_moe import REMASKINGS, BlockSchedule  # noqa: E402
from skypilot_tpu.ops import attention as attn_lib  # noqa: E402
from skypilot_tpu.ops.pallas import decode_attention as pallas_da  # noqa: E402
from skypilot_tpu.ops.pallas.flash_attention import flash_attention_fwd  # noqa: E402

SEED = 2**31 + 37
DTYPE = jnp.float32
CONFIG_FILE = 'sdar-30b-a3b-chat-pp8'
BLOCK = 4
# float32 program against float32 reference: what differs is the order of
# the sums (the program reads K and V back from its cache and sums an
# expert's tokens in blocks), 1e-6 of logits of order 1; 1e-4 leaves two
# digits of room, and the same program in bfloat16 is off by 1e-2
# (`test_a_bfloat16_program_is_outside_the_tolerance`).
ROUNDING = 1e-4


def published_config():
    return manifest.load_json(manifest.BENCH_DIR, 'configs',
                              f'{CONFIG_FILE}.json')


def tiny_config(remasking, steps=4):
    config = copy.deepcopy(published_config())
    family = families.load(config)
    config.update(family.REHEARSAL)
    config['serve'].update(max_seq_len=64)
    config['generation'].update(remasking=remasking, denoising_steps=steps,
                                confidence_threshold=0.02)
    return family, family.dims(config), config


@pytest.fixture(scope='module')
def params():
    family, dims, _ = tiny_config('sequential')
    return jax.jit(lambda k: family.make_params(k, dims, DTYPE))(
        weights.seed_key(SEED))


@pytest.fixture(scope='module')
def engines(params):
    """An engine a schedule over the one set of seeded weights (made when
    first asked for), with its family, sizes, module and reference."""
    made = {}

    def get(remasking, steps=4, **options):
        key = (remasking, steps, tuple(sorted(options.items())))
        if key not in made:
            family, dims, config = tiny_config(remasking, steps)
            model = family.serve_model(dims, config, DTYPE)
            options = dict(dict(n_slots=3, prefill_buckets=(8, 16),
                                steps_per_call=3), **options)
            made[key] = (DecodeEngine(model, params, EngineConfig(**options)),
                         family, dims, model,
                         family.reference(dims, SEED, DTYPE))
        return made[key]

    return get


def reference_answer(ref, dims, prompt, max_new, **kw):
    return sdar_moe_ref.generate(
        ref.logits, prompt, max_new, block=dims.block, mask_id=dims.mask_id,
        remasking=dims.remasking, steps=dims.steps,
        threshold=dims.threshold, **kw)


def drain(engine, requests, limit=400):
    for _ in range(limit):
        if all(r.finished_at is not None for r in requests):
            return
        engine.step_pipelined()
    raise AssertionError('requests did not finish')


def prompt_of(length, seed=0):
    return np.random.default_rng(SEED + seed).integers(
        0, 255, length).tolist()


# ----- (a) prefill then passes through the cache, against the reference ------
@pytest.mark.parametrize('length', [8, 9, 10, 11, 3])
@pytest.mark.parametrize('remasking', REMASKINGS)
def test_passes_through_the_cache_give_the_references_logits(
        engines, params, remasking, length):
    """The prompt's whole blocks prefilled under the mask by blocks, then
    passes over blocks through the engine's cache: at every masked
    position of every denoising pass the logits are those of the
    reference's one forward over the whole sequence so far (ROUNDING says
    why 1e-4), for every `L % 4` (and a prompt shorter than a block) and a
    `max_new_tokens` that ends inside a block.  Then the engine's own
    loop: the tokens are the reference's, and the order of unmasking read
    from the request is the reference's."""
    engine, _, dims, model, ref = engines(remasking)
    prompt, max_new = prompt_of(length, length), 6
    want = reference_answer(ref, dims, prompt, max_new)
    request = engine.submit(prompt, max_new)
    engine._admit_free()                   # the prefill and the insert
    slot = next(i for i, s in enumerate(engine._slots)
                if s is not None and s.request is request)
    cache, state = engine._cache, jax.device_get(engine._last_d)
    first = length - length % BLOCK
    assert int(engine._lens_d[slot]) == first
    assert state['masked'][slot].tolist() == [
        int(j >= length % BLOCK) for j in range(BLOCK)]
    assert state['tok'][slot, :length % BLOCK].tolist() == prompt[first:]

    n = engine.cfg.n_slots
    live = jnp.arange(n) == slot

    def a_pass(cache, start, tokens, masked):
        rows = lambda v: jnp.zeros((n, BLOCK), jnp.asarray(v).dtype).at[  # noqa: E731
            slot].set(jnp.asarray(v))
        logits, out = model.apply(
            {'params': params, 'cache': cache}, rows(tokens),
            positions=rows(start + np.arange(BLOCK)), decode=True,
            masked=rows(masked), live=live, mutable=['cache', 'stats'])
        return np.asarray(logits[slot]), out['cache']

    seq = list(prompt)
    compared = 0
    for i, p in enumerate(want['passes']):
        start, masked = p['start'], np.asarray(p['masked'])
        block = (seq[start:] + [0] * BLOCK)[:BLOCK]
        logits, cache = a_pass(cache, start, block, masked)
        np.testing.assert_allclose(logits[masked], p['logits'][masked],
                                   atol=ROUNDING, rtol=0)
        compared += int(masked.sum())
        seq = (seq + [0] * BLOCK)[:start + BLOCK]
        for j in p['took']:
            seq[start + j] = int(p['logits'][j].argmax())
        last = i + 1 == len(want['passes']) or \
            want['passes'][i + 1]['start'] != start
        if last:                          # the commit pass: clean tokens
            _, cache = a_pass(cache, start, seq[start:start + BLOCK],
                              np.zeros(BLOCK, bool))
    assert compared >= max_new and seq[length:length + max_new] == \
        want['tokens']

    drain(engine, [request])
    assert request.tokens() == want['tokens']
    assert [p for p in request.unmask_order if p < length + max_new] == \
        want['order']


def test_the_schedules_differ_in_their_order(engines):
    """The three schedules are three behaviours on these weights, so the
    test above holds each apart from the others: `sequential` fills a
    block left to right, a position a pass; the static confidence order
    does not go left to right; the threshold (0.02 here: several positions
    of a pass lie above it) takes fewer passes for the same blocks."""
    from skypilot_tpu.server import tracing
    prompt, orders, passes = prompt_of(8, 1), {}, {}
    for remasking in REMASKINGS:
        engine, *_ = engines(remasking)
        request = engine.submit(prompt, 16, request_id=f'order-{remasking}')
        drain(engine, [request])
        orders[remasking] = request.unmask_order[:16]
        passes[remasking] = sum(
            e['attrs']['passes']
            for e in tracing.events_for(f'order-{remasking}')
            if e['name'] == 'engine.blocks')
    assert orders['sequential'] == list(range(8, 24))
    assert orders['low_confidence_static'] != orders['sequential']
    assert sorted(orders['low_confidence_static']) == list(range(8, 24))
    assert passes['sequential'] == passes['low_confidence_static'] == 20
    assert 8 <= passes['low_confidence_dynamic'] < 20


def test_two_positions_a_pass(engines):
    """`denoising_steps` 2: two positions of a block a pass, a block in
    2 + 1 passes; tokens and order are the reference's."""
    engine, _, dims, _, ref = engines('low_confidence_static', steps=2)
    prompt = prompt_of(9, 2)
    want = reference_answer(ref, dims, prompt, 9)
    assert [len(p['took']) for p in want['passes'][:3]] == [2, 1, 2]
    request = engine.submit(prompt, 9)
    drain(engine, [request])
    assert request.tokens() == want['tokens']
    assert [p for p in request.unmask_order if p < 18] == want['order']


def test_served_tokens_pass_the_harness_check(engines):
    """What `correct` runs on the chip: the served tokens of the
    `sequential` order against `reference(...).hidden`, which returns at
    index t the state from which position t + 1's token is taken: every
    served token is the reference's own choice (a gap of rounding), and
    one altered token is far off."""
    engine, family, dims, _, _ = engines('sequential')
    prompts = [prompt_of(n, 10 + n) for n in (7, 12, 16, 10)]
    requests = [engine.submit(p, 10) for p in prompts]
    drain(engine, requests)
    samples = [(p, r.tokens()) for p, r in zip(prompts, requests)]
    assert all(len(t) == 10 for _, t in samples)
    verdict = check.served_gap(family, dims, SEED, DTYPE, samples, (64, 10))
    assert verdict['finite'] and verdict['positions'] == 40
    assert verdict['widest_gap'] < 1e-3, verdict
    prompt, tokens = samples[0]
    altered = list(tokens)
    altered[4] = (altered[4] + 1) % 255
    verdict = check.served_gap(family, dims, SEED, DTYPE,
                               [(prompt, altered)], (64, 10))
    assert verdict['widest_gap'] > 0.5, verdict


def test_a_bfloat16_program_is_outside_the_tolerance(engines, params):
    """The tolerance of (a) holds a lower precision apart: the same pass in
    bfloat16 is off by a hundred times ROUNDING."""
    _, family, dims, _, ref = engines('sequential')
    _, _, config = tiny_config('sequential')
    model = family.serve_model(dims, config, jnp.bfloat16)
    tokens = np.asarray(prompt_of(8, 3))
    masked = np.arange(8) >= 5
    low = model.apply({'params': jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), params)}, tokens[None],
        masked=masked[None])[0]
    want = np.asarray(ref.logits(tokens, masked))
    assert np.abs(np.asarray(low) - want)[masked].max() > 100 * ROUNDING
    full = engines('sequential')[3].apply({'params': params}, tokens[None],
                                          masked=masked[None])[0]
    np.testing.assert_allclose(np.asarray(full), want, atol=ROUNDING, rtol=0)


# ----- (b), (c) slots at different phases; a refilled slot ------------------
def test_slots_at_different_phases_share_passes(engines):
    """Requests admitted at different times are at different phases of
    their blocks in the passes they share (3 passes a call, 5 a block),
    with prompts of different `L % 4`; each answer, and each order of
    unmasking, equals the one generated alone."""
    engine, _, dims, _, ref = engines('low_confidence_static')
    plan = [(prompt_of(9, 20), 11), (prompt_of(12, 21), 7),
            (prompt_of(6, 22), 10), (prompt_of(15, 23), 5)]
    requests = []
    for prompt, max_new in plan:
        requests.append(engine.submit(prompt, max_new))
        for _ in range(2):                 # two calls between arrivals
            engine.step_pipelined()
    drain(engine, requests)
    for (prompt, max_new), request in zip(plan, requests):
        want = reference_answer(ref, dims, prompt, max_new)
        assert request.tokens() == want['tokens']
        assert [p for p in request.unmask_order
                if p < len(prompt) + max_new] == want['order']


def test_a_refilled_slot_reads_nothing_of_its_predecessor(engines):
    """One slot: a long request, then a shorter one in its place (handed
    off inside the long one's last call): the second answer equals the
    one generated alone, though the slot's cache and block state held the
    first's."""
    engine, _, dims, _, ref = engines('low_confidence_static', n_slots=1)
    first, second = prompt_of(16, 30), prompt_of(5, 31)
    requests = [engine.submit(first, 14), engine.submit(second, 9)]
    drain(engine, requests)
    for prompt, request, max_new in ((first, requests[0], 14),
                                     (second, requests[1], 9)):
        assert request.tokens() == reference_answer(
            ref, dims, prompt, max_new)['tokens']


def test_a_wave_hands_over_as_one_group(engines, monkeypatch):
    """Answers of one length admitted together end in two adjacent calls
    by their prompts' `L % 4` (16 tokens at 10 passes a call: 20 passes
    where it is 0, 22 to 24 where it is not).  The slots that end first
    wait one call for the larger rest, the successors are ONE prefill
    group, and the next wave is whole again; each answer is the one
    generated alone.  Never two iterations in a row."""
    engine, _, dims, _, ref = engines('sequential', n_slots=4,
                                      steps_per_call=10)
    groups = []
    admit = engine._admit_group
    monkeypatch.setattr(engine, '_admit_group', lambda bucket, group: (
        groups.append(len(group)), admit(bucket, group))[1])
    lengths = [12, 9, 10, 11, 16, 13, 14, 15, 12, 9, 10, 11]   # one bucket
    plan = [prompt_of(n, 60 + i) for i, n in enumerate(lengths)]
    requests = [engine.submit(prompt, 16) for prompt in plan]
    drain(engine, requests)
    assert groups == [4, 4, 4]
    for prompt, request in zip(plan, requests):
        assert request.tokens() == reference_answer(
            ref, dims, prompt, 16)['tokens']
    engine._admission_held = True               # (all four slots are free)
    assert not engine._hold_admission(1, 8)     # not twice in a row
    assert engine._hold_admission(1, 8)
    engine._admission_held = False
    assert not engine._hold_admission(3, 1)     # the larger part is free
    # The control: without the wait the wave splits, and stays split.
    monkeypatch.setattr(engine, '_hold_admission', lambda *_: False)
    del groups[:]
    drain(engine, [engine.submit(prompt, 16) for prompt in plan])
    assert groups[0] == 4 and len(groups) > 3


def test_a_prefill_of_blocks_keeps_a_program_a_power_of_two(params):
    """A model that declares no `prefill_rows` keeps its prefill programs:
    one a bucket and power of two of rows up to the slots, named for both
    (the TPU path, run here by calling the layout pass by hand), and each
    lowers to the text of the program it was before a model with
    `prefill_rows` got one program a bucket (the parent's function,
    written out below: the rows in one pass under the mask by blocks, one
    scatter of every row's K and V, the first block opened)."""
    family, dims, config = tiny_config('sequential')
    model = family.serve_model(dims, config, DTYPE)
    assert model.served().prefill_rows is None
    # (A copy: the layout pass donates the tree it is handed.)
    engine = DecodeEngine(model, jax.tree.map(jnp.copy, params), EngineConfig(
        n_slots=3, prefill_buckets=(8, 16), steps_per_call=3))
    assert engine._prewarm_sizes() == [1, 2, 4]

    def prefill_insert_blocks(params, big_cache, block, starts, tokens,
                              lengths, slots, valid, rng):
        del valid, rng
        n, p = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(p)[None, :], (n, p))
        _, cache = model.apply(
            {'params': params}, tokens, positions=positions, decode=True,
            lengths=lengths, mutable=['cache'])
        first = lengths - lengths % BLOCK
        offs = jnp.arange(BLOCK)[None, :]
        opened = jnp.take_along_axis(
            tokens, jnp.minimum(first[:, None] + offs, p - 1), axis=1)
        masked = (offs >= (lengths % BLOCK)[:, None]).astype(jnp.int32)
        big_cache = jax.tree_util.tree_map(
            lambda big, small: big.at[slots].set(small), big_cache,
            cache['cache'])
        return (big_cache,
                {'tok': block['tok'].at[slots].set(opened),
                 'masked': block['masked'].at[slots].set(masked)},
                starts.at[slots].set(first))

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    for n in (1, 4):
        rows = jax.ShapeDtypeStruct((n, 16), jnp.int32)
        vec = jax.ShapeDtypeStruct((n,), jnp.int32)
        args = (shapes(params), shapes(engine._cache), shapes(engine._last_d),
                shapes(engine._lens_d), rows, vec, vec, vec,
                shapes(engine._rng))
        assert jax.jit(engine._prefill_raw).lower(*args).as_text() == \
            jax.jit(prefill_insert_blocks).lower(*args).as_text()
    engine._optimize_layouts()
    engine.prewarm()
    assert {key: fn.as_text().split(',', 1)[0].split()[-1]
            for key, fn in engine._prefill_compiled.items()} == {
                (bucket, n): f'jit_prefill_insert_b{bucket}_n{n}'
                for bucket in (8, 16) for n in (1, 2, 4)}


def test_an_end_token_cuts_the_block(engines, params):
    """A request ends at its end token inside a block: the rest of the
    block is not output."""
    _, _, dims, model, ref = engines('sequential')
    prompt = prompt_of(9, 40)
    want = reference_answer(ref, dims, prompt, 12)['tokens']
    eos = want[5]
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=2, prefill_buckets=(16,), steps_per_call=4, eos_id=eos))
    request = engine.submit(prompt, 12)
    drain(engine, [request])
    assert request.tokens() == want[:want.index(eos) + 1]


def test_counters_and_span_follow_tokens_and_passes(engines):
    """`skytpu_engine_decode_tokens_total` counts tokens (not rows of
    `out`), the block counters the slot-passes by kind of slots that hold
    a request, and a traced request gets an `engine.blocks` span a call
    whose blocks, passes and tokens add up to its own."""
    from skypilot_tpu.server import metrics as metrics_lib, tracing
    engine, *_ = engines('sequential')

    def read():
        out = {}
        for line in metrics_lib.render().splitlines():
            for name in ('skytpu_engine_decode_tokens_total',
                         'skytpu_engine_block_passes_total{kind="denoise"}',
                         'skytpu_engine_block_passes_total{kind="commit"}'):
                if line.startswith(name + ' '):
                    out[name] = float(line.rsplit(' ', 1)[1])
        return out

    before = read()
    request = engine.submit(prompt_of(10, 50), 10, request_id='blocks-1')
    drain(engine, [request])
    delta = {k: v - before.get(k, 0.0) for k, v in read().items()}
    # 10 tokens from position 10 on: blocks [8, 12) (2 masked), [12, 16),
    # [16, 20): 2 + 4 + 4 denoising passes and 3 commits.
    assert delta['skytpu_engine_decode_tokens_total'] == 10
    assert delta['skytpu_engine_block_passes_total{kind="denoise"}'] == 10
    assert delta['skytpu_engine_block_passes_total{kind="commit"}'] == 3
    spans = [e for e in tracing.events_for('blocks-1')
             if e['name'] == 'engine.blocks']
    assert sum(e['attrs']['tokens'] for e in spans) == 10
    assert sum(e['attrs']['blocks'] for e in spans) == 3
    assert sum(e['attrs']['passes'] for e in spans) == 13
    # Each names the fetched call it is a slot's view of (ISSUE 39): the
    # engine.call span of that seq ends where it ends, and the first
    # committed block's call is the one the first token names.
    from skypilot_tpu.inference.engine import LOOP_REQUEST_ID
    calls = {e['attrs']['seq']: e for e in tracing.events_for(LOOP_REQUEST_ID)
             if e['name'] == 'engine.call'}
    seqs = [e['attrs']['call'] for e in spans]
    assert seqs == sorted(set(seqs)) and set(seqs) <= set(calls)
    for e in spans:
        call = calls[e['attrs']['call']]
        assert e['ts'] + e['dur_ms'] / 1e3 == pytest.approx(
            call['ts'] + call['dur_ms'] / 1e3, abs=3e-6)
        assert call['attrs']['steps'] == engine.cfg.steps_per_call
    first = [e for e in tracing.events_for('blocks-1')
             if e['name'] == 'engine.first_token']
    assert first[0]['attrs']['call'] == next(
        e['attrs']['call'] for e in spans if e['attrs']['blocks'])
    assert request.first_token_at is not None
    assert [e['name'] for e in tracing.events_for('blocks-1')].count(
        'engine.dispatch') == 1


def test_the_http_server_streams_blocks(engines):
    """`inference/server.py` serves what `_emit` gives it: the engine's
    loop thread, a completion over HTTP, the reference's tokens; a prompt
    longer than a bucket is a 413 with the limit (no chunked prefill for
    blocks)."""
    import asyncio
    from aiohttp.test_utils import TestClient, TestServer
    from skypilot_tpu.inference.server import build_app
    engine, _, dims, _, ref = engines('low_confidence_static', n_slots=2)
    prompt = prompt_of(11, 60)
    want = reference_answer(ref, dims, prompt, 7)['tokens']
    engine.start()

    async def drive():
        client = TestClient(TestServer(build_app(engine)))
        await client.start_server()
        try:
            r = await client.post('/v1/completions', json={
                'prompt_ids': prompt, 'max_tokens': 7})
            assert r.status == 200
            assert (await r.json())['ids'] == want
            r = await client.post('/v1/completions', json={
                'prompt_ids': list(range(17)), 'max_tokens': 4})
            assert r.status == 413
            assert (await r.json())['max_prompt_len'] == 16
        finally:
            await client.close()

    try:
        asyncio.new_event_loop().run_until_complete(drive())
    finally:
        engine.stop()
    assert engine.healthy


def test_the_handoff_counts_tokens_not_rows():
    """What a call's passes commit at the least: whole blocks, each its
    denoising passes and one commit."""
    to_tokens = engine_lib._passes_to_tokens
    assert to_tokens(4, 10, 4, 1) == 8        # 5 + 5 passes
    assert to_tokens(4, 9, 4, 1) == 4
    assert to_tokens(1, 6, 4, 1) == 4         # 2 passes, then 4 of 5
    assert to_tokens(1, 7, 4, 1) == 8         # 2 + 5
    assert to_tokens(4, 10, 4, 2) == 12       # 3 passes a block
    assert to_tokens(3, 3, 4, 1) == 0


@pytest.mark.parametrize('remasking', REMASKINGS)
def test_the_choice_of_a_pass_is_the_references(remasking):
    """The one choice a pass in which the schedules differ, on the device,
    against the reference's on random confidences (ties included)."""
    rng = np.random.default_rng(7)
    conf = np.round(rng.random((64, BLOCK)), 1).astype(np.float32)
    masked = rng.random((64, BLOCK)) < 0.6
    for steps in (4, 2):
        schedule = BlockSchedule(255, remasking, steps, 0.5)
        got = np.asarray(schedule.choose(jnp.asarray(conf),
                                         jnp.asarray(masked)))
        for row in range(64):
            if not masked[row].any():
                assert not got[row].any()
                continue
            want = sdar_moe_ref.unmask_choice(
                conf[row], masked[row], remasking, -(-BLOCK // steps), 0.5)
            assert got[row].tolist() == want.tolist(), (row, steps)


# ----- (d) the refusals ------------------------------------------------------
def test_paging_speculation_and_transfer_are_refused(engines, params):
    """Blocks in the page manager are a later PR (ROADMAP B7): refused at
    construction with the reason, never a silent fall-back; so are sizes
    that a block does not divide, and a prompt longer than a bucket (no
    chunked prefill for blocks)."""
    engine, _, _, model, _ = engines('sequential')
    for options in (dict(kv_page_size=8),
                    dict(kv_page_size=8, speculation=2)):
        with pytest.raises(ValueError, match='generates by passes over '
                           'blocks of positions.*KV transfer'):
            DecodeEngine(model, params, EngineConfig(
                n_slots=2, prefill_buckets=(8, 16), **options))
    with pytest.raises(RuntimeError, match='requires the paged KV cache'):
        engine.submit_prefill([1, 2, 3])
    with pytest.raises(ValueError, match='blocks of 4 positions.*'
                       r'offending values: \[10\]'):
        DecodeEngine(model, params, EngineConfig(
            n_slots=2, prefill_buckets=(8, 10)))
    with pytest.raises(ValueError, match='exceeds max_prompt_len 16'):
        engine.submit(list(range(17)), 4)


# ----- (e) the softmax router with every expert held -------------------------
def test_softmax_router_with_every_expert_held_is_the_dense_sum(params):
    """`DroplessMoE` under a `LinearRouter` with `scoring='softmax'`,
    `held` = all and no shared expert against the reference's loop over
    every expert under its mask; and `scoring` left out is the sigmoid
    router."""
    _, dims, _ = tiny_config('sequential')
    w = params['layer_0']['moe']
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, dims.hidden))
    layer = moe_lib.DroplessMoE(
        dim=dims.hidden, ffn_dim=dims.expert_ffn, n_experts=dims.experts,
        held=dims.held_ids, router=moe_lib.LinearRouter(
            top_k=dims.top_k, scoring='softmax'), n_shared=0, dtype=DTYPE,
        param_dtype=DTYPE)
    got = layer.apply({'params': w}, x)
    with jax.default_matmul_precision('highest'):
        want = sdar_moe_ref.expert_layer(
            w, x, top_k=dims.top_k, matmul=sdar_moe_ref.plain_matmul)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)
    sigmoid = layer.clone(router=moe_lib.LinearRouter(
        top_k=dims.top_k, scoring='sigmoid')).apply({'params': w}, x)
    default = moe_lib.DroplessMoE(
        dim=dims.hidden, ffn_dim=dims.expert_ffn, n_experts=dims.experts,
        held=dims.held_ids, router=moe_lib.LinearRouter(top_k=dims.top_k),
        n_shared=0, dtype=DTYPE, param_dtype=DTYPE).apply({'params': w}, x)
    assert np.array_equal(np.asarray(sigmoid), np.asarray(default))
    assert np.abs(np.asarray(sigmoid) - np.asarray(got)).max() > 1e-3


# ----- (f) the mask by blocks, and the rows of a block in the decode kernel --
def plain_block_attention(q, k, v, block):
    s = q.shape[2]
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) * q.shape[-1] ** -0.5
    sees = (jnp.arange(s)[None, :] // block) <= (jnp.arange(s)[:, None]
                                                 // block)
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(
        jnp.where(sees, scores, -jnp.inf), axis=-1), v)


@pytest.mark.parametrize('block', [4, 8])
def test_the_mask_by_blocks_against_a_plain_mask(block):
    """`flash_attention` (the kernel in interpret mode, 128-wide tiles so
    that tiles above and on the blocks' diagonal both occur) and the XLA
    reference path under `mask_block`, against attention under the mask
    written out."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(block), 3)
    q = jax.random.normal(kq, (2, 4, 256, 64))
    k = jax.random.normal(kk, (2, 2, 256, 64))
    v = jax.random.normal(kv, (2, 2, 256, 64))
    want = plain_block_attention(q, k, v, block)
    ref = attn_lib.mha_reference(q, k, v, causal=True, mask_block=block)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want), atol=1e-5)
    out = flash_attention_fwd(q, k, v, causal=True, block_size=128,
                              interpret=True, mask_block=block)
    assert jnp.max(jnp.abs(out - want)) < 5e-3   # interpret-mode numerics
    # and through the public entry point, which is the reference here
    entry = attn_lib.flash_attention_on_mesh(q, k, v, None, causal=True,
                                             mask_block=block)
    np.testing.assert_allclose(np.asarray(entry), np.asarray(want),
                               atol=1e-5)
    causal = attn_lib.mha_reference(q, k, v, causal=True)
    assert np.abs(np.asarray(causal) - np.asarray(want)).max() > 1e-2


def test_a_block_of_one_is_the_causal_program():
    """`mask_block` 1 lowers to the causal program as it is: the same
    text as with the argument left out (and a block of 4 to another)."""
    q = jax.ShapeDtypeStruct((1, 4, 256, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.float32)

    def text(**kw):
        return flash_attention_fwd.lower(q, kv, kv, causal=True,
                                         block_size=128, interpret=True,
                                         **kw).as_text()

    assert text(mask_block=1) == text()
    assert text(mask_block=4) != text()

    def ref_text(**kw):
        return jax.jit(lambda q, k, v: attn_lib.flash_attention_on_mesh(
            q, k, v, None, causal=True, **kw)).lower(q, kv, kv).as_text()

    assert ref_text(mask_block=1) == ref_text()


def test_a_blocks_rows_in_the_decode_kernel():
    """The decode kernel (interpret mode) with a block's 4 rows folded
    beside the grouped query heads, against the XLA path: every row reads
    the positions below the slot's one length, an empty slot (length 0)
    gives zeros, and one row a slot is the program it was."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    b, hq, hkv, s, d = 3, 8, 2, 256, 128
    q = jax.random.normal(kq, (b, hq, BLOCK, d), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, s, d), jnp.float32)
    lengths = jnp.asarray([200, 0, 12], jnp.int32)
    want = attn_lib.decode_attention(q, k, v, lengths)      # XLA here
    out = pallas_da.decode_attention_fwd(q, k, v, lengths, block=128,
                                         interpret=True)
    assert out.shape == q.shape
    assert jnp.max(jnp.abs(out - want)) < 5e-3
    assert not np.asarray(out[1]).any()
    # by hand for slot 2: 4 rows over positions < 12, nothing masked inside
    group = hq // hkv
    scores = jnp.einsum('hqd,hkd->hqk', q[2],
                        jnp.repeat(k[2, :, :12], group, axis=0)) * d ** -0.5
    by_hand = jnp.einsum('hqk,hkd->hqd', jax.nn.softmax(scores, axis=-1),
                         jnp.repeat(v[2, :, :12], group, axis=0))
    np.testing.assert_allclose(np.asarray(want[2]), np.asarray(by_hand),
                               atol=1e-5)
    one = jax.ShapeDtypeStruct((b, hq, 1, d), jnp.float32)
    rows = jax.ShapeDtypeStruct(q.shape, jnp.float32)
    low = lambda q_: pallas_da.decode_attention_fwd.lower(  # noqa: E731
        q_, k, v, lengths, block=128, interpret=True).as_text()
    assert 'tensor<3x2x8x128xf32>' in low(one)      # 4 heads a KV head
    assert 'tensor<3x2x16x128xf32>' in low(rows)    # x 4 rows a block


# ----- the configuration -----------------------------------------------------
def test_parameter_count_is_the_arithmetic():
    """`num_params()` of the family and of the program's config equal the
    arithmetic of ISSUE 37: a layer 623,120,640, outside 622,331,904, six
    layers 4,361,055,744; the published 48 layers 30.5 B."""
    config = published_config()
    family = families.load(config)
    dims = family.dims(config)
    assert dims.layer_params() == 623_120_640
    assert dims.num_params() == 4_361_055_744 == config['params_total']
    assert dims.kv_bytes_per_position() == 12_288
    model = family.serve_model(dims, config, jnp.bfloat16)
    assert model.cfg.num_params() == dims.num_params()
    served = model.served()
    assert (served.block_length, served.block_schedule) == (
        4, BlockSchedule(151669, 'sequential', 4, 0.9))
    whole = dict(config, num_hidden_layers=48)
    assert family.dims(whole).num_params() == 30_532_122_624
    # Without a `generation` group the family's default.
    bare = {k: v for k, v in config.items() if k != 'generation'}
    assert family.dims(bare).remasking == 'low_confidence_static'
    assert family.dims(bare).steps == 4 and family.dims(bare).block == 4
    _, tiny, cfg = tiny_config('sequential')
    shapes = jax.eval_shape(lambda: family.make_params(
        weights.seed_key(1), tiny, DTYPE))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == \
        tiny.num_params() == family.serve_model(
            tiny, cfg, DTYPE).cfg.num_params()


def test_a_pass_costs_its_rows():
    """`decode_step_cost` is one pass: all the weights outside the experts,
    the experts that 4 rows a slot reach, K and V of the live positions."""
    config = published_config()
    family = families.load(config)
    dims = family.dims(config)
    cost = family.decode_step_cost(dims, 32, 32 * 640)
    fixed = 6 * dims.fixed_params() + dims.vocab * dims.hidden
    assert 127.9 < family.touched_experts(dims, 128) <= 128
    # (the share of even routing's count that the chip read: `routing`)
    touched = 6 * family.least_touched_experts(dims, 128)
    assert 0.9 * 128 < touched / 6 <= 128
    assert cost['bytes'] == pytest.approx(
        2 * (fixed + touched * dims.expert_params()) + 32 * 640 * 12288)
    assert 7.9e9 < cost['bytes'] < 8.7e9
    assert cost['flops'] > 2 * 128 * fixed
