"""The seam between a decoder and `DecodeEngine`: what each of the six
served decoders declares (`models/served.py Served`, the table below is
the documentation's twin), and what the engine refuses at build, before
any program is compiled: a model that declares nothing, and a declaration
that its model or its cache does not bear out.

Models at their families' rehearsal sizes, abstract weights: nothing here
runs a program.
"""
import copy
import dataclasses
import os
import sys
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import families  # noqa: E402
from benchmarks.harness import manifest, weights  # noqa: E402
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, LlamaConfig  # noqa: E402
from skypilot_tpu.models.sdar_moe import BlockSchedule  # noqa: E402
from skypilot_tpu.models.served import Served  # noqa: E402
from skypilot_tpu.ops import attention as attn_lib  # noqa: E402
from served_utils import declaring  # noqa: E402

DTYPE = jnp.float32
ENGINE = EngineConfig(n_slots=2, prefill_buckets=(8, 16), steps_per_call=2)
FIELDS = [f.name for f in dataclasses.fields(Served)]

# A decoder's class, the configuration its family builds it from, what it
# declares (`decode_kv_block` as the patched choosers below answer: 512
# from `decode_kv_block`, 256 from `latent_kv_block`), whether it publishes
# a `stats` collection, and the geometry it asks a tile for.
DECLARED = {
    'Llama': ('yi-coder-1.5b-chat', Served(
        decode_takes_live=True, decode_kv_block=512), False, (2, 16, 64)),
    'SolarOpen2': ('solar-open2-250b-ep8', Served(
        unpaged_cache='keeps recurrent state beside its keys and values'),
        True, None),
    'OpenPanguMoE': ('openpangu-ultra-moe-718b-ep16', Served(
        unpaged_cache=('caches a latent a position in place of keys and '
                       'values a head'),
        latent_leaves=('c_kv', 'k_pe'), prefill_rows=1,
        decode_kv_block=256), True, (32, 64)),
    'SDARMoE': ('sdar-30b-a3b-chat-pp8', Served(
        unpaged_cache='generates by passes over blocks of positions',
        block_length=4,
        block_schedule=BlockSchedule(255, 'sequential', 4, 0.9),
        decode_takes_live=True, decode_kv_block=512), True, (2, 16, 64)),
    'MiMoV2': ('mimo-v2.5-ep16', Served(
        unpaged_cache=("keeps a ring of its window's positions in its "
                       'window layers beside the whole context in its full '
                       'layers'),
        window_leaves=('ring_k', 'ring_v'), prefill_rows=1,
        decode_takes_live=True, decode_kv_block=512), True, (2, 16, 64)),
    'GraniteHybrid': ('granite-4.0-h-micro', Served(
        unpaged_cache='keeps recurrent state beside its keys and values',
        prefill_rows=8, decode_kv_block=512), True, (1, 32, 64)),
    'Zaya': ('zaya1-8b-pp2', Served(
        unpaged_cache=("keeps the last position's convolution taps and "
                       'half value, of fixed size a slot, beside its keys '
                       'and values'),
        prefill_rows=1, decode_takes_live=True, decode_kv_block=512), True,
        (2, 16, 64)),
}


def tiny(config_file):
    """(the decoder, its abstract weights) at the family's rehearsal size."""
    config = copy.deepcopy(manifest.load_json(
        manifest.BENCH_DIR, 'configs', f'{config_file}.json'))
    family = families.load(config)
    config.update(family.REHEARSAL)
    config['serve'].update(max_seq_len=64)
    dims = family.dims(config)
    params = jax.eval_shape(lambda k: family.make_params(k, dims, DTYPE),
                            weights.seed_key(46))
    return family.serve_model(dims, config, DTYPE), params


@pytest.fixture
def asked(monkeypatch):
    """The geometry a decoder asks a decode tile for: the choosers answer
    for the TPU (`jax.default_backend()` is the CPU here)."""
    asked = []

    def kv(h, d, s, dtype=jnp.bfloat16, mesh=None):
        asked.append((h, d, s))
        return 512 if mesh is None or mesh.size == 1 else None

    def latent(rank, s, mesh=None):
        asked.append((rank, s))
        return 256

    monkeypatch.setattr(attn_lib, 'decode_kv_block', kv)
    monkeypatch.setattr(attn_lib, 'latent_kv_block', latent)
    return asked


@pytest.mark.parametrize('decoder', sorted(DECLARED))
def test_what_a_decoder_declares(decoder, asked):
    """`served()` of each decoder against the table, the engine's own
    reading of it, and nothing of the seam left on the class beside it."""
    config_file, want, publishes, geometry = DECLARED[decoder]
    model, params = tiny(config_file)
    assert type(model).__name__ == decoder
    got = model.served()
    assert dataclasses.replace(got, publish_stats=None) == want
    assert callable(got.publish_stats) == publishes
    assert asked == ([geometry] if geometry else [])
    left = [n for n in FIELDS if n != 'publish_stats' and
            hasattr(type(model), n)]
    assert not left
    engine = DecodeEngine(model, params, ENGINE)
    assert (engine._prefill_rows, engine._takes_live, engine._kv_block,
            engine._block, engine._schedule) == (
                want.prefill_rows, want.decode_takes_live,
                want.decode_kv_block, want.block_length, want.block_schedule)
    assert (engine._stats_abs is not None) == publishes
    assert engine._prewarm_sizes() == ([2] if want.prefill_rows else [1, 2])


class Undeclared(nn.Module):
    """A decoder that was never told of the seam."""
    cfg: LlamaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, positions=None, decode=False, lengths=None):
        return Llama(self.cfg, self.mesh, name='inner')(
            tokens, positions, decode, lengths=lengths)


class ADict(Llama):
    """... and one that answers with something else than a `Served`."""

    def served(self):
        return {'prefill_rows': 2}


@pytest.mark.parametrize('kind', [Undeclared, ADict])
def test_a_model_that_declares_nothing_is_refused(kind):
    """No `served()`, or one that returns something else than a `Served`:
    refused at build with the contract in the message, every field of it."""
    with pytest.raises(TypeError) as refused:
        DecodeEngine(kind(LLAMA_CONFIGS['tiny']), {}, ENGINE)
    message = str(refused.value)
    assert kind.__name__ in message
    assert 'served(self) -> Served' in message
    assert all(f'`{name}`' in message for name in FIELDS)


@pytest.mark.parametrize('fields,said', [
    ({'prefil_rows': 2}, TypeError),
    ({'prefill_rows': 0}, ValueError),
    ({'prefill_rows': 2.0}, ValueError),
    ({'block_length': 4}, ValueError),
    ({'block_schedule': BlockSchedule(255)}, ValueError),
    ({'latent_leaves': ('c_kv', 'k_pe'), 'window_leaves': ('k_pe',)},
     ValueError)])
def test_a_declaration_that_cannot_hold_is_refused_where_it_is_written(
        fields, said):
    with pytest.raises(said):
        Served(**fields)


@pytest.mark.parametrize('decoder,fields,named', [
    ('Llama', {'latent_leaves': ('c_kv',), 'unpaged_cache': 'is odd'},
     ['latent_leaves', "['c_kv']"]),
    ('MiMoV2', {'window_leaves': ('ring_k', 'ring_w')},
     ['window_leaves', "['ring_w']"]),
    ('SolarOpen2', {'unpaged_cache': None}, ['unpaged_cache', 'recurrent']),
    ('OpenPanguMoE', {'unpaged_cache': None}, ['unpaged_cache', 'latent']),
    ('MiMoV2', {'unpaged_cache': None}, ['unpaged_cache', 'window']),
    ('OpenPanguMoE', {'decode_takes_live': True},
     ['decode_takes_live', '`live`']),
    ('SDARMoE', {'block_length': 3}, ['blocks of 3', '[8, 16, 64]'])])
def test_a_declaration_the_model_does_not_bear_out_is_refused_at_build(
        decoder, fields, named):
    """A leaf name the cache lacks, a leaf that is no key or value without
    a reason the page manager cannot hold it, `live` promised of a
    `__call__` that takes none, a block that does not divide the buckets:
    each refused when the engine is built, by the model's class and the
    field."""
    model, params = tiny(DECLARED[decoder][0])
    kind = declaring(type(model), **fields)
    with pytest.raises(ValueError) as refused:
        DecodeEngine(kind(model.cfg), params, ENGINE)
    message = str(refused.value)
    assert kind.__name__ in message
    assert all(part in message for part in named)


def test_what_is_declared_is_read_from_the_model_the_engine_runs(asked):
    """A model built without a mesh and served under `EngineConfig(mesh=)`:
    the engine runs the clone that carries the mesh, whose decode step's
    attention reads every slot whole (the kernel is for one device), and
    counts K/V positions for that, not for the model it was handed."""
    from skypilot_tpu.models.llama import init_params
    from skypilot_tpu.parallel.mesh import build_serve_mesh
    cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], dtype=jnp.float32)
    model = Llama(cfg)
    params = init_params(model, jax.random.PRNGKey(0))['params']
    assert model.served().decode_kv_block == 512
    mesh = build_serve_mesh(2, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_kv_heads)
    engine = DecodeEngine(model, params, EngineConfig(
        mesh=mesh, n_slots=2, prefill_buckets=(8,)))
    assert engine.model.mesh is mesh and model.mesh is None
    assert engine._kv_block is None
    alone = DecodeEngine(model, params, EngineConfig(
        n_slots=2, prefill_buckets=(8,)))
    assert alone.model is model and alone._kv_block == 512
