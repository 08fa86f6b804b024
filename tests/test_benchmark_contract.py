"""The benchmark's manifest against its contract, as far as files can show
it (PERF.md section 7 (a)): `BENCHMARK.json` has nothing the driver would
refuse, every configuration's family loads and counts the parameters its
file states, every metric has its reader.  No JAX program runs here."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import families  # noqa: E402
from benchmarks.harness import manifest, reducers  # noqa: E402

MAN = manifest.manifest()


def test_manifest_has_no_problems():
    assert manifest.problems(MAN) == []


@pytest.mark.parametrize('name', [c['name'] for c in MAN['configs']])
def test_configuration_loads_and_counts_its_parameters(name):
    config = manifest.config_of(MAN, name)
    family = families.load(config)
    dims = family.dims(config)
    assert dims.num_params() == config['params_total']
    assert dims.layers == config['num_hidden_layers']
    assert dims.vocab == config['vocab_size']
    listed = next(c for c in MAN['configs'] if c['name'] == name)
    assert listed['reduced'] == config['reduced']
    assert listed['source'] == config['source']


@pytest.mark.parametrize('cell', [w['name'] for w in MAN['workloads']])
def test_cell_has_its_files_and_its_builder(cell):
    entry = manifest.cell(MAN, cell)
    mix = manifest.traffic_of(entry['traffic'])
    family = families.load(manifest.config_of(MAN, entry['config']))
    builder = 'train_model' if mix['kind'] == 'train' else 'serve_model'
    assert hasattr(family, builder), (cell, builder)
    assert len(manifest.metrics_of(MAN, cell, 'end_to_end')) >= 2
    assert manifest.metrics_of(MAN, cell, 'per_layer')


@pytest.mark.parametrize(
    'metric', [m['name'] for m in MAN['end_to_end'] + MAN['per_layer']])
def test_metric_has_its_reader(metric):
    spec = manifest.reducer_spec(metric)
    own = os.path.join(manifest.reducer_dir(metric), f'{metric}.py')
    if os.path.exists(own):
        with open(own, encoding='utf-8') as f:
            assert 'def reduce(ctx' in f.read()
    else:
        assert callable(getattr(reducers, spec['reducer'], None)), spec


MOE_METRICS = [m['name'] for m in MAN['per_layer']
               if m['name'].startswith('moe_')]
# Two readers of one counter, by what a serving step is: a token a slot,
# or (a family whose sizes have a `block`) a pass over a block a slot.
PER_STEP, PER_PASS = ('moe_experts_touched_per_step',
                      'moe_experts_touched_per_pass')


@pytest.mark.parametrize('metric', MOE_METRICS)
def test_every_cell_of_an_expert_family_reports_the_expert_metric(metric):
    """A family that holds part of an expert layer says so through
    `touched_experts` (what the `moe_*` readers ask of it): each of its
    cells is in each `moe_*` metric's list, so a new expert cell cannot
    leave the expert layer unread.  The experts reached a step are read by
    the one of the two readers that divides by the step's rows: a cell is
    in that one's list and not in the other's.  A reader whose manifest
    says `cells_with` reads what only some families' sizes have (a router
    with an output that is no expert): its list is the expert cells whose
    sizes have that attribute, and the per-step reader, which takes its
    layer-steps from pairs that leave the skipped ones out, lists none of
    those."""
    families_of = {
        w['name']: families.load(manifest.config_of(MAN, w['config']))
        for w in MAN['workloads']}
    expert_cells = {name for name, family in families_of.items()
                    if hasattr(family, 'touched_experts')}
    def having(attribute):
        return {
            name for name in expert_cells if getattr(families_of[name].dims(
                manifest.config_of(MAN, manifest.cell(MAN, name)['config'])),
                attribute, None)}

    by_blocks, skipping = having('block'), having('skip_outputs')
    only_with = manifest.reducer_spec(metric).get('cells_with')
    assert len(expert_cells) >= 3 and len(MOE_METRICS) >= 5 and by_blocks
    listed = set(next(m for m in MAN['per_layer']
                      if m['name'] == metric)['workloads'])
    if metric == PER_PASS:
        assert listed == by_blocks, (metric, by_blocks)
    elif metric == PER_STEP:
        assert listed == expert_cells - by_blocks - skipping, (
            metric, expert_cells)
    elif only_with:
        assert listed and listed == having(only_with), (metric, listed)
    else:
        assert expert_cells <= listed, (metric, expert_cells)
