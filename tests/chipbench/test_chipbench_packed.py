"""The packed-document cell and the one-chip train cell at tiny sizes on the
CPU: whole runs of the harness come out correct, and not correct under the
control, the faults and a step that ignores the segment mask; the driver's
packing and loss agree with ``packed_lm_ref``. This file carries its own
tiny configuration and limits (the cells' own limits are set from chip
readings). No number here is a measurement."""

import json
import os

import numpy as np
import pytest

import chipbench_tiny
from chipbench import faults, harness, mask_fault

SEED = 2 ** 31 + 41
PACKED = 'pile_docs_pythia160m.packed'
TRAIN = 'pile_pythia160m.train'
#: ~1.2 pieces per document of lognormal length, a few split, ~6 documents
#: a row group
DOCS = {'driver': 'packed_lm', 'rows': 40, 'row_group_mb': 0.004,
        'doc_len_median': 24, 'doc_len_sigma': 1.2, 'doc_len_min': 2,
        'doc_len_max': 400, 'vocab_size': 256, 'd_model': 64, 'n_heads': 4,
        'n_layers': 2, 'd_ff': 128, 'seq_len': 64, 'attention': 'flash',
        'compute_dtype': 'bfloat16', 'batch_per_chip': 2, 'lr': 0.0006,
        'adam_b1': 0.9, 'adam_b2': 0.95, 'adam_eps': 1e-8,
        'weight_decay': 0.01, 'ref_block_rows': 2, 'check_batches': 0}
#: Set between the sound readings (seeds 100-103, 2147483677 and SEED) and
#: the fp8 control (seeds 100, 101, 2147483677) at these sizes on the CPU:
#: packed sound at most 4.6e-4 / 3.0e-3 / 1.8e-3, control at least
#: 4.0e-3 / 0.056 / 0.0074; train sound at most 4.2e-4 / 3.1e-3 / 3.3e-3,
#: control at least 1.6e-3 / 0.0196 / 0.0084.
LIMITS = {PACKED: {'loss_gap': 0.0015, 'grad_gap': 0.02, 'change_gap': 0.004},
          TRAIN: {'loss_gap': 0.0009, 'grad_gap': 0.01, 'change_gap': 0.006}}
CELL_FAULTS = [(PACKED, 'control'), (PACKED, 'half_batch'),
               (PACKED, 'altered_value'), (PACKED, mask_fault.FAULT),
               (PACKED, 'state_unchanged'), (TRAIN, 'control'),
               (TRAIN, 'half_batch'), (TRAIN, 'altered_value')]
TRIPS = {'state_unchanged': {'change_gap', 'grad_gap'},
         'half_batch': {'loss_gap', 'grad_gap', 'change_gap'},
         'altered_value': {'rows_mismatched'},
         'control': {'loss_gap', 'grad_gap', 'change_gap'},
         mask_fault.FAULT: {'loss_gap', 'grad_gap', 'change_gap'}}


@pytest.fixture(scope='module')
def bench():
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    base = tmp_path_factory.mktemp('tiny')
    search = chipbench_tiny.write(base, harness.BENCH_DIR)
    with open(base / 'configs' / 'pile_docs_pythia160m.json', 'w') as f:
        json.dump(DOCS, f)
    for mix in ('packed', 'train'):
        with open(os.path.join(harness.BENCH_DIR, 'traffic',
                               mix + '.json')) as f:
            traffic = json.load(f)
        traffic['warmup_steps'] = 4
        with open(base / 'traffic' / (mix + '.json'), 'w') as f:
            json.dump(traffic, f)
    for cell, limits in LIMITS.items():
        with open(base / 'limits' / (cell + '.json'), 'w') as f:
            json.dump({'limits': dict(chipbench_tiny.EXACT, **limits)}, f)
    return search, str(base / 'stores')


@pytest.mark.parametrize('workload', [PACKED, TRAIN])
def test_cell_runs_correct(bench, tiny, workload):
    search, stores = tiny
    result = harness.run(bench, workload, SEED, 0.5, False, search, stores)
    assert result['correct'], result['checks']
    assert result['device']['count'] == 1
    assert result['attempted'] > 0 and result['failed'] == 0
    assert result['metrics'] == {}                 # a CPU rehearsal


@pytest.mark.parametrize('workload, fault', CELL_FAULTS)
def test_planted_fault_is_not_correct(bench, tiny, monkeypatch, workload,
                                      fault):
    search, stores = tiny
    base = faults.broken
    monkeypatch.setattr(faults, 'broken',
                        lambda d, f, r=None: mask_fault.broken(d, f, r, base))
    result = harness.run(bench, workload, SEED, 0.3, False, search, stores,
                         fault=fault)
    assert result['correct'] is False
    tripped = {k for k, v in result['checks'].items()
               if v['value'] > v['limit']}
    assert tripped & TRIPS[fault], result['checks']


def _docs_parts(tiny):
    search, stores = tiny
    lookup = harness.Lookup(search)
    cfg = lookup.json('configs', 'pile_docs_pythia160m')
    driver = lookup.module('drivers', 'packed_lm')
    ref = lookup.module('drivers', 'packed_lm_ref')
    path = harness.store_path(stores, 'pile_docs_pythia160m', cfg, SEED,
                              driver, ref)
    facts, _ = harness.ensure_store(path, cfg, SEED, driver)
    return cfg, driver, ref, path, facts


def test_the_packer_makes_the_references_rows(tiny):
    """Each row group through the reader's packing transform gives exactly
    the rows the reference packs for it, under the same ids; the store's
    ids count pieces."""
    import glob

    import pyarrow.parquet as pq

    from chipbench.drivers import packed_lm
    from petastorm_tpu.readers.columnar_worker import _column_to_numpy
    cfg, driver, ref, path, facts = _docs_parts(tiny)
    source = ref.RowSource(cfg, path, SEED)
    pack = driver.reader_kwargs(cfg, SEED)['transform_spec'].func
    field = packed_lm._schema().fields['tokens']
    rows = 0
    for name in sorted(glob.glob(os.path.join(path, '*.parquet'))):
        f = pq.ParquetFile(name)
        for g in range(f.num_row_groups):
            table = f.read_row_group(g)
            got = pack({'tokens': _column_to_numpy(table.column('tokens'),
                                                   field),
                        'row_id': table.column('row_id').to_numpy()})
            want = source.rows(got['row_id'])
            for column in packed_lm.COLUMNS:
                np.testing.assert_array_equal(got[column], want[column])
            rows += len(got['row_id'])
    assert rows == len(source) and len(source) < facts['rows']
    assert 0 < source.padding_share < 1
    assert 0 < source.attention_share < 1


def test_the_reference_loss_is_the_programs(tiny):
    """In float32 at full matmul precision, the program's packed loss and
    gradient on the driver's weights equal the reference's, whose mask,
    rotary angles and weights are written independently."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models import transformer_lm as tlm
    from petastorm_tpu.packing import packed_lm_targets
    cfg, driver, ref, path, _ = _docs_parts(tiny)
    source = ref.RowSource(cfg, path, SEED)
    batch = source.rows(sorted(source._where)[:3])
    config = tlm.TransformerConfig(
        vocab_size=cfg['vocab_size'], d_model=cfg['d_model'],
        n_heads=cfg['n_heads'], n_layers=cfg['n_layers'], d_ff=cfg['d_ff'],
        max_seq_len=cfg['seq_len'], attention='flash', dtype=jnp.float32)
    params = ref.init(cfg, SEED)
    program = tlm.init(jax.random.PRNGKey(SEED % (1 << 32)), config)
    for a, b in zip(jax.tree_util.tree_leaves(program),
                    jax.tree_util.tree_leaves(params)):
        # the same draw, scaled in another order: equal to float32 rounding
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def program_loss(p):
        targets, weights = packed_lm_targets(batch['tokens'],
                                             batch['segment_ids'])
        return tlm.loss_fn(p, batch['tokens'], targets, config,
                           positions=batch['positions'],
                           segment_ids=batch['segment_ids'], weights=weights)

    def ref_loss(p):
        total, weight = ref.loss_sum(p, batch['tokens'], batch['segment_ids'],
                                     batch['positions'], cfg)
        return total / weight

    with jax.default_matmul_precision('highest'):
        got, got_grad = jax.value_and_grad(program_loss)(params)
        want, want_grad = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_grad),
                    jax.tree_util.tree_leaves(want_grad)):
        # float32 sums taken in another order: off by round-off of the
        # leaf's scale
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-4,
                                   atol=1e-5 * np.abs(b).max())


def test_pack_metric_reads_the_transform_stage():
    lookup = harness.Lookup([harness.BENCH_DIR])
    reader = lookup.module('metrics', 'reader.pack_ms_per_sample')
    run = harness.Run()
    run.fetched, run.global_batch = 10, 4
    run.stats_delta = {'worker_decode_s': 0.5}      # a program without it
    assert reader.read(run) is None
    run.stats_delta = {'worker_transform_s': 0.02, 'pack_tokens': 900,
                       'pack_pad_tokens': 100}
    assert reader.read(run) == pytest.approx(0.5)
