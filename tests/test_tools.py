"""CLI tools + test-util components: copy_dataset, metadata_util, generate
metadata, ReaderMock, shuffling analysis, dummy-reader microbench, and the
package's console-script entry points."""

import importlib
import os
import tomllib

import numpy as np
import pytest

from petastorm_tpu.reader import make_reader

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO_ROOT, 'pyproject.toml'), 'rb') as _f:
    CONSOLE_SCRIPTS = tomllib.load(_f)['project']['scripts']


class TestThroughputCli:
    def test_single_run(self, scalar_dataset, capsys):
        from petastorm_tpu.benchmark.cli import main
        assert main([scalar_dataset.url, '-m', '20', '-n', '50',
                     '-w', '2']) == 0
        out = capsys.readouterr().out
        assert 'samples/sec' in out
        assert 'Dispersion' not in out

    def test_runs_dispersion(self, scalar_dataset, capsys):
        from petastorm_tpu.benchmark.cli import main
        assert main([scalar_dataset.url, '-m', '20', '-n', '50', '-w', '2',
                     '--runs', '3']) == 0
        out = capsys.readouterr().out
        assert 'Dispersion over 3 runs' in out
        assert 'spread' in out


class TestCopyDataset:
    def test_full_copy(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = 'file://' + str(tmp_path / 'copy')
        copied = copy_dataset(synthetic_dataset.url, target)
        assert copied == len(synthetic_dataset.data)
        with make_reader(target, reader_pool_type='dummy', num_epochs=1) as r:
            ids = sorted(row.id for row in r)
        assert ids == sorted(r_['id'] for r_ in synthetic_dataset.data)

    def test_field_subset(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = 'file://' + str(tmp_path / 'subset')
        copy_dataset(synthetic_dataset.url, target, field_regex=['^id.*'])
        with make_reader(target, reader_pool_type='dummy', num_epochs=1) as r:
            row = next(iter(r))
        assert set(row._fields) == {'id', 'id2', 'id_float', 'id_odd'}

    def test_not_null_filter(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import copy_dataset
        target = 'file://' + str(tmp_path / 'notnull')
        copied = copy_dataset(synthetic_dataset.url, target,
                              field_regex=['id', 'matrix_nullable'],
                              not_null_fields=['matrix_nullable'])
        expected = [r for r in synthetic_dataset.data
                    if r['matrix_nullable'] is not None]
        assert copied == len(expected)

    def test_cli_main(self, synthetic_dataset, tmp_path):
        from petastorm_tpu.tools.copy_dataset import main
        target = 'file://' + str(tmp_path / 'cli_copy')
        assert main([synthetic_dataset.url, target, '--field-regex', '^id$']) == 0
        with make_reader(target, reader_pool_type='dummy', num_epochs=1) as r:
            assert sorted(row.id for row in r) == sorted(
                r_['id'] for r_ in synthetic_dataset.data)


class TestMetadataUtil:
    def test_prints_schema_and_rowgroups(self, synthetic_dataset, capsys):
        from petastorm_tpu.etl.metadata_util import main
        assert main([synthetic_dataset.url, '--schema', '--row-groups']) == 0
        out = capsys.readouterr().out
        assert 'Schema (stored)' in out
        assert 'row groups' in out
        assert 'matrix' in out

    def test_prints_index(self, tmp_path, capsys):
        from petastorm_tpu.etl.metadata_util import main
        from petastorm_tpu.etl.rowgroup_indexers import SingleFieldIndexer
        from petastorm_tpu.etl.rowgroup_indexing import build_rowgroup_index
        from petastorm_tpu.test_util.dataset_gen import create_test_dataset
        url = 'file://' + str(tmp_path / 'indexed_meta')
        create_test_dataset(url, range(20))
        build_rowgroup_index(url, [SingleFieldIndexer('by_pk', 'partition_key')])
        assert main([url, '--index']) == 0
        out = capsys.readouterr().out
        assert 'by_pk' in out


class TestReaderMock:
    def test_yields_schema_rows(self):
        from petastorm_tpu.test_util.dataset_gen import TestSchema
        from petastorm_tpu.test_util.reader_mock import ReaderMock
        mock = ReaderMock(TestSchema, num_rows=10)
        rows = list(mock)
        assert len(rows) == 10
        assert rows[0].matrix.shape == (8, 4, 3)
        assert isinstance(rows[0].partition_key, str)

    def test_reset(self):
        from petastorm_tpu.test_util.dataset_gen import TestSchema
        from petastorm_tpu.test_util.reader_mock import ReaderMock
        mock = ReaderMock(TestSchema, num_rows=5)
        first = [r.id for r in mock]
        mock.reset()
        second = [r.id for r in mock]
        assert first == second

    def test_feeds_jax_loader(self):
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.test_util.dataset_gen import TestSchema
        from petastorm_tpu.test_util.reader_mock import ReaderMock
        mock = ReaderMock(TestSchema.create_schema_view(
            [TestSchema.fields['id'], TestSchema.fields['matrix']]), num_rows=20)
        loader = JaxDataLoader(mock, batch_size=5)
        batches = list(loader)
        assert len(batches) == 4
        assert batches[0]['matrix'].shape == (5, 8, 4, 3)


class TestShufflingAnalysis:
    def test_identical_stream_correlates(self):
        from petastorm_tpu.test_util.shuffling_analysis import \
            compute_correlation_distance
        ids = list(range(100))
        assert compute_correlation_distance(ids, ids) == pytest.approx(1.0)

    def test_shuffled_stream_decorrelates(self):
        from petastorm_tpu.test_util.shuffling_analysis import \
            compute_correlation_distance
        rng = np.random.default_rng(0)
        ids = list(range(1000))
        shuffled = list(rng.permutation(ids))
        assert compute_correlation_distance(shuffled, ids) < 0.2

    def test_mismatched_streams_rejected(self):
        from petastorm_tpu.test_util.shuffling_analysis import \
            compute_correlation_distance
        with pytest.raises(ValueError):
            compute_correlation_distance([1, 2], [1, 3])

    def test_reader_shuffling_quality(self, synthetic_dataset):
        from petastorm_tpu.test_util.shuffling_analysis import \
            analyze_shuffling_quality

        def factory(shuffle):
            return make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                               num_epochs=1, shuffle_row_groups=shuffle,
                               schema_fields=['id'])

        distance = analyze_shuffling_quality(factory, num_reads=2)
        assert distance < 0.9   # row-group shuffle: coarse but present


class TestDummyReaderBench:
    def test_runs(self, capsys):
        from petastorm_tpu.benchmark.dummy_reader import (DummyBatchReader,
                                                          _measure)
        from petastorm_tpu.jax_utils import JaxDataLoader
        reader = DummyBatchReader(chunk_size=100, num_chunks=5)
        rate = _measure(lambda: JaxDataLoader(reader, batch_size=50),
                        'test', 500)
        assert rate > 0


@pytest.mark.parametrize('name', sorted(CONSOLE_SCRIPTS))
def test_console_script_resolves(name):
    """Every ``[project.scripts]`` entry point names an importable
    callable."""
    module_name, _, attr = CONSOLE_SCRIPTS[name].partition(':')
    assert attr, '{} has no callable after the colon'.format(name)
    obj = importlib.import_module(module_name)
    for part in attr.split('.'):
        obj = getattr(obj, part)
    assert callable(obj)
