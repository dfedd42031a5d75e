"""JAX adapter tests: host loader, sharded loader over a virtual 8-device CPU
mesh, device prefetch, dtype sanitization, in-memory epoch caching.

Reference analogues: ``petastorm/tests/test_pytorch_dataloader.py`` and
``test_tf_dataset.py`` — re-targeted at the JAX adapter this framework ships
instead of TF/torch adapters.
"""

import numpy as np
import pytest

from petastorm_tpu.jax_utils import (JaxDataLoader, LoaderIterator,
                                     make_jax_loader, prefetch_batches,
                                     prefetch_to_device, sanitize_jax_types)
from petastorm_tpu.reader import make_batch_reader, make_reader


def _all_ids(batches, key='id'):
    out = []
    for b in batches:
        out.extend(np.asarray(b[key]).ravel().tolist())
    return out


class TestHostLoader:
    def test_row_reader_batches(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1, shuffle_row_groups=False) as reader:
            loader = JaxDataLoader(reader, batch_size=10)
            batches = list(loader)
        expected = sorted(r['id'] for r in synthetic_dataset.data)
        assert sorted(_all_ids(batches)) == expected
        # full batches except possibly the last
        for b in batches[:-1]:
            assert len(b['id']) == 10

    def test_row_reader_drop_last(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1) as reader:
            loader = JaxDataLoader(reader, batch_size=32, drop_last=True)
            batches = list(loader)
        assert all(len(b['id']) == 32 for b in batches)
        assert len(batches) == len(synthetic_dataset.data) // 32

    def test_batch_reader_vectorized_path(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1) as reader:
            loader = JaxDataLoader(reader, batch_size=16)
            batches = list(loader)
        assert sorted(_all_ids(batches)) == sorted(r['id'] for r in scalar_dataset.data)
        for b in batches[:-1]:
            assert len(b['id']) == 16

    def test_shuffling_changes_order(self, synthetic_dataset):
        def read(shuffle_capacity, seed):
            with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                             num_epochs=1, shuffle_row_groups=False) as reader:
                loader = JaxDataLoader(reader, batch_size=10,
                                       shuffling_queue_capacity=shuffle_capacity,
                                       seed=seed)
                return _all_ids(list(loader))

        plain = read(0, None)
        shuffled = read(50, 42)
        assert sorted(plain) == sorted(shuffled)
        assert plain != shuffled

    def test_batched_shuffling(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1, shuffle_row_groups=False) as reader:
            loader = JaxDataLoader(reader, batch_size=10,
                                   shuffling_queue_capacity=40, seed=0)
            ids = _all_ids(list(loader))
        assert sorted(ids) == sorted(r['id'] for r in scalar_dataset.data)

    def test_multidim_fields_stacked(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1,
                         schema_fields=['id', 'matrix']) as reader:
            loader = JaxDataLoader(reader, batch_size=5)
            batch = next(iter(loader))
        assert batch['matrix'].shape == (5, 8, 4, 3)
        by_id = {r['id']: r['matrix'] for r in synthetic_dataset.data}
        for i, row_id in enumerate(batch['id']):
            np.testing.assert_array_equal(batch['matrix'][i], by_id[row_id])

    def test_transform_fn(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1) as reader:
            loader = JaxDataLoader(
                reader, batch_size=8,
                transform_fn=lambda b: {'twice': b['id'] * 2})
            batch = next(iter(loader))
        # '_provenance' is the loader's reserved lineage annotation (see
        # docs/lineage.md); the transform itself only ever sees its own keys
        assert set(batch.keys()) - {'_provenance'} == {'twice'}

    def test_inmemory_cache_replays_epochs(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1) as reader:
            loader = JaxDataLoader(reader, batch_size=16, inmemory_cache_all=True)
            first = _all_ids(list(loader))
            second = _all_ids(list(loader))   # reader is exhausted; replay from cache
        assert first == second
        assert sorted(first) == sorted(r['id'] for r in scalar_dataset.data)

    def test_double_iteration_resets_reader(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1) as reader:
            loader = JaxDataLoader(reader, batch_size=16)
            first = sorted(_all_ids(list(loader)))
            second = sorted(_all_ids(list(loader)))
        assert first == second

    def test_concurrent_iteration_rejected(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy') as reader:
            loader = JaxDataLoader(reader, batch_size=4)
            it = iter(loader)
            next(it)
            with pytest.raises(RuntimeError, match='already being iterated'):
                next(iter(loader))


class TestSanitize:
    def test_decimal_and_datetime(self):
        from decimal import Decimal
        row = {'d': Decimal('1.5'),
               'ts': np.array(['2020-01-01'], dtype='datetime64[D]'),
               'x': np.int32(3)}
        out = sanitize_jax_types(row)
        assert out['d'].dtype == np.float64 and out['d'] == 1.5
        assert out['ts'].dtype == np.int64
        assert out['x'] == 3

    def test_decimal_array(self):
        from decimal import Decimal
        row = {'d': np.array([Decimal('1.5'), Decimal('2.5')], dtype=object)}
        out = sanitize_jax_types(row)
        assert out['d'].dtype == np.float64
        np.testing.assert_array_equal(out['d'], [1.5, 2.5])


class TestShardedLoader:
    @pytest.fixture()
    def mesh(self):
        import jax
        from jax.sharding import Mesh
        devices = np.array(jax.devices('cpu')[:8]).reshape(8)
        return Mesh(devices, ('data',))

    def test_global_arrays_over_mesh(self, scalar_dataset, mesh):
        import jax
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1) as reader:
            loader = make_jax_loader(reader, batch_size=16, mesh=mesh)
            batches = list(loader)
        for b in batches:
            arr = b['id']
            assert isinstance(arr, jax.Array)
            assert arr.shape[0] == 16
            assert len(arr.sharding.device_set) == 8
        # all ids present (drop_last may drop a ragged tail)
        ids = np.concatenate([np.asarray(b['id']) for b in batches])
        assert len(set(ids.tolist())) == len(ids)

    def test_string_columns_stay_on_host(self, synthetic_dataset, mesh):
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1,
                         schema_fields=['id', 'partition_key']) as reader:
            loader = make_jax_loader(reader, batch_size=8, mesh=mesh)
            batch = next(iter(loader))
        assert '_host' in batch and 'partition_key' in batch['_host']
        assert len(batch['_host']['partition_key']) == 8

    def test_jit_consumes_sharded_batch(self, scalar_dataset, mesh):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        @jax.jit
        def step(x):
            return jnp.sum(x * 2)

        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1) as reader:
            loader = make_jax_loader(reader, batch_size=16, mesh=mesh)
            total = 0.0
            plain = 0
            for b in loader:
                total += float(step(b['id']))
                plain += int(np.sum(np.asarray(b['id']))) * 2
        assert total == plain


class TestPrefetch:
    def test_prefetch_preserves_stream(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1, shuffle_row_groups=False) as reader:
            loader = JaxDataLoader(reader, batch_size=16)
            direct = _all_ids(list(loader))
            prefetched = _all_ids(list(prefetch_to_device(iter(loader), size=2)))
        assert direct == prefetched

    @pytest.mark.parametrize('feed', [
        lambda loader: iter(loader),
        lambda loader: prefetch_to_device(loader, size=2),
        lambda loader: prefetch_to_device(iter(loader), size=2),
        lambda loader: loader.iter_prefetched(),
        lambda loader: prefetch_batches(loader, size=2),
    ], ids=['direct', 'to_device(loader)', 'to_device(iter(loader))',
            'iter_prefetched', 'batches(loader)'])
    def test_loop_boundary_is_recorded_where_the_loop_waits(
            self, scalar_dataset, feed):
        """A slow producer: ``infeed_wait_s`` is the wait the loop saw,
        ``batches_out`` the batches it got, and the ``infeed_wait`` /
        ``train_step`` spans carry the loop's thread. A prefetcher's
        producer thread records none of them, only its ``stage_next``."""
        import threading
        import time

        def slow(batch):
            time.sleep(0.01)
            return batch

        with make_batch_reader(scalar_dataset.url, reader_pool_type='dummy',
                               num_epochs=1, trace=True) as reader:
            loader = JaxDataLoader(reader, batch_size=8, transform_fn=slow)
            batches = feed(loader)
            got, waited = 0, 0.0
            while True:
                start = time.perf_counter()
                try:
                    next(batches)
                except StopIteration:
                    break
                waited += time.perf_counter() - start
                got += 1
            snapshot = reader.stats.snapshot()
            spans = reader.tracer.spans()
        loop = threading.get_ident()
        assert got >= 3 and snapshot['batches_out'] == got
        assert 0.9 * waited <= snapshot['infeed_wait_s'] <= waited
        assert snapshot['infeed_wait_s'] >= 0.005 * got
        boundary = [s for s in spans if s[0] in ('infeed_wait', 'train_step')]
        assert {s[5] for s in boundary} == {loop}
        assert sum(1 for s in boundary if s[0] == 'infeed_wait') == got
        assert sum(1 for s in boundary if s[0] == 'train_step') == got
        # the goodput step spans end where the loop's steps ended, even
        # when the producer thread folded them a batch later
        step_ends = sorted(s[2] + s[3] for s in boundary
                           if s[0] == 'train_step')
        assert sorted(s[2] + s[3] for s in spans if s[0] == 'step') == \
            pytest.approx(step_ends, abs=1e-6)
        producer = {s[5] for s in spans if s[0] == 'stage_next'}
        assert loop not in producer
        assert bool(producer) != isinstance(batches, LoaderIterator)

    def test_prefetch_propagates_errors(self):
        def boom():
            yield {'x': np.arange(3)}
            raise ValueError('downstream failure')

        it = prefetch_to_device(boom(), size=2)
        next(it)
        with pytest.raises(ValueError, match='downstream failure'):
            list(it)


class TestRaggedPadding:
    """pad_spec: variable-length fields become dense bucketed device arrays
    (SURVEY §7 'hard parts': pad-to-bucket vs XLA's static-shape world)."""

    @pytest.fixture(scope='class')
    def ragged_url(self, tmp_path_factory):
        from petastorm_tpu import materialize_dataset
        from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
        from petastorm_tpu.unischema import Unischema, UnischemaField
        schema = Unischema('Ragged', [
            UnischemaField('id', np.int64, (), ScalarCodec(), False),
            UnischemaField('tokens', np.int32, (None,), NdarrayCodec(), False)])
        url = 'file://' + str(tmp_path_factory.mktemp('ragged') / 'ds')
        rng = np.random.default_rng(0)
        with materialize_dataset(url, schema) as w:
            w.write_rows({'id': np.int64(i),
                          'tokens': rng.integers(1, 100, 3 + i % 20).astype(np.int32)}
                         for i in range(40))
        return url

    def test_unit_pad_and_lengths(self):
        from petastorm_tpu.jax_utils import pad_ragged_batch, validate_pad_spec
        col = np.empty(3, dtype=object)
        col[0] = np.array([1, 2], np.int32)
        col[1] = np.array([3], np.int32)
        col[2] = np.array([4, 5, 6], np.int32)
        spec = validate_pad_spec({'tokens': {'buckets': [2, 4, 8],
                                             'pad_value': -1}})
        out = pad_ragged_batch({'tokens': col}, spec)
        assert out['tokens'].shape == (3, 4)        # bucket 4 covers max len 3
        np.testing.assert_array_equal(out['tokens_len'], [2, 1, 3])
        np.testing.assert_array_equal(out['tokens'][1], [3, -1, -1, -1])

    def test_bucket_overflow_raises(self):
        from petastorm_tpu.jax_utils import pad_ragged_batch, validate_pad_spec
        col = np.empty(1, dtype=object)
        col[0] = np.arange(10, dtype=np.int32)
        spec = validate_pad_spec({'t': {'max_len': 4}})
        with pytest.raises(ValueError, match='exceeds largest bucket'):
            pad_ragged_batch({'t': col}, spec)

    def test_spec_validation(self):
        from petastorm_tpu.jax_utils import validate_pad_spec
        with pytest.raises(ValueError, match='exactly one of'):
            validate_pad_spec({'t': {}})
        with pytest.raises(ValueError, match='unknown keys'):
            validate_pad_spec({'t': {'max_len': 4, 'bukets': [2]}})
        with pytest.raises(ValueError, match='positive'):
            validate_pad_spec({'t': {'buckets': [0, 4]}})

    def test_loader_pads_and_jit_consumes(self, ragged_url):
        import jax
        import jax.numpy as jnp
        with make_reader(ragged_url, reader_pool_type='dummy', num_epochs=1,
                         shuffle_row_groups=False) as reader:
            loader = JaxDataLoader(reader, batch_size=8, drop_last=True,
                                   pad_spec={'tokens': {'buckets': [8, 16, 32],
                                                        'pad_value': 0}})
            batches = list(loader)
        assert batches
        for b in batches:
            assert b['tokens'].dtype == np.int32
            assert b['tokens'].shape[1] in (8, 16, 32)
            assert b['tokens_len'].dtype == np.int32

            @jax.jit
            def masked_sum(tokens, lengths):
                mask = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]
                return jnp.sum(tokens * mask, axis=1)

            dev = masked_sum(jnp.asarray(b['tokens']), jnp.asarray(b['tokens_len']))
            # padded positions (pad_value 0 here, but mask regardless) excluded
            expected = [int(row[:n].sum()) for row, n in
                        zip(b['tokens'], b['tokens_len'])]
            np.testing.assert_array_equal(np.asarray(dev), expected)

    def test_batch_size_one_still_buckets(self, ragged_url):
        # a single-row batch arrives DENSE from _collate; it must still pad
        # to a bucket or every distinct length is a fresh XLA compile
        with make_reader(ragged_url, reader_pool_type='dummy', num_epochs=1,
                         shuffle_row_groups=False) as reader:
            loader = JaxDataLoader(reader, batch_size=1,
                                   pad_spec={'tokens': {'buckets': [32]}})
            widths = {b['tokens'].shape[1] for b in loader}
        assert widths == {32}

    def test_unknown_pad_field_fails_fast(self, ragged_url):
        with make_reader(ragged_url, reader_pool_type='dummy') as reader:
            with pytest.raises(ValueError, match='unknown fields'):
                JaxDataLoader(reader, batch_size=4,
                              pad_spec={'token': {'max_len': 8}})

    def test_sharded_loader_rejects_multi_bucket(self, ragged_url):
        import jax
        from jax.sharding import Mesh
        from petastorm_tpu.jax_utils import ShardedJaxLoader
        devices = jax.devices('cpu')
        if len(devices) < 8:
            pytest.skip('needs 8 CPU devices')
        mesh = Mesh(np.array(devices[:8]), ('data',))
        with make_reader(ragged_url, reader_pool_type='dummy') as reader:
            with pytest.raises(ValueError, match='single-bucket'):
                ShardedJaxLoader(reader, mesh, 8,
                                 pad_spec={'tokens': {'buckets': [8, 16]}})
            loader = ShardedJaxLoader(reader, mesh, 8,
                                      pad_spec={'tokens': {'max_len': 32}})
            batch = next(iter(loader))
            assert batch['tokens'].shape[1] == 32    # global, fixed width
