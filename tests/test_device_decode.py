"""Device-side decode (bytes-through) tests: plan eligibility and the
decline matrix, raw-view/repack layout proofs, bit-identity of the jitted
decoder against the numpy reference against the host codec across dtypes x
chunkings (multi-chunk, empty chunk, zero-size cells), the
``PETASTORM_TPU_DEVICE_DECODE`` kill switch, fused device ``TransformSpec``
equality, end-to-end bytes-through epochs with the
``rows_decoded_device``/``bytes_shipped_raw`` observability split and the
lineage coverage audit on thread AND process pools, the ETL repack of
``CompressedNdarrayCodec`` stores, the device-staging ``prefetch_depth``
knob, and ``_contiguous_rows_view`` edge cases."""

import numpy as np
import pyarrow as pa
import pytest

from petastorm_tpu.codecs import (CompressedNdarrayCodec, NdarrayCodec,
                                  batched_decode_enabled)
from petastorm_tpu.jax_utils import (DEFAULT_PREFETCH_DEPTH,
                                     PREFETCH_DEPTH_ENV_VAR, JaxDataLoader,
                                     _contiguous_rows_view, infeed_diagnosis,
                                     make_jax_loader, resolve_prefetch_depth)
from petastorm_tpu.ops.decode import (DEVICE_DECODE_ENV_VAR,
                                      DEVICE_DECODE_PROGRAM, DeviceColumnPlan,
                                      build_fused_infeed, decode_raw_host,
                                      decode_raw_jax, device_decode_enabled,
                                      npy_header_bytes, plan_device_decode,
                                      plan_for_field, raw_column_view,
                                      repack_to_raw, split_device_columns)
from petastorm_tpu.reader import make_columnar_reader, make_reader
from petastorm_tpu.transform import TransformSpec
from petastorm_tpu.unischema import Unischema, UnischemaField
from petastorm_tpu.workers.stats import device_decode_fraction

RNG = np.random.default_rng(11)

jax = pytest.importorskip('jax')


def _field(name='x', dtype=np.float32, shape=(4, 3), codec=None,
           nullable=False):
    return UnischemaField(name, dtype, shape,
                          codec if codec is not None else NdarrayCodec(),
                          nullable)


def _cells(field, values):
    return [field.codec.encode(field, v) for v in values]


def _chunked(cells, chunk_sizes=None):
    """A binary ChunkedArray from encoded cells, optionally split into the
    given chunk sizes (0 = an empty chunk in the middle)."""
    if chunk_sizes is None:
        return pa.chunked_array([pa.array(cells, type=pa.binary())])
    chunks, at = [], 0
    for size in chunk_sizes:
        chunks.append(pa.array(cells[at:at + size], type=pa.binary()))
        at += size
    assert at == len(cells), 'chunk_sizes must cover every cell'
    return pa.chunked_array(chunks, type=pa.binary())


def _values(dtype, shape, n):
    dtype = np.dtype(dtype)
    if dtype.kind == 'b':
        return [RNG.integers(0, 2, size=shape).astype(dtype)
                for _ in range(n)]
    if dtype.kind in 'iu':
        info = np.iinfo(dtype)
        return [RNG.integers(info.min, info.max, size=shape,
                             endpoint=True).astype(dtype) for _ in range(n)]
    return [RNG.standard_normal(shape).astype(dtype) for _ in range(n)]


class TestPlanning:
    def test_npy_header_bytes_pins_the_writer_prefix(self):
        import io
        header = npy_header_bytes(np.float32, (4, 3))
        buf = io.BytesIO()
        np.save(buf, np.zeros((4, 3), dtype=np.float32))
        assert header is not None
        assert buf.getvalue().startswith(header)

    def test_npy_header_bytes_declines_object_dtype(self):
        assert npy_header_bytes(np.dtype(object), (2,)) is None

    @pytest.mark.parametrize('dtype,shape', [
        (np.float32, (4, 3)), (np.int16, (7,)), (np.uint8, (2, 2, 3)),
        (np.bool_, (5,)), (np.float16, (3,)), (np.int32, (0,)),
    ])
    def test_eligible_fields_plan(self, dtype, shape):
        plan, reason = plan_for_field(_field(dtype=dtype, shape=shape))
        assert reason is None
        assert plan.dtype == np.dtype(dtype)
        assert plan.shape == shape
        assert plan.stride == plan.header_len + plan.cell_nbytes

    @pytest.mark.parametrize('field,why', [
        (_field(codec=CompressedNdarrayCodec()), 'zlib'),
        (_field(shape=(None, 3)), 'shape'),
        (_field(nullable=True), 'nullable'),
        (_field(dtype=np.str_, shape=()), ''),
    ])
    def test_ineligible_fields_decline_with_a_reason(self, field, why):
        plan, reason = plan_for_field(field)
        assert plan is None
        assert isinstance(reason, str) and reason

    def test_big_endian_declines(self):
        plan, reason = plan_for_field(_field(dtype=np.dtype('>f4')))
        assert plan is None and reason

    def test_8_byte_dtypes_need_x64(self):
        """Without jax x64, i8/f8 arrays canonicalize to 32-bit — a bitcast
        decode could not be bit-identical, so planning must decline."""
        plan, reason = plan_for_field(_field(dtype=np.int64, shape=(7,)))
        if jax.config.jax_enable_x64:
            assert reason is None
        else:
            assert plan is None and 'x64' in reason


class TestPlanDecliners:
    """The whole-reader decline matrix of docs/decode.md: every feature
    that needs decoded host values turns planning off wholesale, with the
    reason recorded under '*'; nothing ever raises."""

    SCHEMA = Unischema('S', [_field('tokens', np.int32, (8,))])

    def _declines(self, **kwargs):
        plans, declined = plan_device_decode(self.SCHEMA, enabled=True,
                                             **kwargs)
        assert plans == {}
        assert '*' in declined
        return declined['*']

    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'off')
        assert not device_decode_enabled()
        plans, declined = plan_device_decode(self.SCHEMA)
        assert plans == {}
        assert DEVICE_DECODE_ENV_VAR in declined['*']

    def test_kill_switch_default_on(self, monkeypatch):
        monkeypatch.delenv(DEVICE_DECODE_ENV_VAR, raising=False)
        assert device_decode_enabled()

    def test_percell_ab_switch_wins(self, monkeypatch):
        """PETASTORM_TPU_BATCHED_DECODE=0 demands the host per-cell loop;
        bytes-through would silently bypass it, so planning declines."""
        from petastorm_tpu.codecs import BATCHED_DECODE_ENV_VAR
        monkeypatch.setenv(BATCHED_DECODE_ENV_VAR, '0')
        assert not batched_decode_enabled()
        plans, declined = plan_device_decode(self.SCHEMA, enabled=True)
        assert plans == {}
        assert BATCHED_DECODE_ENV_VAR in declined['*']

    def test_row_granular_reader(self):
        assert 'row-granular' in self._declines(batched_output=False)

    def test_unsupported_worker(self):
        assert 'worker' in self._declines(worker_supported=False)

    def test_predicate(self):
        assert 'predicate' in self._declines(has_predicate=True)

    def test_ngram(self):
        assert 'NGram' in self._declines(has_ngram=True)

    def test_tolerant_decode(self):
        assert 'on_decode_error' in self._declines(tolerant_decode=True)

    def test_host_transform_spec(self):
        spec = TransformSpec(lambda c: c)
        assert 'device=True' in self._declines(transform_spec=spec)

    def test_device_spec_changing_field_set(self):
        spec = TransformSpec(lambda c: c, device=True,
                             removed_fields=['tokens'])
        transformed = Unischema('T', [_field('other', np.int32, (8,))])
        reason = self._declines(transform_spec=spec,
                                transformed_schema=transformed)
        assert 'field set' in reason

    def test_device_spec_in_place_plans(self):
        spec = TransformSpec(lambda c: c, device=True)
        plans, declined = plan_device_decode(self.SCHEMA, enabled=True,
                                             transform_spec=spec,
                                             transformed_schema=self.SCHEMA)
        assert set(plans) == {'tokens'}

    def test_decode_hints_decline_per_column(self):
        schema = Unischema('S2', [_field('a', np.int32, (4,)),
                                  _field('b', np.float32, (2,))])
        plans, declined = plan_device_decode(
            schema, enabled=True, decode_hints={'a': {'scale': 2}})
        assert set(plans) == {'b'}
        assert 'hint' in declined['a']


class TestRawViewAndBitIdentity:
    """The core property: for every eligible dtype and chunking, the raw
    grid decodes bit-identically through the numpy reference, the jitted
    device path, and the host codec itself."""

    CASES = [
        (np.float32, (4, 3), None),
        (np.float32, (4, 3), [3, 0, 5]),       # empty chunk mid-column
        (np.int16, (7,), [2, 6]),
        (np.uint8, (2, 2), None),              # itemsize-1 bitcast
        (np.bool_, (5,), [4, 4]),
        (np.float16, (3,), None),
        (np.int32, (0,), [3, 5]),              # zero-size cells
    ]

    @pytest.mark.parametrize('dtype,shape,chunks', CASES)
    def test_three_way_bit_identity(self, dtype, shape, chunks):
        field = _field(dtype=dtype, shape=shape)
        plan, reason = plan_for_field(field)
        assert reason is None
        values = _values(dtype, shape, 8)
        column = _chunked(_cells(field, values), chunks)
        raw = raw_column_view(column, plan)
        assert raw is not None
        assert raw.shape == (8, plan.stride) and raw.dtype == np.uint8
        host = decode_raw_host(plan, raw)
        device = np.asarray(decode_raw_jax(plan, raw))
        codec_ref = np.stack([field.codec.decode(field, c)
                              for c in _cells(field, values)])
        assert host.dtype == device.dtype == codec_ref.dtype
        assert host.shape == device.shape == codec_ref.shape
        assert bool(np.array_equal(host, codec_ref))
        assert bool(np.array_equal(device, codec_ref))

    def test_decode_under_jit_matches_eager(self):
        field = _field(dtype=np.int32, shape=(6,))
        plan, _ = plan_for_field(field)
        values = _values(np.int32, (6,), 5)
        raw = raw_column_view(_chunked(_cells(field, values)), plan)
        jitted = jax.jit(lambda r: decode_raw_jax(plan, r))
        assert bool(np.array_equal(np.asarray(jitted(raw)),
                                   decode_raw_host(plan, raw)))

    def test_raw_view_is_zero_copy_single_chunk(self):
        field = _field(dtype=np.float32, shape=(4,))
        plan, _ = plan_for_field(field)
        column = _chunked(_cells(field, _values(np.float32, (4,), 6)))
        raw = raw_column_view(column, plan)
        assert raw.base is not None   # a view over the arrow buffer

    def test_nulls_decline_to_repack(self):
        field = _field(dtype=np.float32, shape=(2,))
        plan, _ = plan_for_field(field)
        cells = _cells(field, _values(np.float32, (2,), 3))
        column = pa.chunked_array([pa.array(cells[:2] + [None],
                                            type=pa.binary())])
        assert raw_column_view(column, plan) is None

    def test_foreign_header_declines(self):
        field = _field(dtype=np.float32, shape=(2,))
        other = _field(dtype=np.int64, shape=(1,))
        plan, _ = plan_for_field(field)
        cells = _cells(other, _values(np.int64, (1,), 3))
        assert raw_column_view(_chunked(cells), plan) is None

    def test_stride_drift_declines(self):
        field = _field(dtype=np.float32, shape=(2,))
        plan, _ = plan_for_field(field)
        cells = _cells(field, _values(np.float32, (2,), 3))
        cells[1] += b'\x00'   # one cell longer than the pinned stride
        assert raw_column_view(_chunked(cells), plan) is None

    def test_repack_round_trips(self):
        field = _field(dtype=np.int16, shape=(3, 2))
        plan, _ = plan_for_field(field)
        decoded = np.stack(_values(np.int16, (3, 2), 5))
        raw = repack_to_raw(plan, decoded)
        assert raw.shape == (5, plan.stride)
        assert bool(np.array_equal(decode_raw_host(plan, raw), decoded))
        assert bool(np.array_equal(np.asarray(decode_raw_jax(plan, raw)),
                                   decoded))

    def test_repack_shape_mismatch_raises(self):
        plan, _ = plan_for_field(_field(dtype=np.int16, shape=(3, 2)))
        with pytest.raises(ValueError):
            repack_to_raw(plan, np.zeros((5, 2, 3), dtype=np.int16))

    def test_host_decode_is_writable(self):
        field = _field(dtype=np.float32, shape=(4,))
        plan, _ = plan_for_field(field)
        raw = raw_column_view(
            _chunked(_cells(field, _values(np.float32, (4,), 3))), plan)
        out = decode_raw_host(plan, raw)
        out[0, 0] = 1.5   # the per-cell contract: callers may mutate


class TestFusedInfeed:
    def test_fused_decode_plus_device_transform(self):
        field = _field('tokens', np.int32, (4,))
        plan, _ = plan_for_field(field)
        values = _values(np.int32, (4,), 6)
        raw = raw_column_view(_chunked(_cells(field, values)), plan)
        spec = TransformSpec(
            lambda cols: dict(cols, tokens=cols['tokens'] * 2), device=True)
        fused = build_fused_infeed({'tokens': plan}, spec)
        out = fused({'tokens': raw})
        expect = np.stack(values) * 2
        assert bool(np.array_equal(np.asarray(out['tokens']), expect))

    def test_program_compiles_under_a_stable_name(self):
        """A profiler trace names the fused program's runs by this name, so
        a trace reduction finds the staging layer's device work."""
        plan, _ = plan_for_field(_field('tokens', np.int32, (4,)))
        raw = np.zeros((2, plan.stride), dtype=np.uint8)
        text = build_fused_infeed({'tokens': plan}).lower(
            {'tokens': raw}).as_text()
        assert DEVICE_DECODE_PROGRAM == 'petastorm_device_decode'
        assert 'jit_petastorm_device_decode' in text

    def test_split_routes_only_planned_columns_by_default(self):
        """Unplanned columns must stay host numpy: silently returning them
        as immutable jax.Arrays breaks consumers that mutate in place."""
        plan, _ = plan_for_field(_field('tokens', np.int32, (4,)))
        batch = {'tokens': np.zeros((2, plan.stride), dtype=np.uint8),
                 'idx': np.arange(2),
                 'name': np.array(['a', 'b'], dtype=object)}
        device_cols, host_cols = split_device_columns(batch,
                                                      {'tokens': plan})
        assert set(device_cols) == {'tokens'}
        assert set(host_cols) == {'idx', 'name'}

    def test_split_includes_unplanned_numerics_for_fused_transform(self):
        """A fused device TransformSpec receives the full column dict, so
        unplanned numeric ndarrays ride the jit with it; object/str columns
        stay host either way."""
        plan, _ = plan_for_field(_field('tokens', np.int32, (4,)))
        batch = {'tokens': np.zeros((2, plan.stride), dtype=np.uint8),
                 'idx': np.arange(2),
                 'name': np.array(['a', 'b'], dtype=object)}
        device_cols, host_cols = split_device_columns(
            batch, {'tokens': plan}, include_unplanned=True)
        assert set(device_cols) == {'tokens', 'idx'}
        assert set(host_cols) == {'name'}


@pytest.fixture(scope='module')
def token_store(tmp_path_factory):
    from petastorm_tpu.test_util.dataset_gen import generate_token_dataset
    url = 'file://' + str(tmp_path_factory.mktemp('device_decode') / 'tok')
    generate_token_dataset(url, rows=64, seq_len=16, vocab=64, seed=3,
                           row_group_size_mb=0.01, ndarray_codec=True)
    return url


@pytest.fixture(scope='module')
def mixed_store(tmp_path_factory):
    """Two device-planned ndarray columns plus an UNPLANNED scalar column."""
    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import materialize_dataset
    url = 'file://' + str(tmp_path_factory.mktemp('device_decode_mixed')
                          / 'mix')
    schema = Unischema('M', [
        _field('vec', np.float32, (3,)),
        _field('aux', np.int16, (2,)),
        UnischemaField('idx', np.int32, (), ScalarCodec(), False),
    ])
    with materialize_dataset(url, schema, row_group_size_mb=0.01) as writer:
        for i in range(24):
            writer.write_row({'vec': np.full((3,), i, dtype=np.float32),
                              'aux': np.array([i, -i], dtype=np.int16),
                              'idx': np.int32(i)})
    return url


def _epoch_tokens(url, monkeypatch, device, pool='thread', loader=True,
                  **loader_kwargs):
    """One epoch's tokens (row-order stable: shuffle off) plus the stats
    snapshot, through the reader alone or reader + JaxDataLoader."""
    monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on' if device else 'off')
    collected = []
    with make_columnar_reader(url, num_epochs=1, reader_pool_type=pool,
                              workers_count=1,
                              shuffle_row_groups=False) as reader:
        declined = dict(reader.device_decode_declined)
        if loader:
            with JaxDataLoader(reader, batch_size=16,
                               **loader_kwargs) as jax_loader:
                for batch in jax_loader:
                    collected.append(np.asarray(batch['tokens']))
        else:
            for batch in reader:
                collected.append(np.asarray(batch.tokens))
        snapshot = reader._stats_snapshot()
        report = reader.audit().assert_complete()
    tokens = np.concatenate(collected) if collected else np.empty((0,))
    return tokens, snapshot, declined, report


class TestEndToEnd:
    """Bytes-through epochs vs the kill-switch-off baseline: bit-identical
    output, the counters prove which path decoded, and the lineage audit
    stays green on both pool types."""

    @pytest.mark.parametrize('pool', ['thread', 'process'])
    def test_loader_epoch_identical_and_audited(self, token_store,
                                                monkeypatch, pool):
        device, snap_dev, declined, _ = _epoch_tokens(
            token_store, monkeypatch, True, pool=pool)
        host, snap_host, _, _ = _epoch_tokens(
            token_store, monkeypatch, False, pool=pool)
        assert declined.get('*') is None
        assert device.dtype == host.dtype == np.int32
        assert bool(np.array_equal(device, host))
        assert snap_dev['rows_decoded_device'] == len(device)
        assert snap_dev['rows_decoded_batched'] == 0
        assert snap_dev['bytes_shipped_raw'] > 0
        assert snap_dev['device_decode_fraction'] == 1.0
        assert snap_host['rows_decoded_device'] == 0
        assert snap_host['rows_decoded_batched'] == len(host)
        assert snap_host['bytes_shipped_raw'] == 0
        assert snap_host['device_decode_fraction'] == 0.0

    def test_reader_without_loader_host_decodes(self, token_store,
                                                monkeypatch):
        """No loader claims the plans: __next__ host-decodes the raw grids
        so plain reader consumers see decoded columns, bit-identical."""
        raw_path, snap, _, _ = _epoch_tokens(token_store, monkeypatch, True,
                                             loader=False)
        host, _, _, _ = _epoch_tokens(token_store, monkeypatch, False,
                                      loader=False)
        assert bool(np.array_equal(raw_path, host))
        assert snap['bytes_shipped_raw'] > 0          # workers shipped raw
        assert snap['rows_decoded_batched'] == len(raw_path)  # host fallback

    def test_loader_device_decode_off_knob(self, token_store, monkeypatch):
        """device_decode=False on the loader: the reader keeps host-decoding
        even though it planned bytes-through."""
        tokens, snap, _, _ = _epoch_tokens(token_store, monkeypatch, True,
                                           device_decode=False)
        assert snap['rows_decoded_device'] == 0
        assert snap['rows_decoded_batched'] == len(tokens)

    def test_fused_device_transform_spec(self, token_store, monkeypatch):
        baseline, _, _, _ = _epoch_tokens(token_store, monkeypatch, False)
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        spec = TransformSpec(
            lambda cols: dict(cols, tokens=cols['tokens'] * 2), device=True)
        collected = []
        with make_columnar_reader(token_store, num_epochs=1,
                                  workers_count=1, shuffle_row_groups=False,
                                  transform_spec=spec) as reader:
            assert reader.device_decode_plans
            with JaxDataLoader(reader, batch_size=16) as loader:
                for batch in loader:
                    collected.append(np.asarray(batch['tokens']))
            snapshot = reader._stats_snapshot()
        assert bool(np.array_equal(np.concatenate(collected), baseline * 2))
        assert snapshot['device_decode_fraction'] == 1.0

    def test_unplanned_columns_stay_numpy(self, mixed_store, monkeypatch):
        """REVIEW fix: only PLANNED columns come back as jax.Arrays; the
        unplanned scalar column stays an np.ndarray (zero-copy collated
        batches are read-only per docs/decode.md, but the TYPE contract —
        numpy in, numpy out for unplanned columns — must hold with device
        decode on)."""
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        seen_types = []

        def bump_idx(batch):
            seen_types.append(type(batch['idx']))
            return dict(batch, idx=batch['idx'] + 1)

        collected = []
        with make_columnar_reader(mixed_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            assert set(reader.device_decode_plans) == {'vec', 'aux'}
            with JaxDataLoader(reader, batch_size=8,
                               transform_fn=bump_idx) as loader:
                for batch in loader:
                    assert isinstance(batch['idx'], np.ndarray)
                    collected.append((np.asarray(batch['idx']),
                                      np.asarray(batch['vec'])))
        assert seen_types and all(t is np.ndarray for t in seen_types)
        idx = np.concatenate([i for i, _ in collected])
        vec = np.concatenate([v for _, v in collected])
        assert bool(np.array_equal(np.sort(idx), np.arange(1, 25)))
        assert vec.dtype == np.float32 and vec.shape == (24, 3)
        assert bool(np.array_equal(vec[:, 0].astype(np.int64), idx - 1))

    def test_host_fallback_counts_rows_per_column(self, mixed_store,
                                                  monkeypatch):
        """REVIEW fix: the reader's no-loader host fallback accumulates
        rows per decoded COLUMN (2 planned columns here), matching the
        worker batched path's semantics so the derived fractions divide
        like-for-like."""
        def epoch(device):
            monkeypatch.setenv(DEVICE_DECODE_ENV_VAR,
                               'on' if device else 'off')
            with make_columnar_reader(mixed_store, num_epochs=1,
                                      workers_count=1,
                                      shuffle_row_groups=False) as reader:
                rows = sum(len(batch.idx) for batch in reader)
                return rows, reader._stats_snapshot()

        rows, snap_fallback = epoch(True)
        _, snap_host = epoch(False)
        assert rows == 24
        assert snap_fallback['rows_decoded_batched'] == 2 * rows
        assert (snap_fallback['rows_decoded_batched']
                == snap_host['rows_decoded_batched'])

    def test_row_reader_declines_wholesale(self, token_store, monkeypatch):
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        with make_reader(token_store, num_epochs=1,
                         shuffle_row_groups=False) as reader:
            assert reader.device_decode_plans == {}
            assert '*' in reader.device_decode_declined
            next(iter(reader))

    def test_infeed_diagnosis_carries_split(self, token_store, monkeypatch):
        _, snapshot, _, _ = _epoch_tokens(token_store, monkeypatch, True)
        diag = infeed_diagnosis(snapshot)
        device = diag['device']
        assert device['rows_decoded_device'] == snapshot['rows_decoded_device']
        assert device['bytes_shipped_raw'] == snapshot['bytes_shipped_raw']
        assert device['device_decode_fraction'] == 1.0
        assert 'goodput_fraction' in device
        assert 'data_stall_fraction' in device
        assert 'prefetch_occupancy' in device

    def test_fraction_derivation(self):
        assert device_decode_fraction({'rows_decoded_device': 3,
                                       'rows_decoded_batched': 1}) == 0.75
        assert device_decode_fraction({}) is None


class TestShardedLoader:
    def test_sharded_decode_post_staging(self, token_store, monkeypatch):
        from petastorm_tpu.jax_utils import ShardedJaxLoader
        from jax.sharding import Mesh
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        mesh = Mesh(np.array(jax.devices()[:1]), ('data',))
        baseline, _, _, _ = _epoch_tokens(token_store, monkeypatch, False)
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        collected = []
        with make_columnar_reader(token_store, num_epochs=1,
                                  workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with ShardedJaxLoader(reader, mesh,
                                  local_batch_size=16) as loader:
                for batch in loader:
                    collected.append(np.asarray(batch['tokens']))
            snapshot = reader._stats_snapshot()
        got = np.concatenate(collected)
        assert got.dtype == np.int32
        assert bool(np.array_equal(got, baseline))
        assert snapshot['rows_decoded_device'] == len(got)
        assert snapshot['device_decode_fraction'] == 1.0

    def test_prefetched_global_batches_stage_once(self, token_store,
                                                 monkeypatch):
        """The four-chip cell's feed: ``ShardedJaxLoader`` under
        ``prefetch_to_device(iter(loader))``. Each batch is staged and
        decoded once, in ``stage_to_global``; the prefetcher has nothing
        left to transfer and records no second ``device_stage``."""
        from petastorm_tpu.jax_utils import (ShardedJaxLoader,
                                             prefetch_to_device)
        from jax.sharding import Mesh
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
        with make_columnar_reader(token_store, num_epochs=1,
                                  workers_count=1, shuffle_row_groups=False,
                                  trace=True) as reader:
            with ShardedJaxLoader(reader, mesh, local_batch_size=8) as loader:
                batches = list(prefetch_to_device(iter(loader), size=2))
                steps = loader.goodput.summary()['steps']
            snapshot = reader.stats.snapshot()
            stages = [s for s in reader.tracer.spans()
                      if s[0] == 'device_stage']
            latency = reader.stats.latency.histograms['device_stage'].count
        assert len(batches) == 8
        assert all(len(b['tokens'].sharding.device_set) == 4
                   for b in batches)
        assert len(stages) == latency == snapshot['batches_out'] == 8
        assert steps == 8
        assert snapshot['rows_decoded_device'] == 64

    def test_transform_fn_declines_claim_and_sees_decoded_numpy(
            self, token_store, monkeypatch):
        """REVIEW fix: a host transform_fn runs pre-staging in the inner
        loader, where post-staging device decode has not happened yet — so
        the sharded loader must decline the bytes-through claim and let the
        reader host-decode. The transform must see decoded int32 numpy,
        never the raw (n, stride) uint8 grid."""
        from jax.sharding import Mesh
        from petastorm_tpu.jax_utils import ShardedJaxLoader
        baseline, _, _, _ = _epoch_tokens(token_store, monkeypatch, False)
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        mesh = Mesh(np.array(jax.devices()[:1]), ('data',))
        seen = []

        def double(batch):
            seen.append((batch['tokens'].dtype, batch['tokens'].shape))
            return dict(batch, tokens=np.asarray(batch['tokens']) * 2)

        collected = []
        with make_columnar_reader(token_store, num_epochs=1,
                                  workers_count=1,
                                  shuffle_row_groups=False) as reader:
            assert reader.device_decode_plans   # the reader DID plan
            with ShardedJaxLoader(reader, mesh, local_batch_size=16,
                                  transform_fn=double) as loader:
                for batch in loader:
                    collected.append(np.asarray(batch['tokens']))
            snapshot = reader._stats_snapshot()
        got = np.concatenate(collected)
        assert seen and all(dt == np.int32 for dt, _ in seen)
        assert all(shape[1:] == baseline.shape[1:] for _, shape in seen)
        assert bool(np.array_equal(got, baseline * 2))
        # nothing decoded on device: the claim was declined, the reader
        # host-decoded and the host counters carry the whole epoch
        assert snapshot['rows_decoded_device'] == 0
        assert snapshot['rows_decoded_batched'] == len(got)


class TestEtlRepack:
    @pytest.fixture(scope='class')
    def compressed_store(self, tmp_path_factory):
        from petastorm_tpu.etl.dataset_metadata import materialize_dataset
        url = 'file://' + str(tmp_path_factory.mktemp('repack') / 'zlib')
        schema = Unischema('Z', [
            _field('emb', np.float32, (4, 3), CompressedNdarrayCodec()),
            _field('tag', np.int32, (2,), NdarrayCodec()),
        ])
        rows = [{'emb': RNG.standard_normal((4, 3)).astype(np.float32),
                 'tag': np.array([i, i + 1], dtype=np.int32)}
                for i in range(12)]
        with materialize_dataset(url, schema,
                                 row_group_size_mb=0.01) as writer:
            for row in rows:
                writer.write_row(row)
        return url, rows

    def test_repack_schema_swaps_codecs(self, compressed_store):
        from petastorm_tpu.etl.dataset_metadata import \
            get_schema_from_dataset_url
        from petastorm_tpu.etl.repack import repack_schema
        schema = get_schema_from_dataset_url(compressed_store[0])
        out, repacked = repack_schema(schema)
        assert repacked == ['emb']
        assert isinstance(out.fields['emb'].codec, NdarrayCodec)
        assert isinstance(out.fields['tag'].codec, NdarrayCodec)

    def test_repack_nullable_field_warns_still_ineligible(self, caplog):
        """REVIEW fix: the codec swap cannot fix static decliners like
        nullable=True — the repack must say so instead of silently
        producing a store that still declines device decode."""
        import logging
        from petastorm_tpu.etl.repack import (repack_schema,
                                              still_ineligible_after_repack)
        schema = Unischema('N', [
            _field('emb', np.float32, (2,), CompressedNdarrayCodec(),
                   nullable=True),
            _field('ok', np.float32, (2,), CompressedNdarrayCodec()),
        ])
        with caplog.at_level(logging.WARNING,
                             logger='petastorm_tpu.etl.repack'):
            out, repacked = repack_schema(schema)
        assert sorted(repacked) == ['emb', 'ok']
        reasons = still_ineligible_after_repack(out, repacked)
        assert set(reasons) == {'emb'}
        assert 'nullable' in reasons['emb']
        assert any('emb' in r.message and 'INELIGIBLE' in r.message
                   for r in caplog.records)

    def test_repack_schema_rejects_bad_field_names(self, compressed_store):
        from petastorm_tpu.etl.dataset_metadata import \
            get_schema_from_dataset_url
        from petastorm_tpu.etl.repack import repack_schema
        schema = get_schema_from_dataset_url(compressed_store[0])
        with pytest.raises(ValueError):
            repack_schema(schema, fields=['nope'])
        with pytest.raises(ValueError):
            repack_schema(schema, fields=['tag'])   # already NdarrayCodec

    def test_repacked_store_is_device_eligible_and_identical(
            self, compressed_store, tmp_path, monkeypatch):
        from petastorm_tpu.etl.repack import repack_to_ndarray_codec
        source_url, rows = compressed_store
        out_url = 'file://' + str(tmp_path / 'repacked')
        summary = repack_to_ndarray_codec(source_url, out_url)
        assert summary['rows'] == len(rows)
        assert summary['repacked_fields'] == ['emb']
        assert summary['still_ineligible'] == {}

        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'on')
        with make_columnar_reader(source_url, num_epochs=1,
                                  shuffle_row_groups=False) as reader:
            assert 'emb' in reader.device_decode_declined
        got = {}
        with make_columnar_reader(out_url, num_epochs=1,
                                  shuffle_row_groups=False) as reader:
            assert 'emb' in reader.device_decode_plans
            with JaxDataLoader(reader, batch_size=4) as loader:
                for batch in loader:
                    tags = np.asarray(batch['tag'])
                    embs = np.asarray(batch['emb'])
                    for i in range(len(tags)):
                        got[int(tags[i][0])] = embs[i]
            assert reader._stats_snapshot()['device_decode_fraction'] == 1.0
        assert len(got) == len(rows)
        for i, row in enumerate(rows):
            assert bool(np.array_equal(got[i], row['emb']))


class TestContiguousRowsViewEdges:
    """ISSUE-16 satellite: the zero-copy collate's edge cases."""

    def _col(self, n=10, shape=(4, 3)):
        # .copy() so the column OWNS its buffer (reshape alone returns a
        # view of the flat arange, collapsing row .base to the 1-D owner)
        return np.arange(n * int(np.prod(shape)),
                         dtype=np.float32).reshape((n,) + shape).copy()

    def test_empty_batch_declines(self):
        assert _contiguous_rows_view([]) is None

    def test_single_row_is_a_one_row_slice(self):
        col = self._col()
        out = _contiguous_rows_view([col[3]])
        assert out is not None and out.shape == (1, 4, 3)
        assert out.base is col
        assert bool(np.array_equal(out, col[3:4]))

    def test_non_owned_base_resolves_to_the_owner(self):
        """Rows sliced from a view: numpy collapses .base to the owning
        array, and the collate must still find the right range in it."""
        owner = self._col(12)
        col = owner[2:10]       # non-owning
        rows = [col[i] for i in range(3, 6)]
        out = _contiguous_rows_view(rows)
        assert out is not None
        assert out.base is owner
        assert bool(np.array_equal(out, owner[5:8]))

    def test_read_only_views_share_writability(self):
        col = self._col()
        col.setflags(write=False)
        out = _contiguous_rows_view([col[i] for i in range(2, 5)])
        assert out is not None
        assert not out.flags.writeable   # the slice shares the column's
        assert bool(np.array_equal(out, col[2:5]))

    def test_shuffled_rows_decline(self):
        col = self._col()
        assert _contiguous_rows_view([col[4], col[2], col[3]]) is None


class TestPrefetchDepthKnob:
    def test_default(self, monkeypatch):
        monkeypatch.delenv(PREFETCH_DEPTH_ENV_VAR, raising=False)
        assert resolve_prefetch_depth(None) == DEFAULT_PREFETCH_DEPTH

    def test_env_var(self, monkeypatch):
        monkeypatch.setenv(PREFETCH_DEPTH_ENV_VAR, '5')
        assert resolve_prefetch_depth(None) == 5

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv(PREFETCH_DEPTH_ENV_VAR, '5')
        assert resolve_prefetch_depth(3) == 3

    @pytest.mark.parametrize('bad', [0, -1, 'junk', 2.5])
    def test_invalid_depths_raise(self, bad):
        with pytest.raises(ValueError):
            resolve_prefetch_depth(bad)

    def test_invalid_env_raises_at_construction(self, monkeypatch):
        monkeypatch.setenv(PREFETCH_DEPTH_ENV_VAR, 'zero')
        with pytest.raises(ValueError):
            resolve_prefetch_depth(None)

    def test_loader_and_factory_thread_the_knob(self, token_store,
                                                monkeypatch):
        monkeypatch.setenv(PREFETCH_DEPTH_ENV_VAR, '4')
        with make_columnar_reader(token_store, num_epochs=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                assert loader.prefetch_depth == 4
                for _ in loader:
                    pass
        with make_columnar_reader(token_store, num_epochs=1,
                                  shuffle_row_groups=False) as reader:
            with make_jax_loader(reader, batch_size=16,
                                 prefetch_depth=3) as loader:
                assert loader.prefetch_depth == 3
                for _ in loader:
                    pass
