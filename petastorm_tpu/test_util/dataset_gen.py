"""Synthetic dataset generators for tests and benchmarks.

Reference parity: ``petastorm/tests/test_common.py`` (``TestSchema`` :39-57,
``create_test_dataset`` :98-297) — but written with the pyarrow-native
``materialize_dataset`` instead of a local Spark session (SURVEY.md §4).

Generators return the expected decoded rows so tests can do value-exact
round-trip asserts.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from petastorm_tpu.codecs import (ArrowListCodec, CompressedImageCodec, CompressedNdarrayCodec,
                                  NdarrayCodec, ScalarCodec)
from petastorm_tpu.etl.dataset_metadata import materialize_dataset
from petastorm_tpu.fs import get_filesystem_and_path_or_paths
from petastorm_tpu.unischema import Unischema, UnischemaField

TestSchema = Unischema('TestSchema', [
    UnischemaField('id', np.int64, (), ScalarCodec(), False),
    UnischemaField('id2', np.int32, (), ScalarCodec(), False),
    UnischemaField('id_float', np.float64, (), ScalarCodec(), False),
    UnischemaField('id_odd', np.bool_, (), ScalarCodec(), False),
    UnischemaField('partition_key', str, (), ScalarCodec(), False),
    UnischemaField('python_primitive_uint8', np.uint8, (), ScalarCodec(), False),
    UnischemaField('image_png', np.uint8, (16, 8, 3), CompressedImageCodec('png'), False),
    UnischemaField('matrix', np.float32, (8, 4, 3), NdarrayCodec(), False),
    UnischemaField('matrix_uint16', np.uint16, (2, 3), CompressedNdarrayCodec(), False),
    UnischemaField('matrix_nullable', np.int32, (None,), NdarrayCodec(), True),
    UnischemaField('sensor_name', str, (1,), NdarrayCodec(), False),
    UnischemaField('string_array_nullable', str, (None,), NdarrayCodec(), True),
])


def _row_for_id(i: int) -> Dict:
    """Deterministic row content for a given id (seeded per-row)."""
    rng = np.random.default_rng(i)
    return {
        'id': np.int64(i),
        'id2': np.int32(i % 5),
        'id_float': np.float64(i),
        'id_odd': np.bool_(i % 2),
        'partition_key': 'p_{}'.format(i % 10),
        'python_primitive_uint8': np.uint8(i % 255),
        'image_png': rng.integers(0, 255, (16, 8, 3), dtype=np.uint8),
        'matrix': rng.standard_normal((8, 4, 3)).astype(np.float32),
        'matrix_uint16': rng.integers(0, 2 ** 16, (2, 3), dtype='uint16').astype(np.uint16),
        'matrix_nullable': (rng.integers(0, 100, (4,), dtype='int64').astype(np.int32)
                            if i % 3 else None),
        'sensor_name': np.asarray(['sensor_{}'.format(i)]),
        'string_array_nullable': (np.asarray([str(i), 'abc']) if i % 4 else None),
    }


def create_test_dataset(url: str, ids, num_files: int = 4,
                        row_group_size_mb: float = 0.002) -> List[Dict]:
    """Materialize the full-featured ``TestSchema`` dataset; returns expected rows."""
    ids = list(ids)
    rows = [_row_for_id(i) for i in ids]
    rows_per_file = max(1, (len(rows) + num_files - 1) // num_files)
    with materialize_dataset(url, TestSchema, row_group_size_mb=row_group_size_mb,
                             rows_per_file=rows_per_file) as writer:
        writer.write_rows(rows)
    return rows


def create_test_scalar_dataset(url: str, num_rows: int, num_files: int = 2,
                               partition_by=None) -> List[Dict]:
    """Scalars-only petastorm_tpu dataset (reference ``create_test_scalar_dataset``)."""
    schema = Unischema('ScalarSchema', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('int_fixed_size_list', np.int32, (), ScalarCodec(), False),
        UnischemaField('float64', np.float64, (), ScalarCodec(), True),
        UnischemaField('string', str, (), ScalarCodec(), True),
    ])
    rows = [{'id': np.int64(i),
             'int_fixed_size_list': np.int32(i * 2),
             'float64': np.float64(i) / 3 if i % 7 else None,
             'string': 'hello_{}'.format(i)} for i in range(num_rows)]
    rows_per_file = max(1, (num_rows + num_files - 1) // num_files)
    with materialize_dataset(url, schema, rows_per_file=rows_per_file) as writer:
        writer.write_rows(rows)
    return rows


def create_partitioned_dataset(url: str, num_rows: int, num_partitions: int = 3) -> List[Dict]:
    """Hive-partitioned plain parquet store: ``part=p_K/part_*.parquet``."""
    fs, path, _ = get_filesystem_and_path_or_paths(url)
    rows = [{'id': i, 'value': float(i), 'part': 'p_{}'.format(i % num_partitions)}
            for i in range(num_rows)]
    for k in range(num_partitions):
        part_dir = '{}/part=p_{}'.format(path, k)
        fs.makedirs(part_dir, exist_ok=True)
        chunk = [{'id': r['id'], 'value': r['value']} for r in rows
                 if r['part'] == 'p_{}'.format(k)]
        table = pa.Table.from_pylist(chunk)
        with fs.open(part_dir + '/part_00000.parquet', 'wb') as f:
            pq.write_table(table, f, row_group_size=max(1, len(chunk) // 2))
    return rows


def create_non_petastorm_dataset(url: str, num_rows: int, num_files: int = 2) -> List[Dict]:
    """A plain parquet store (no ``_common_metadata``) for ``make_batch_reader`` tests."""
    fs, path, _ = get_filesystem_and_path_or_paths(url)
    fs.makedirs(path, exist_ok=True)
    rows = [{'id': i, 'value': float(i) * 1.5, 'name': 'row_{}'.format(i)}
            for i in range(num_rows)]
    per_file = max(1, (num_rows + num_files - 1) // num_files)
    for part, start in enumerate(range(0, num_rows, per_file)):
        chunk = rows[start:start + per_file]
        table = pa.Table.from_pylist(chunk)
        with fs.open('{}/part_{:05d}.parquet'.format(path, part), 'wb') as f:
            # Two row groups per file so row-group-granular features are exercised.
            pq.write_table(table, f, row_group_size=max(1, len(chunk) // 2))
    return rows


# ---------------------------------------------------------------------------
# seeded stores shaped like common training inputs
# ---------------------------------------------------------------------------

MnistImageSchema = Unischema('MnistImageSchema', [
    UnischemaField('idx', np.int64, (), ScalarCodec(), False),
    UnischemaField('image', np.uint8, (28, 28), CompressedImageCodec('png'), False),
    UnischemaField('label', np.int64, (), ScalarCodec(), False),
])


def generate_mnist_images_dataset(output_url: str, rows: int = 16384,
                                  seed: int = 0,
                                  row_group_size_mb: float = 0.5) -> str:
    """Synthetic MNIST-shaped dataset: png images + labels.

    Small row groups by default: a row group is the unit of worker
    parallelism, and tiny-png groups must outnumber the decode workers."""
    rng = np.random.default_rng(seed)

    def gen():
        for i in range(rows):
            yield {'idx': np.int64(i),
                   'image': rng.integers(0, 255, size=(28, 28), dtype=np.uint8),
                   'label': np.int64(i % 10)}

    with materialize_dataset(output_url, MnistImageSchema,
                             row_group_size_mb=row_group_size_mb) as writer:
        writer.write_rows(gen())
    return output_url


def make_token_schema(seq_len: int, ndarray_codec: bool = False) -> Unischema:
    # arrow_list: token windows decode vectorized in C++ (no per-row
    # np.load). ndarray_codec=True stores np.save payloads instead — the
    # opaque-bytes layout the batched and device decode paths take.
    codec = NdarrayCodec() if ndarray_codec else ArrowListCodec()
    return Unischema('TokenSchema', [
        UnischemaField('tokens', np.int32, (seq_len + 1,), codec, False),
    ])


def generate_token_dataset(output_url: str, rows: int = 2048,
                           seq_len: int = 256, vocab: int = 8192,
                           seed: int = 0,
                           row_group_size_mb: float = 4.0,
                           ndarray_codec: bool = False) -> str:
    """LM token windows: each row holds seq_len+1 tokens (input + shifted
    target), the shape the NGram pipeline emits for next-token training."""
    rng = np.random.default_rng(seed)
    schema = make_token_schema(seq_len, ndarray_codec=ndarray_codec)

    def gen():
        for _ in range(rows):
            yield {'tokens': rng.integers(0, vocab, size=(seq_len + 1,),
                                          dtype=np.int32)}

    with materialize_dataset(output_url, schema,
                             row_group_size_mb=row_group_size_mb) as writer:
        writer.write_rows(gen())
    return output_url


def generate_imagenet_dataset(output_url: str, rows: int = 256,
                              classes: int = 16, seed: int = 0,
                              row_group_size_mb: float = 8.0,
                              image_codec: str = 'png') -> str:
    """Synthetic ImageNet-style dataset at realistic sizes (~500x375),
    via the examples/imagenet ETL. ``image_codec='jpeg'`` matches real
    ImageNet files and enables DCT-scaled decode hints."""
    import examples.imagenet.generate_imagenet as gen
    gen.generate(output_url, gen.synthetic_rows(rows, classes=classes, seed=seed),
                 row_group_size_mb=row_group_size_mb, image_codec=image_codec)
    return output_url


def generate_timeseries_token_dataset(output_url: str, rows: int = 4096,
                                      chunk: int = 64, vocab: int = 8192,
                                      seed: int = 0,
                                      rows_per_group: int = 256) -> str:
    """Timestamped token chunks — the raw material for the NGram LM pipeline
    (SURVEY §5.7: NGram is *the* reference input pipeline for sequence
    models). Each row is one timestep: ``ts`` orders rows, ``tokens`` holds a
    fixed-size chunk; the NGram reader assembles consecutive rows into
    windows at read time.

    ``rows_per_group`` bounds the windows a single ventilated row group can
    pre-assemble: a row group is the streaming reader's unit of read-ahead."""
    rng = np.random.default_rng(seed)
    schema = Unischema('TimeseriesTokens', [
        UnischemaField('ts', np.int64, (), ScalarCodec(), False),
        UnischemaField('tokens', np.int32, (chunk,), ArrowListCodec(), False),
    ])

    def gen():
        for i in range(rows):
            yield {'ts': np.int64(i),
                   'tokens': rng.integers(0, vocab, size=(chunk,),
                                          dtype=np.int32)}

    with materialize_dataset(output_url, schema, row_group_size_mb=256,
                             rows_per_file=rows_per_group) as writer:
        writer.write_rows(gen())
    return output_url


def generate_readahead_dataset(url: str, rows: int, rows_per_group: int = 8):
    """Small petastorm store with one compressed-ndarray payload column."""
    schema = Unischema('ReadaheadBench', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('payload', np.uint8, (32, 32, 3),
                       CompressedNdarrayCodec(), False),
    ])
    # incompressible payload: row-group byte size tracks row count, so the
    # row_group_size_mb knob maps to rows_per_group deterministically
    payload_bytes = 32 * 32 * 3
    row_group_size_mb = rows_per_group * payload_bytes / (1024.0 * 1024.0)
    row_dicts = []
    for i in range(rows):
        rng = np.random.default_rng(i)
        row_dicts.append({
            'id': np.int64(i),
            'payload': rng.integers(0, 255, (32, 32, 3), dtype=np.uint8),
        })
    with materialize_dataset(url, schema, row_group_size_mb=row_group_size_mb,
                             rows_per_file=max(rows_per_group * 4, rows // 2)
                             ) as writer:
        writer.write_rows(row_dicts)
    return schema
