"""Zero-copy transport tests: serializer round-trips (in-process and through
a real 2-worker ``ProcessPool``), multipart frame semantics, the copy
counters on both ends of the hop, and the ``zmq_copy_buffers=False``
frame-lifetime regression."""

import gc

import numpy as np
import pyarrow as pa
import pytest

from petastorm_tpu.workers import EmptyResultError
from petastorm_tpu.workers.process_pool import ProcessPool
from petastorm_tpu.workers.serializers import (ArrowTableSerializer,
                                               PickleSerializer,
                                               ZeroCopySerializer, as_multipart)
from petastorm_tpu.workers.ventilator import ConcurrentVentilator
from petastorm_tpu.workers.worker_base import WorkerBase

SERIALIZERS = [PickleSerializer, ZeroCopySerializer]
SERIALIZER_IDS = ['pickle', 'zero_copy']


def roundtrip(serializer, payload):
    frames = serializer.serialize_multipart(payload)
    # the pool may hand back read-only buffers; mimic the strictest case
    frames = [memoryview(bytes(f)) for f in frames]
    return serializer.deserialize_multipart(frames)


def assert_payload_equal(actual, expected):
    if isinstance(expected, dict):
        assert set(actual) == set(expected)
        for key in expected:
            assert_payload_equal(actual[key], expected[key])
    elif isinstance(expected, (list, tuple)):
        assert len(actual) == len(expected)
        for a, e in zip(actual, expected):
            assert_payload_equal(a, e)
    elif isinstance(expected, np.ndarray):
        assert actual.dtype == expected.dtype
        if expected.dtype == object:
            assert actual.shape == expected.shape
            for a, e in zip(actual.ravel(), expected.ravel()):
                assert_payload_equal(a, e)
        else:
            np.testing.assert_array_equal(actual, expected)
    else:
        assert actual == expected


@pytest.mark.parametrize('serializer_cls', SERIALIZERS, ids=SERIALIZER_IDS)
class TestSerializerRoundTrips:
    def test_none_and_empty_payloads(self, serializer_cls):
        s = serializer_cls()
        assert roundtrip(s, None) is None
        assert roundtrip(s, []) == []
        assert roundtrip(s, {}) == {}
        assert_payload_equal(roundtrip(s, np.empty(0, np.float32)),
                             np.empty(0, np.float32))

    def test_zero_d_array(self, serializer_cls):
        s = serializer_cls()
        assert_payload_equal(roundtrip(s, np.asarray(np.float32(3.5))),
                             np.asarray(np.float32(3.5)))

    def test_large_array(self, serializer_cls):
        s = serializer_cls()
        big = np.arange(1 << 20, dtype=np.int64)  # 8 MB
        assert_payload_equal(roundtrip(s, big), big)

    def test_non_contiguous_array(self, serializer_cls):
        s = serializer_cls()
        base = np.arange(10000, dtype=np.float64).reshape(100, 100)
        strided = base[::2, ::3]
        assert not strided.flags['C_CONTIGUOUS']
        assert_payload_equal(roundtrip(s, strided), strided)

    def test_unicode_and_object_columns(self, serializer_cls):
        s = serializer_cls()
        payload = {
            'strings': np.asarray(['héllo', 'wörld', ''], dtype=object),
            'unicode': np.asarray(['αβγ', 'δεζ'], dtype='<U3'),
            'ragged': np.asarray([np.arange(3), np.arange(5)], dtype=object),
        }
        assert_payload_equal(roundtrip(s, payload), payload)

    def test_row_dict_list_payload(self, serializer_cls):
        s = serializer_cls()
        rows = [{'id': i, 'vec': np.full((7,), i, np.float32)} for i in range(5)]
        assert_payload_equal(roundtrip(s, rows), rows)


class TestZeroCopyFraming:
    def test_large_buffers_go_out_of_band(self):
        s = ZeroCopySerializer()
        payload = {'image': np.zeros((256, 256, 3), np.uint8),
                   'label': np.arange(4)}
        frames = s.serialize_multipart(payload)
        assert len(frames) == 2          # meta + the one >=64KB buffer
        assert len(frames[0]) < payload['image'].nbytes  # bytes not in the blob
        assert s.copies == 0

    def test_small_buffers_stay_in_band(self):
        s = ZeroCopySerializer()
        frames = s.serialize_multipart({'tiny': np.arange(8)})
        assert len(frames) == 1

    def test_deserialized_array_views_received_frames(self):
        s = ZeroCopySerializer()
        big = np.arange(1 << 18, dtype=np.int64)
        frames = s.serialize_multipart(big)
        out = s.deserialize_multipart(frames)
        # zero-copy reconstruction: the array's memory IS the received frame
        assert out.base is not None
        np.testing.assert_array_equal(out, big)

    def test_copy_counter_vs_pickle(self):
        payload = np.zeros(1 << 20, np.uint8)
        zc, pk = ZeroCopySerializer(), PickleSerializer()
        zc.deserialize_multipart(zc.serialize_multipart(payload))
        pk.deserialize_multipart(pk.serialize_multipart(payload))
        assert zc.copies == 0
        assert pk.copies == 2
        assert zc.copies < pk.copies

    def test_protocol5_metadata_frame(self):
        s = ZeroCopySerializer()
        frames = s.serialize_multipart(np.zeros(1 << 20, np.uint8))
        # frame 0 must be a protocol-5 pickle stream (PROTO opcode, version 5)
        assert frames[0][:2] == b'\x80\x05'


class TestArrowTableSerializer:
    def test_serialize_returns_buffer_not_bytes(self):
        s = ArrowTableSerializer()
        table = pa.table({'x': np.arange(100), 'y': np.arange(100.0)})
        payload = s.serialize(table)
        assert isinstance(payload, pa.Buffer)   # no to_pybytes re-copy
        assert s.copies == 1

    @pytest.mark.parametrize('wrap', [bytes, bytearray, memoryview,
                                      pa.py_buffer],
                             ids=['bytes', 'bytearray', 'memoryview', 'pa_buffer'])
    def test_deserialize_accepts_buffer_protocol(self, wrap):
        s = ArrowTableSerializer()
        table = pa.table({'x': np.arange(1000)})
        raw = s.serialize(table).to_pybytes()
        out = s.deserialize(wrap(raw))
        assert out.equals(table)

    def test_none_roundtrip(self):
        s = ArrowTableSerializer()
        assert s.deserialize(s.serialize(None)) is None
        assert s.deserialize(memoryview(b'')) is None

    def test_multipart_adapter_passthrough(self):
        s = ArrowTableSerializer()
        assert as_multipart(s) is s
        table = pa.table({'x': [1, 2, 3]})
        out = s.deserialize_multipart(s.serialize_multipart(table))
        assert out.equals(table)


def _drain(pool):
    results = []
    while True:
        try:
            results.append(pool.get_results(timeout=60))
        except EmptyResultError:
            return results


def make_image_payload(rows, height, width):
    """A decoded-image column batch: ``(rows, h, w, 3)`` uint8 plus labels,
    with deterministic content."""
    n = rows * height * width * 3
    image = (np.arange(n, dtype=np.uint32) % 251).astype(np.uint8)
    return {
        'image': image.reshape(rows, height, width, 3),
        'label': np.arange(rows, dtype=np.int64),
    }


class ImageStreamWorker(WorkerBase):
    """Publishes one decoded-image column batch per ventilated item."""

    def __init__(self, worker_id, publish_func, args):
        super().__init__(worker_id, publish_func, args)
        self._payload = make_image_payload(args['rows'], args['height'],
                                           args['width'])

    def process(self, item_index):
        self.publish_func(self._payload)


# zero-copy: no copy on either side; pickle: the worker's dump and the
# consumer's load, one full-payload copy each
@pytest.mark.parametrize('serializer_cls,copies_per_item',
                         [(ZeroCopySerializer, 0), (PickleSerializer, 2)],
                         ids=['zero_copy', 'pickle'])
@pytest.mark.parametrize('zmq_copy_buffers', [True, False],
                         ids=['copy', 'nocopy'])
def test_zero_copy_cross_process_roundtrip(zmq_copy_buffers, serializer_cls,
                                           copies_per_item):
    """Large decoded-image batches through a real 2-worker pool; with
    ``copy=False`` the arrays are views over ZMQ frame buffers, so content
    equality after a forced gc is the frame-lifetime regression check (a
    ``Frame.buffer`` memoryview outliving its frame corrupts data). The
    pool's ``payload_copies`` counts both ends of the hop."""
    rows, h, w = 24, 96, 96    # ~0.66 MB per payload, well out-of-band
    items = 6
    expected = make_image_payload(rows, h, w)
    pool = ProcessPool(2, serializer=serializer_cls(),
                       zmq_copy_buffers=zmq_copy_buffers)
    vent = ConcurrentVentilator(pool.ventilate,
                                [{'item_index': i} for i in range(items)],
                                iterations=1)
    pool.start(ImageStreamWorker,
               worker_args={'rows': rows, 'height': h, 'width': w},
               ventilator=vent)
    try:
        results = _drain(pool)
        assert len(results) == items
        # drop every pool-side reference we can and force collection: only
        # the received batches themselves may keep their frames alive
        gc.collect()
        for batch in results:
            np.testing.assert_array_equal(batch['image'], expected['image'])
            np.testing.assert_array_equal(batch['label'], expected['label'])
        assert pool.stats.snapshot()['payload_copies'] \
            == copies_per_item * items
    finally:
        pool.stop()
        pool.join()
