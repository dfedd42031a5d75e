"""Per-field codecs: encode numpy values into Parquet-storable cells and back.

Reference parity: ``petastorm/codecs.py`` (``CompressedImageCodec`` :58-130,
``NdarrayCodec`` :133-171, ``CompressedNdarrayCodec`` :174-212, ``ScalarCodec``
:215-271, shape check ``_is_compliant_shape`` :274-294).

Deviation from the reference (deliberate): codecs are serialized to **JSON by
registered name**, never pickled, so codec class paths are not an ABI
(the reference admits the pickle-ABI trap at ``codecs.py:20-21``). Storage types
are expressed as ``pyarrow`` types instead of Spark SQL types — the write path is
pyarrow-native, no JVM.
"""

from __future__ import annotations

import inspect
import io
import os
import re
from abc import ABC, abstractmethod
from itertools import repeat
from typing import Any, Dict, Type

import numpy as np
import pyarrow as pa

#: Environment variable gating the row-group-vectorized (batched) decode
#: path (default on). ``0``/``false``/``off`` forces every codec column
#: through the per-cell loop — the uniform observability/behavior kill
#: switch shape (``PETASTORM_TPU_HEALTH``, ``PETASTORM_TPU_LINEAGE``,
#: ``PETASTORM_TPU_PROFILER``). The two paths are bit-identical by
#: contract (``docs/decode.md``); the switch is an escape hatch.
BATCHED_DECODE_ENV_VAR = 'PETASTORM_TPU_BATCHED_DECODE'


def batched_decode_enabled() -> bool:
    """The :data:`BATCHED_DECODE_ENV_VAR` gate (default on). Read once per
    worker at construction, never per cell."""
    value = os.environ.get(BATCHED_DECODE_ENV_VAR, '').strip().lower()
    return value not in ('0', 'false', 'off')


def split_binary_chunk(chunk: pa.Array):
    """``(offsets, data)`` of one (large_)binary arrow chunk: the int
    offsets vector and the shared ``uint8`` data buffer, both zero-copy.
    Cell ``i`` is ``data[offsets[i]:offsets[i + 1]]`` — the one
    buffer-splitting primitive under every batched decoder and the
    per-cell view builder."""
    n = len(chunk)
    _validity, offsets_buf, data_buf = chunk.buffers()
    off_dtype = np.dtype(
        np.int64 if pa.types.is_large_binary(chunk.type) else np.int32)
    offsets = np.frombuffer(offsets_buf, dtype=off_dtype, count=n + 1,
                            offset=chunk.offset * off_dtype.itemsize)
    data = (np.frombuffer(data_buf, dtype=np.uint8)
            if data_buf is not None else np.empty(0, np.uint8))
    return offsets, data


class DataframeColumnCodec(ABC):
    """Abstract codec translating one field's numpy value to a storable cell.

    Mirrors the reference ABC at ``codecs.py:36-55``.
    """

    #: Registry key; subclasses must set a unique stable name (it is written
    #: into dataset metadata and must remain valid across versions).
    codec_name: str = None

    @abstractmethod
    def encode(self, unischema_field, value):
        """Encode ``value`` (numpy) into an arrow-storable python value."""

    @abstractmethod
    def decode(self, unischema_field, value):
        """Decode a storable value back to the numpy form declared by the field."""

    def make_cell_decoder(self, unischema_field):
        """Return a callable decoding ONE cell of this field's column.

        The columnar reader calls this once per column and then invokes the
        returned callable per cell, so per-column setup (module lookups, flag
        resolution) hoists out of the hot loop. Cells arrive as zero-copy
        ``uint8`` ndarray views over the arrow data buffer; this default
        adapter converts them to ``bytes`` for codecs whose :meth:`decode`
        expects that. Override for a per-cell fast path."""
        def decode_cell(cell):
            return self.decode(
                unischema_field,
                cell.tobytes() if isinstance(cell, np.ndarray) else cell)
        return decode_cell

    def make_column_decoder(self, unischema_field):
        """Return ``decode_chunk(chunk: pa.Array) -> Optional[np.ndarray]``
        decoding one null-free (large_)binary column chunk in a single
        shot, or ``None`` when this codec has no vectorized path.

        Contract (``docs/decode.md``): the reader calls the returned
        callable only for fixed-shape fields on null-free chunks, with no
        per-field decode override in play. The callable returns the decoded
        ``(len(chunk), *shape)`` array **bit-identical** to what the
        per-cell loop produces for the same chunk, or ``None`` to punt a
        chunk it cannot vectorize; it may also raise on corrupt data — the
        reader then retries the column per cell, so quarantine row offsets
        and error semantics are exactly the per-cell loop's. Never return
        an approximation."""
        return None

    def device_decode_unsupported_reason(self, unischema_field):
        """``None`` when this codec's stored cells for ``unischema_field``
        can decode on the accelerator under ``jax.jit``
        (``ops/decode.py``), else a human-readable decline reason. The
        default is ineligible: device decode is opt-in per codec, and a
        decline routes the column to the host matrix — it never owns an
        error."""
        return 'codec {} has no device-decode path'.format(
            type(self).__name__)

    @abstractmethod
    def arrow_type(self, unischema_field) -> pa.DataType:
        """The pyarrow storage type used for this field's column."""

    def to_json_dict(self) -> Dict[str, Any]:
        return {'codec': self.codec_name}

    @classmethod
    def from_json_dict(cls, d: Dict[str, Any]) -> 'DataframeColumnCodec':
        return cls()

    def __eq__(self, other):
        return isinstance(other, type(self)) and self.to_json_dict() == other.to_json_dict()

    def __hash__(self):
        return hash(repr(sorted(self.to_json_dict().items())))

    def __repr__(self):
        return '{}()'.format(type(self).__name__)


_CODEC_REGISTRY: Dict[str, Type[DataframeColumnCodec]] = {}


def register_codec(cls: Type[DataframeColumnCodec]) -> Type[DataframeColumnCodec]:
    """Class decorator adding a codec to the JSON (de)serialization registry."""
    assert cls.codec_name, 'codec_name must be set'
    _CODEC_REGISTRY[cls.codec_name] = cls
    return cls


def codec_from_json_dict(d: Dict[str, Any]) -> DataframeColumnCodec:
    name = d['codec']
    if name not in _CODEC_REGISTRY:
        raise ValueError('Unknown codec name {!r}; known: {}'.format(name, sorted(_CODEC_REGISTRY)))
    return _CODEC_REGISTRY[name].from_json_dict(d)


def _is_compliant_shape(actual: tuple, expected: tuple) -> bool:
    """True if ``actual`` matches ``expected`` where ``None`` is a wildcard.

    Reference: ``codecs.py:274-294``.
    """
    if len(actual) != len(expected):
        return False
    for a, e in zip(actual, expected):
        if e is not None and a != e:
            return False
    return True


def _check_shape(field, value: np.ndarray):
    if not _is_compliant_shape(value.shape, field.shape):
        raise ValueError(
            'Field {!r} with shape {} got a value of non-compliant shape {}'.format(
                field.name, field.shape, value.shape))


def _check_dtype(field, value: np.ndarray):
    """dtype compliance; string/bytes fields match by kind since unicode/bytes
    itemsize varies per value."""
    declared = field.numpy_dtype
    if declared is str:
        ok = value.dtype.kind == 'U'
    elif declared is bytes:
        ok = value.dtype.kind == 'S'
    else:
        declared = np.dtype(declared)
        ok = (value.dtype.kind == declared.kind if declared.kind in 'US'
              else value.dtype == declared)
    if not ok:
        raise ValueError('Field {!r} expected dtype {} got {}'.format(
            field.name, field.numpy_dtype, value.dtype))


# Strict matcher for the header np.save itself generates. np.load parses this
# dict with ast.literal_eval (compile + AST walk) on EVERY cell — ~8% of a
# decode-bound reader's CPU in profiles. Payloads matching this exact
# machine-generated form take the fast path; anything else (fortran order,
# structured/object dtypes, hand-crafted files) falls back to np.load.
_NPY_FAST_HEADER = re.compile(
    rb"^\{'descr': '([<>=|][a-zA-Z]\d*)', 'fortran_order': False, "
    rb"'shape': \((\d*(?:, ?\d+)*,?)\), \}\s*$")


def _parse_fast_npy_header(value):
    """``(dtype, shape, header_end)`` of a standard-form ``np.save`` v1
    payload prefix, or ``None`` when the header is not machine-generated
    v1 (fortran order, object dtype, hand-crafted). ``value`` is any
    sliceable buffer (bytes or memoryview)."""
    # magic \x93NUMPY, version (1,0), little-endian u2 header length
    if len(value) < 10 or bytes(value[:8]) != b'\x93NUMPY\x01\x00':
        return None
    hlen = value[8] | (value[9] << 8)
    header_end = 10 + hlen
    m = _NPY_FAST_HEADER.match(value[10:header_end])
    if m is None:
        return None
    dtype = np.dtype(m.group(1).decode())
    if dtype.hasobject:          # pickled payload — np.load territory
        return None
    shape_src = m.group(2)
    shape = tuple(int(p) for p in shape_src.replace(b' ', b'').split(b',') if p) \
        if shape_src else ()
    return dtype, shape, header_end


def _fast_npy_decode(value):
    """Decode an ``np.save`` payload without ast-based header parsing;
    returns None when the payload is not in the standard v1 form.
    ``value`` may be ``bytes`` or any buffer-protocol object (the columnar
    reader passes zero-copy uint8 ndarray views).

    Returns a WRITABLE array (one memcpy), matching what ``np.load`` gives
    consumers on the fallback path — an in-place transform must not work for
    one serialization form and crash for another."""
    if isinstance(value, np.ndarray):
        value = memoryview(value)
    parsed = _parse_fast_npy_header(value)
    if parsed is None:
        return None
    dtype, shape, header_end = parsed
    flat = np.frombuffer(value, dtype=dtype, offset=header_end)
    return flat.reshape(shape).copy()


@register_codec
class NdarrayCodec(DataframeColumnCodec):
    """Lossless ndarray <-> bytes via ``np.save`` (reference ``codecs.py:133-171``)."""

    codec_name = 'ndarray'

    def encode(self, unischema_field, value):
        _check_dtype(unischema_field, value)
        _check_shape(unischema_field, value)
        memfile = io.BytesIO()
        np.save(memfile, value)
        return memfile.getvalue()

    def decode(self, unischema_field, value):
        fast = _fast_npy_decode(value)
        if fast is not None:
            return fast
        memfile = io.BytesIO(value)
        return np.load(memfile)

    def make_cell_decoder(self, unischema_field):
        # _fast_npy_decode and BytesIO both take buffer views directly; no
        # bytes materialization needed.
        def decode_cell(cell):
            fast = _fast_npy_decode(cell)
            if fast is not None:
                return fast
            return np.load(io.BytesIO(cell))
        return decode_cell

    def make_column_decoder(self, unischema_field):
        """Vectorized whole-chunk decode: when every cell is the same
        machine-generated ``np.save`` v1 payload (identical header bytes,
        identical stride — the invariant a fixed-shape column written by
        :meth:`encode` satisfies by construction), the entire chunk decodes
        with ONE header compare and ONE contiguous copy instead of N
        Python calls. Anything else punts to the per-cell loop."""
        shape = unischema_field.shape
        if shape is None or any(s is None for s in shape):
            return None   # wildcard fields keep the per-cell object contract

        def decode_chunk(chunk):
            if chunk.null_count:
                return None
            n = len(chunk)
            offsets, data = split_binary_chunk(chunk)
            stride = int(offsets[1]) - int(offsets[0])
            if stride <= 10 or not bool(
                    np.all(np.diff(offsets) == stride)):
                return None
            block = data[int(offsets[0]):int(offsets[-1])]
            parsed = _parse_fast_npy_header(memoryview(block[:stride]))
            if parsed is None:
                return None
            dtype, cell_shape, header_end = parsed
            expected = int(np.prod(cell_shape, dtype=np.int64)) * dtype.itemsize
            if stride - header_end != expected:
                return None
            grid = block.reshape(n, stride)
            # one vectorized compare proves every cell shares the first
            # cell's exact header (dtype AND shape), so one copy decodes all
            if not bool((grid[:, :header_end] == grid[0, :header_end]).all()):
                return None
            payload = np.ascontiguousarray(grid[:, header_end:])
            if not payload.flags.writeable:
                # a 1-row chunk's payload slice is already contiguous, so
                # ascontiguousarray returns the read-only arrow-buffer view
                # itself; the per-cell path promises WRITABLE arrays
                payload = payload.copy()
            if not expected:      # zero-size cells: nothing to reinterpret
                return np.empty((n,) + cell_shape, dtype=dtype)
            return payload.view(dtype).reshape((n,) + cell_shape)
        return decode_chunk

    def device_decode_unsupported_reason(self, unischema_field):
        """Eligible when the stored layout is statically provable: fixed
        shape (every cell shares one ``np.save`` header), non-nullable
        (the raw grid has no slot for missing cells), plain little-endian
        numeric/bool dtype (``lax.bitcast_convert_type`` reinterprets
        native-order bytes only)."""
        import sys
        shape = unischema_field.shape
        if shape is None or any(s is None for s in shape):
            return 'wildcard shape: cells do not share one np.save header'
        if unischema_field.nullable:
            return 'nullable field: the raw grid has no missing-cell slot'
        try:
            dtype = np.dtype(unischema_field.numpy_dtype)
        except TypeError:
            return 'field dtype is not a numpy dtype'
        if dtype.kind not in 'biuf':
            return 'dtype kind {!r} is not device-representable'.format(
                dtype.kind)
        if dtype.itemsize > 1 and (dtype.str[0] == '>'
                                   or sys.byteorder != 'little'):
            return 'big-endian payload: device bitcast is little-endian'
        return None

    def arrow_type(self, unischema_field):
        return pa.binary()


@register_codec
class ArrowListCodec(DataframeColumnCodec):
    """Numeric ndarrays stored as **native arrow list columns** instead of
    opaque ``np.save`` bytes.

    TPU-first design, no reference analogue: with values living in arrow's own
    layout, the columnar reader decodes an entire row group with zero Python
    per row (``flatten().to_numpy().reshape``), which matters for token/embed
    pipelines feeding accelerators. Requires a numeric dtype; the shape may
    contain wildcards only if it is 1-D (arrow lists are variable-length).
    """

    codec_name = 'arrow_list'

    def encode(self, unischema_field, value):
        value = np.asarray(value)
        _check_dtype(unischema_field, value)
        _check_shape(unischema_field, value)
        return value.ravel()

    def decode(self, unischema_field, value):
        arr = np.asarray(value, dtype=np.dtype(unischema_field.numpy_dtype))
        shape = unischema_field.shape
        if shape and all(s is not None for s in shape):
            return arr.reshape(shape)
        return arr

    def arrow_type(self, unischema_field):
        dtype = np.dtype(unischema_field.numpy_dtype)
        if dtype.kind not in 'biuf':
            raise ValueError('ArrowListCodec requires a numeric dtype; field '
                             '{!r} has {}'.format(unischema_field.name, dtype))
        shape = unischema_field.shape
        if shape and any(s is None for s in shape) and len(shape) != 1:
            raise ValueError('ArrowListCodec wildcard shapes must be 1-D; field '
                             '{!r} has shape {}'.format(unischema_field.name, shape))
        return pa.list_(pa.from_numpy_dtype(dtype))


@register_codec
class CompressedNdarrayCodec(DataframeColumnCodec):
    """Zlib-compressed ndarray via ``np.savez_compressed`` (reference ``codecs.py:174-212``)."""

    codec_name = 'compressed_ndarray'

    def encode(self, unischema_field, value):
        _check_dtype(unischema_field, value)
        _check_shape(unischema_field, value)
        memfile = io.BytesIO()
        np.savez_compressed(memfile, arr=value)
        return memfile.getvalue()

    def decode(self, unischema_field, value):
        memfile = io.BytesIO(value)
        return np.load(memfile)['arr']

    def make_cell_decoder(self, unischema_field):
        def decode_cell(cell):   # BytesIO accepts buffer views directly
            return np.load(io.BytesIO(cell))['arr']
        return decode_cell

    def device_decode_unsupported_reason(self, unischema_field):
        """zlib streams stay a host decode: there is no jittable inflate.
        The device-eligible route for compressed stores is an ETL-time
        repack to the raw ``NdarrayCodec`` layout
        (``etl/repack.py::repack_to_ndarray_codec``)."""
        return ('zlib inflate has no device path — repack the store to '
                'NdarrayCodec via etl.repack to make it device-eligible')

    def arrow_type(self, unischema_field):
        return pa.binary()


@register_codec
class CompressedImageCodec(DataframeColumnCodec):
    """png/jpeg image compression via OpenCV (reference ``codecs.py:58-130``).

    Values are uint8 (or uint16 for png) HxW or HxWx3 arrays in **RGB** channel
    order; cv2's BGR convention is converted at the codec boundary exactly as the
    reference does (``codecs.py:99-103,117-121``).
    """

    codec_name = 'compressed_image'

    def __init__(self, image_codec='png', quality=80):
        if image_codec not in ('png', 'jpeg', 'jpg'):
            raise ValueError('image_codec must be png or jpeg, got {!r}'.format(image_codec))
        self._image_codec = '.' + image_codec
        self._quality = int(quality)

    @property
    def image_codec(self):
        return self._image_codec[1:]

    @property
    def quality(self):
        return self._quality

    def encode(self, unischema_field, value):
        import cv2
        _check_dtype(unischema_field, value)
        _check_shape(unischema_field, value)
        image_bgr_or_gray = value
        if value.ndim == 3 and value.shape[2] == 3:
            image_bgr_or_gray = cv2.cvtColor(value, cv2.COLOR_RGB2BGR)
        if self._image_codec in ('.jpeg', '.jpg'):
            params = [int(cv2.IMWRITE_JPEG_QUALITY), self._quality]
        else:
            params = []
        ok, contents = cv2.imencode(self._image_codec, image_bgr_or_gray, params)
        if not ok:
            raise ValueError('cv2.imencode failed for field {!r}'.format(unischema_field.name))
        return contents.tobytes()

    def decode(self, unischema_field, value):
        return self._decode_flag(unischema_field, value, None)

    def make_cell_decoder(self, unischema_field):
        # Hot-loop variant of decode(): cv2 attribute lookups and the flag
        # resolve once per column; ndarray cell views feed imdecode directly
        # (it takes any uint8 array, so no frombuffer for the common case).
        import cv2
        imdecode, cvt_color = cv2.imdecode, cv2.cvtColor
        bgr2rgb, flag = cv2.COLOR_BGR2RGB, cv2.IMREAD_UNCHANGED
        name = unischema_field.name

        def decode_cell(cell):
            if not isinstance(cell, np.ndarray):
                cell = np.frombuffer(cell, np.uint8)
            img = imdecode(cell, flag)
            if img is None:
                raise ValueError(
                    'cv2.imdecode failed for field {!r}'.format(name))
            if img.ndim == 3 and img.shape[2] == 3:
                return cvt_color(img, bgr2rgb)
            return img
        return decode_cell

    def make_column_decoder(self, unischema_field):
        """Batched buffer-splitting decode: the chunk's cells are sliced
        from the arrow data buffer in one offsets pass, the only per-cell
        work is the actual image decompression (a C-level ``map`` over
        ``cv2.imdecode`` — no Python loop machinery between cells), and
        the decoded frames assemble straight into one dense array.
        Mixed-geometry or corrupt chunks punt to the per-cell loop, which
        owns the exact error/quarantine semantics."""
        import cv2
        imdecode, cvt_color = cv2.imdecode, cv2.cvtColor
        bgr2rgb, flag = cv2.COLOR_BGR2RGB, cv2.IMREAD_UNCHANGED

        def decode_chunk(chunk):
            if chunk.null_count:
                return None
            offsets, data = split_binary_chunk(chunk)
            cells = list(map(data.__getitem__,
                             map(slice, offsets[:-1].tolist(),
                                 offsets[1:].tolist())))
            decoded = list(map(imdecode, cells, repeat(flag)))
            # a failed imdecode must surface as the per-cell path's
            # field-named ValueError at the exact row: punt, don't guess
            if any(img is None for img in decoded):
                return None
            first = decoded[0]
            if first.ndim == 3 and first.shape[2] == 3:
                # cvtColor raising on a mixed gray/color chunk propagates
                # to the caller, which retries per cell (same punt)
                decoded = list(map(cvt_color, decoded, repeat(bgr2rgb)))
                first = decoded[0]
            out = np.empty((len(decoded),) + first.shape, first.dtype)
            for i, img in enumerate(decoded):
                out[i] = img      # shape mismatch raises -> per-cell retry
            return out
        return decode_chunk

    def validate_decode_hint(self, unischema_field, min_shape=None,
                             scale=None, allow_upscale=False):
        """Construction-time value check for :meth:`decode_scaled` kwargs —
        bad hint VALUES must fail at the factory, not per-cell in workers."""
        if min_shape is not None and scale is not None:
            raise ValueError("decode hint takes 'min_shape' or 'scale', "
                             'not both')
        if scale is not None and scale not in (2, 4, 8):
            raise ValueError('scale must be one of 2, 4, 8 (jpeg DCT '
                             'denominators), got {!r}'.format(scale))
        if min_shape is not None:
            import operator
            try:        # any 2-sequence of integral values (tuple/list/ndarray)
                vals = [operator.index(s) for s in min_shape]
                ok = len(vals) == 2 and all(v > 0 for v in vals)
            except TypeError:
                ok = False
            if not ok:
                raise ValueError(
                    'min_shape must be a (height, width) pair of positive '
                    'ints, got {!r}'.format(min_shape))

    def _scalable_payload(self, unischema_field) -> bool:
        """Payload-level scalability: jpeg only (png REDUCED rounds instead of
        ceiling), uint8 only, gray or 3-channel. Spatial dims may be unknown
        (an explicit ``scale`` hint does not need them)."""
        shape = unischema_field.shape
        return (self._image_codec in ('.jpg', '.jpeg')
                and np.dtype(unischema_field.numpy_dtype) == np.uint8
                and shape is not None and len(shape) >= 2
                and (len(shape) == 2 or (len(shape) == 3 and shape[2] == 3)))

    def can_scale(self, unischema_field) -> bool:
        """Whether a ``min_shape`` hint can ever reduce this field: a scalable
        payload WITH known spatial dims (the denominator choice needs them)."""
        shape = unischema_field.shape
        return (self._scalable_payload(unischema_field)
                and all(s is not None for s in shape[:2]))

    def _reduced_flag(self, unischema_field, denom):
        import cv2
        color = len(unischema_field.shape) > 2
        return {2: cv2.IMREAD_REDUCED_COLOR_2 if color else cv2.IMREAD_REDUCED_GRAYSCALE_2,
                4: cv2.IMREAD_REDUCED_COLOR_4 if color else cv2.IMREAD_REDUCED_GRAYSCALE_4,
                8: cv2.IMREAD_REDUCED_COLOR_8 if color else cv2.IMREAD_REDUCED_GRAYSCALE_8}[denom]

    def decode_scaled(self, unischema_field, value, min_shape=None,
                      scale=None, allow_upscale=False):
        """Decode at reduced resolution when the consumer will downscale
        anyway — the jpeg DCT denominator (2/4/8) is applied during entropy
        decode, substantially cheaper than decode-then-resize. TPU-first
        addition (the reference always decodes at full resolution); same
        trick as torchvision's ``decode_jpeg(..., size=...)``.

        Two hint forms:

        - ``min_shape=(h, w)``: picks the largest denominator whose output
          still covers ``min_shape`` (or, with ``allow_upscale``, stays
          within one halving of it). Needs the field's stored shape to be
          fully known; otherwise falls back to a full decode.
        - ``scale=2|4|8``: applies that denominator unconditionally — the
          form for variable-shape fields (e.g. raw ImageNet), where the
          caller asserts the reduced size still covers its resize target.

        Either form silently falls back to a full decode for payloads that
        cannot scale (png, uint16, RGBA)."""
        if scale is not None:
            if not self._scalable_payload(unischema_field):
                return self.decode(unischema_field, value)
            return self._decode_flag(unischema_field, value,
                                     self._reduced_flag(unischema_field, scale))
        shape = unischema_field.shape
        if min_shape is None or not self.can_scale(unischema_field):
            return self.decode(unischema_field, value)
        min_h, min_w = int(min_shape[0]), int(min_shape[1])
        chosen = None
        for denom in (8, 4, 2):
            h, w = -(-shape[0] // denom), -(-shape[1] // denom)
            if (h >= min_h and w >= min_w) or \
                    (allow_upscale and 2 * h >= min_h and 2 * w >= min_w):
                chosen = self._reduced_flag(unischema_field, denom)
                break
        return self._decode_flag(unischema_field, value, chosen)

    def _decode_flag(self, unischema_field, value, flag):
        import cv2
        image_bgr_or_gray = cv2.imdecode(
            np.frombuffer(value, dtype=np.uint8),
            cv2.IMREAD_UNCHANGED if flag is None else flag)
        if image_bgr_or_gray is None:
            raise ValueError('cv2.imdecode failed for field {!r}'.format(unischema_field.name))
        if image_bgr_or_gray.ndim == 3 and image_bgr_or_gray.shape[2] == 3:
            return cv2.cvtColor(image_bgr_or_gray, cv2.COLOR_BGR2RGB)
        return image_bgr_or_gray

    def arrow_type(self, unischema_field):
        return pa.binary()

    def to_json_dict(self):
        return {'codec': self.codec_name, 'image_codec': self.image_codec,
                'quality': self._quality}

    @classmethod
    def from_json_dict(cls, d):
        return cls(image_codec=d.get('image_codec', 'png'), quality=d.get('quality', 80))

    def __repr__(self):
        return 'CompressedImageCodec({!r}, quality={})'.format(self.image_codec, self._quality)


@register_codec
class ScalarCodec(DataframeColumnCodec):
    """Stores a scalar natively in the column, with dtype-directed casts.

    The reference variant (``codecs.py:215-271``) is parameterized by a Spark SQL
    type; ours is parameterized by a numpy dtype (defaulting to the field's own
    dtype) and maps it to an arrow type via ``pa.from_numpy_dtype``.
    """

    codec_name = 'scalar'

    def __init__(self, numpy_dtype=None):
        self._dtype = np.dtype(numpy_dtype) if numpy_dtype is not None else None

    def _storage_dtype(self, unischema_field):
        return self._dtype if self._dtype is not None else np.dtype(unischema_field.numpy_dtype)

    def encode(self, unischema_field, value):
        if isinstance(value, np.ndarray) and value.ndim > 0:
            raise TypeError('Field {!r} is scalar but got an array of shape {}'.format(
                unischema_field.name, value.shape))
        dtype = self._storage_dtype(unischema_field)
        if dtype.kind in ('U', 'S', 'O'):
            return value if isinstance(value, (str, bytes)) else str(value)
        if dtype.kind == 'b':
            return bool(value)
        # .item() converts numpy scalars to native python so arrow accepts them.
        return np.asarray(value).astype(dtype).item()

    def decode(self, unischema_field, value):
        dtype = np.dtype(unischema_field.numpy_dtype)
        if dtype.kind in ('U', 'S', 'O'):
            return value
        return dtype.type(value)

    def make_column_decoder(self, unischema_field):
        """Pass-through fields (string/bytes/object dtypes, whose
        :meth:`decode` returns the stored value unchanged) decode a binary
        chunk with one ``to_pylist`` call instead of a per-cell
        view->bytes->decode loop. Numeric-from-binary fields keep the
        per-cell path (its contract is one numpy scalar per cell)."""
        try:
            kind = np.dtype(unischema_field.numpy_dtype).kind
        except TypeError:   # a non-dtype-able declaration: per-cell decides
            return None
        if kind not in ('U', 'S', 'O'):
            return None

        def decode_chunk(chunk):
            if chunk.null_count:
                return None
            out = np.empty(len(chunk), dtype=object)
            out[:] = chunk.to_pylist()
            return out
        return decode_chunk

    def arrow_type(self, unischema_field):
        dtype = self._storage_dtype(unischema_field)
        if dtype.kind in ('U', 'O'):
            return pa.string()
        if dtype.kind == 'S':
            return pa.binary()
        if dtype.kind == 'M':  # datetime64
            return pa.timestamp('ns')
        return pa.from_numpy_dtype(dtype)

    def to_json_dict(self):
        d = {'codec': self.codec_name}
        if self._dtype is not None:
            d['dtype'] = self._dtype.str
        return d

    @classmethod
    def from_json_dict(cls, d):
        return cls(numpy_dtype=d.get('dtype'))

    def __repr__(self):
        return 'ScalarCodec({})'.format(self._dtype if self._dtype is not None else '')


def build_decode_overrides(schema, decode_hints):
    """``{field: callable(value)}`` from reader-level decode hints.

    ``decode_hints`` maps field name -> kwargs for the codec's
    ``decode_scaled`` (e.g. ``{'image': {'min_shape': (112, 112)}}``).
    Validates at reader construction that every hinted field exists and its
    codec supports scaled decoding. Built inside workers from the plain hint
    dicts so nothing unpicklable crosses the pool boundary."""
    if not decode_hints:
        return {}
    overrides = {}
    for name, hint in decode_hints.items():
        field = schema.fields.get(name)
        if field is None:
            raise ValueError('decode_hints names unknown field {!r}'.format(name))
        scaled = getattr(field.codec, 'decode_scaled', None)
        if scaled is None:
            raise ValueError(
                'decode_hints for field {!r}: codec {!r} has no decode_scaled'
                .format(name, type(field.codec).__name__))
        try:      # typo'd kwargs must fail here, not per-cell inside workers
            inspect.signature(scaled).bind(field, b'', **hint)
        except TypeError as e:
            raise ValueError(
                'decode_hints for field {!r} do not match {}.decode_scaled: {}'
                .format(name, type(field.codec).__name__, e))
        validate = getattr(field.codec, 'validate_decode_hint', None)
        if validate is not None:  # value-level check (types/arity of kwargs)
            validate(field, **hint)
        def _decode(value, _fn=scaled, _field=field, _kw=dict(hint)):
            return _fn(_field, value, **_kw)
        overrides[name] = _decode
    return overrides
