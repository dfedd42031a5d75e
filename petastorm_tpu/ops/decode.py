"""Device-side decode: jittable decoders for bytes-through columns.

BENCH_r13 closed the host half of the decode wall and named what remains:
per-cell codec cost dominates small payloads, and thread workers convoy on
the GIL around sub-quantum decode calls. Both walls fall the same way —
stop decoding on the host. This module is the device half of that plan
(docs/decode.md "Device-side decode"):

- **Plan time** (:func:`plan_device_decode`): per column, decide at reader
  construction whether the raw stored payload can decode *on the
  accelerator* under ``jax.jit``. Eligibility is strict and static — the
  codec must expose a device plan (``NdarrayCodec`` today), the field must
  be fixed-shape, non-nullable, little-endian numeric, and no reader
  feature that needs decoded host values (predicates, NGram windows,
  per-field decode hints, a host ``TransformSpec``) may be in play. A
  column that fails planning **declines to the host path; it never owns an
  error**.
- **Ship time** (:func:`raw_column_view`): workers skip host decode for
  planned columns and ship the raw arrow payload as one ``(n, stride)``
  uint8 grid — zero-copy out of the arrow data buffer and zero-copy
  through the multipart transport. Validation failures (header drift,
  nulls that appeared at read time) re-decode on the host and
  :func:`repack_to_raw` so a column's representation stays uniform for the
  reader's lifetime (the shuffling buffers preallocate per-column storage
  from the first chunk's dtype).
- **Decode time** (:func:`build_fused_infeed`): the strict v1 ``np.save``
  header parser (``codecs._parse_fast_npy_header``) proves fixed-shape
  cells share identical header bytes, so device decode is a header-strip +
  ``lax.bitcast_convert_type`` + reshape over the stacked uint8 buffer —
  one jitted program, fused with a device-flagged ``TransformSpec`` on the
  staging stream. :func:`decode_raw_host` is the bit-identical numpy
  reference (property-tested in ``tests/test_device_decode.py``) and the
  host fallback when no loader claims the raw columns.

Kill switch: ``PETASTORM_TPU_DEVICE_DECODE`` (default on where eligible),
read once per reader at plan time — the uniform switch shape
(``PETASTORM_TPU_BATCHED_DECODE``, ``_LINEAGE``, ``_PROFILER``).
"""

from __future__ import annotations

import io
import os
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np
import pyarrow as pa

from petastorm_tpu.codecs import (BATCHED_DECODE_ENV_VAR,
                                  _parse_fast_npy_header,
                                  batched_decode_enabled, split_binary_chunk)

#: Environment variable gating the device-decode path (default on where
#: eligible). ``0``/``false``/``off`` plans nothing, so every column keeps
#: the host batched/per-cell matrix. Read once per reader at plan time.
DEVICE_DECODE_ENV_VAR = 'PETASTORM_TPU_DEVICE_DECODE'


def device_decode_enabled() -> bool:
    """The :data:`DEVICE_DECODE_ENV_VAR` gate (default on)."""
    value = os.environ.get(DEVICE_DECODE_ENV_VAR, '').strip().lower()
    return value not in ('0', 'false', 'off')


def jax_x64_enabled() -> bool:
    """True when jax keeps 64-bit dtypes (``JAX_ENABLE_X64``). Without it
    jax canonicalizes i8/u8/f8-descr arrays to their 32-bit cousins, so a
    bitcast decode of an 8-byte column cannot be bit-identical — those
    columns must decline at plan time."""
    try:
        import jax
        return bool(jax.config.jax_enable_x64)
    except Exception:  # noqa: BLE001 - any failure means "decline"
        return False


def jax_backend_available() -> bool:
    """True when jax imports and has a device. A host without jax declines
    device planning (the reader works through the host matrix); a backend
    that fails to initialize raises — it must not silently turn device
    decode off."""
    try:
        import jax
    except ImportError:
        return False
    return len(jax.devices()) > 0


class DeviceColumnPlan(NamedTuple):
    """Picklable per-column decode plan, computed once at reader
    construction and shipped to workers inside ``worker_args``.

    The plan pins the EXACT stored layout the raw path expects: every cell
    of the column is ``header`` (the byte-identical machine-generated
    ``np.save`` v1 prefix for ``(descr, shape)``) followed by
    ``stride - header_len`` payload bytes. Workers verify the pin per
    chunk (:func:`raw_column_view`) and repack via the host decoder when
    it does not hold."""

    name: str
    descr: str          # normalized dtype.str, e.g. '<f4' / '|u1'
    shape: Tuple[int, ...]
    header: bytes       # the full np.save v1 prefix (magic + len + dict)

    @property
    def header_len(self) -> int:
        return len(self.header)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self.descr)

    @property
    def cell_count(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))

    @property
    def cell_nbytes(self) -> int:
        return self.cell_count * self.dtype.itemsize

    @property
    def stride(self) -> int:
        return self.header_len + self.cell_nbytes


def npy_header_bytes(dtype: np.dtype, shape) -> Optional[bytes]:
    """The exact ``np.save`` v1 prefix (magic + header-length + dict) every
    cell of a fixed ``(dtype, shape)`` column shares, or ``None`` when the
    writer would not emit the machine-generated v1 form this plan pins.

    Built by running the actual writer on an empty-strided dummy (no
    guessing at numpy's dict formatting across versions) and re-verified
    through the same strict parser the host fast path trusts."""
    dtype = np.dtype(dtype)
    if dtype.hasobject:
        return None
    buf = io.BytesIO()
    try:
        np.save(buf, np.zeros(tuple(shape), dtype=dtype))
    except (TypeError, ValueError):
        return None
    raw = buf.getvalue()
    parsed = _parse_fast_npy_header(memoryview(raw))
    if parsed is None:
        return None
    parsed_dtype, parsed_shape, header_end = parsed
    if parsed_dtype != dtype or parsed_shape != tuple(shape):
        return None
    return raw[:header_end]


def plan_for_field(field) -> Tuple[Optional[DeviceColumnPlan], Optional[str]]:
    """``(plan, None)`` when ``field`` is device-decodable, else
    ``(None, reason)``. The codec owns the eligibility verdict
    (``device_decode_unsupported_reason``); this wrapper builds the pinned
    header for the eligible ones."""
    codec = field.codec
    if codec is None:
        return None, 'native arrow column (no codec payload to strip)'
    check = getattr(codec, 'device_decode_unsupported_reason', None)
    if check is None:
        return None, 'codec {} has no device-decode path'.format(
            type(codec).__name__)
    reason = check(field)
    if reason:
        return None, reason
    dtype = np.dtype(field.numpy_dtype)
    if dtype.itemsize == 8 and not jax_x64_enabled():
        return None, '8-byte dtype {} decodes as its 32-bit cousin without ' \
            'jax x64 mode (set JAX_ENABLE_X64 to plan it)'.format(dtype)
    header = npy_header_bytes(dtype, field.shape)
    if header is None:
        return None, 'np.save header for {} {} is not the machine-' \
            'generated v1 form'.format(dtype, field.shape)
    return DeviceColumnPlan(name=field.name, descr=dtype.str,
                            shape=tuple(field.shape), header=header), None


def plan_device_decode(schema, enabled: Optional[bool] = None,
                       has_predicate: bool = False,
                       has_ngram: bool = False,
                       decode_hints: Optional[dict] = None,
                       transform_spec=None,
                       transformed_schema=None,
                       batched_output: bool = True,
                       tolerant_decode: bool = False,
                       worker_supported: bool = True):
    """``(plans, declined)`` for a reader's output view: ``plans`` maps
    column name -> :class:`DeviceColumnPlan`; ``declined`` maps column
    name (or ``'*'`` for whole-reader reasons) -> human-readable reason.

    Whole-reader decliners come first — features that need decoded host
    values make every column ineligible: predicates evaluate on decoded
    cells, NGram regroups decoded rows, a host ``TransformSpec`` receives
    decoded columns (a ``device=True`` spec instead *fuses into* the
    jitted decode), and row-granular readers split columns into per-row
    views the raw grid cannot satisfy."""
    declined: Dict[str, str] = {}
    if enabled is None:
        enabled = device_decode_enabled()
    if not enabled:
        return {}, {'*': '{}=off'.format(DEVICE_DECODE_ENV_VAR)}
    if not batched_decode_enabled():
        # the per-cell A/B switch demands every codec cell go through the
        # host per-cell loop; bytes-through would silently bypass it
        return {}, {'*': '{}=off forces the host per-cell loop'.format(
            BATCHED_DECODE_ENV_VAR)}
    if not batched_output:
        return {}, {'*': 'row-granular reader (rows split out of columns '
                         'before any loader could decode them)'}
    if not worker_supported:
        return {}, {'*': 'worker class has no bytes-through publish path '
                         '(supports_device_decode is unset)'}
    if has_predicate:
        return {}, {'*': 'predicate evaluates on decoded host values'}
    if has_ngram:
        return {}, {'*': 'NGram windows regroup decoded rows on the host'}
    if tolerant_decode:
        return {}, {'*': 'on_decode_error quarantines per-cell codec '
                         'failures, which only the host decode can observe'}
    if transform_spec is not None and not getattr(transform_spec, 'device',
                                                  False):
        return {}, {'*': 'host TransformSpec receives decoded columns '
                         '(declare device=True to fuse it into the jitted '
                         'decode instead)'}
    if (transform_spec is not None and transformed_schema is not None
            and set(transformed_schema.fields) != set(schema.fields)):
        # workers publish pre-transform columns under bytes-through; a
        # field-set-changing spec would break the batch namedtuple contract
        return {}, {'*': 'device TransformSpec changes the field set '
                         '(edit dtypes/shapes in place to stay fusable)'}
    if not jax_backend_available():
        return {}, {'*': 'jax is not importable on this host'}
    plans: Dict[str, DeviceColumnPlan] = {}
    hints = decode_hints or {}
    for name, field in schema.fields.items():
        if name in hints:
            declined[name] = 'per-field decode hint overrides the codec'
            continue
        plan, reason = plan_for_field(field)
        if plan is None:
            declined[name] = reason or 'ineligible'
        else:
            plans[name] = plan
    return plans, declined


# ---------------------------------------------------------------------------
# worker side: raw views + host repack
# ---------------------------------------------------------------------------

def raw_column_view(column, plan: DeviceColumnPlan) -> Optional[np.ndarray]:
    """The ``(n, stride)`` uint8 grid of one (large_)binary column's raw
    cells, zero-copy out of the arrow data buffer (single-chunk columns;
    multi-chunk concatenates), or ``None`` when the stored bytes do not
    match the plan's pinned layout — nulls, stride drift, any cell whose
    header differs from the pinned prefix. ``None`` means "host-decode and
    repack", never an error."""
    chunks = column.chunks if isinstance(column, pa.ChunkedArray) else [column]
    header = np.frombuffer(plan.header, dtype=np.uint8)
    stride = plan.stride
    parts = []
    for chunk in chunks:
        if chunk.null_count:
            return None
        n = len(chunk)
        if n == 0:
            continue
        offsets, data = split_binary_chunk(chunk)
        if int(offsets[1]) - int(offsets[0]) != stride or not bool(
                np.all(np.diff(offsets) == stride)):
            return None
        grid = data[int(offsets[0]):int(offsets[-1])].reshape(n, stride)
        if not bool((grid[:, :plan.header_len] == header).all()):
            return None
        parts.append(grid)
    if not parts:
        return np.empty((0, stride), dtype=np.uint8)
    if len(parts) == 1:
        return parts[0]
    return np.concatenate(parts, axis=0)


def repack_to_raw(plan: DeviceColumnPlan, decoded) -> np.ndarray:
    """Host-decoded ``(n, *shape)`` values re-laid as the plan's raw
    ``(n, stride)`` grid — the uniform-representation fallback when
    :func:`raw_column_view` declines a chunk (and the ETL repack primitive
    for ``CompressedNdarrayCodec`` stores, ``etl/repack.py``)."""
    decoded = np.ascontiguousarray(decoded, dtype=plan.dtype)
    n = decoded.shape[0] if decoded.ndim else 0
    if decoded.shape[1:] != plan.shape:
        raise ValueError('repack_to_raw: column {!r} decoded to {} but the '
                         'plan pins cell shape {}'.format(
                             plan.name, decoded.shape[1:], plan.shape))
    out = np.empty((n, plan.stride), dtype=np.uint8)
    out[:, :plan.header_len] = np.frombuffer(plan.header, dtype=np.uint8)
    if plan.cell_nbytes:
        out[:, plan.header_len:] = decoded.reshape(n, -1).view(np.uint8)
    return out


# ---------------------------------------------------------------------------
# decode: numpy reference + jitted device path
# ---------------------------------------------------------------------------

def decode_raw_host(plan: DeviceColumnPlan, raw) -> np.ndarray:
    """Bit-identical numpy reference for the jitted decoder, and the host
    fallback when no loader claims a bytes-through reader's raw columns.
    Returns a WRITABLE ``(n, *shape)`` array, matching the per-cell path's
    contract."""
    raw = np.asarray(raw)
    n = raw.shape[0]
    if not plan.cell_count:
        return np.empty((n,) + plan.shape, dtype=plan.dtype)
    payload = np.ascontiguousarray(raw[:, plan.header_len:])
    if not payload.flags.writeable:
        payload = payload.copy()
    return payload.view(plan.dtype).reshape((n,) + plan.shape)


def decode_raw_jax(plan: DeviceColumnPlan, raw):
    """One planned column's jittable decode: header-strip + bitcast +
    reshape. ``raw`` is a ``(n, stride)`` uint8 array (jnp or np); the
    result is the ``(n, *shape)`` typed array, bit-identical to
    :func:`decode_raw_host` (little-endian descrs only — big-endian is
    excluded at plan time)."""
    import jax
    import jax.numpy as jnp
    n = raw.shape[0]
    dtype = plan.dtype
    if not plan.cell_count:
        return jnp.zeros((n,) + plan.shape, dtype=dtype)
    payload = raw[:, plan.header_len:]
    if dtype.kind == 'b':
        # np.save stores bools as 0x00/0x01; nonzero-is-True matches the
        # numpy buffer-view semantics exactly for those values
        out = payload != 0
    elif dtype.itemsize == 1:
        out = jax.lax.bitcast_convert_type(payload, dtype)
    else:
        out = jax.lax.bitcast_convert_type(
            payload.reshape(n, plan.cell_count, dtype.itemsize), dtype)
    return out.reshape((n,) + plan.shape)


def build_fused_infeed(plans: Dict[str, DeviceColumnPlan],
                       transform_spec=None):
    """ONE jitted program for the staging stream: decode every planned raw
    column, then apply the device-flagged ``TransformSpec`` over the full
    column dict. The returned callable takes and returns a dict of
    device-compatible arrays (the caller keeps host-only columns out and
    merges them back; ``stage_to_global`` / ``prefetch_to_device`` /
    ``JaxDataLoader`` all share this builder so the three call sites
    cannot drift). It compiles as :data:`DEVICE_DECODE_PROGRAM`: a
    profiler trace names its runs ``jit_petastorm_device_decode``."""
    import jax
    plans = dict(plans)
    func = None
    if transform_spec is not None and getattr(transform_spec, 'func',
                                              None) is not None:
        func = transform_spec.func

    def petastorm_device_decode(columns):
        out = dict(columns)
        for name, plan in plans.items():
            if name in out:
                out[name] = decode_raw_jax(plan, out[name])
        if func is not None:
            out = func(out)
        return out

    return jax.jit(petastorm_device_decode)


#: The name the fused infeed program compiles under (its ``XLA Modules``
#: events read ``jit_`` and this name), kept stable so a trace reduction
#: finds the staging layer's device work.
DEVICE_DECODE_PROGRAM = 'petastorm_device_decode'


def split_device_columns(batch, plans: Dict[str, DeviceColumnPlan],
                         include_unplanned: bool = False):
    """``(device_cols, host_cols)``: planned raw columns go through the
    jitted program; every other column stays a host numpy array, untouched
    — a bytes-through batch must not silently turn unplanned columns into
    immutable ``jax.Array``s (consumers mutate batches in place).
    ``include_unplanned=True`` additionally routes unplanned numeric
    ndarrays through the jit — required when a fused device
    ``TransformSpec`` runs, since its func receives the full column dict;
    object/str columns stay on the host either way."""
    device_cols, host_cols = {}, {}
    for name, value in batch.items():
        if name in plans:
            device_cols[name] = value
        elif (include_unplanned and isinstance(value, np.ndarray)
              and value.dtype.kind in 'biufc'):
            device_cols[name] = value
        else:
            host_cols[name] = value
    return device_cols, host_cols
