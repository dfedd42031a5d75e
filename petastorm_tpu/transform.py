"""User-supplied row/batch transforms executed on reader workers.

Reference parity: ``petastorm/transform.py`` — ``TransformSpec`` (:27-57),
``transform_schema`` (:60-89).

TPU-first addition: a ``TransformSpec`` may declare ``is_batched_jax=True``; the
JAX adapter (``petastorm_tpu/jax_utils``) will then run ``func`` on-device under
``jax.jit`` over whole batches instead of on the CPU worker.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from petastorm_tpu.unischema import Unischema, UnischemaField


class TransformSpec:
    """Defines a transform applied on a worker (thread/process) before data
    reaches the consumer, plus the schema mutation it implies.

    :param func: callable applied to each row dict (``make_reader``) or pandas
        DataFrame (``make_batch_reader``). May be ``None`` if only field
        selection/removal is needed.
    :param edit_fields: list of :class:`UnischemaField` (or 4-tuples
        ``(name, dtype, shape, nullable)``) added/modified by the transform.
    :param removed_fields: field names deleted by the transform.
    :param selected_fields: if set, the post-transform schema keeps exactly these
        fields. Mutually exclusive with ``removed_fields``
        (reference ``transform.py:53-57``).
    :param device: declare ``func`` jit-compatible (jnp ops over a dict of
        batch columns, no Python side effects). A device spec is **fused
        into the jitted device-decode program** on the staging stream
        (``ops.decode.build_fused_infeed``) instead of running on CPU
        workers — the ``is_batched_jax`` promise above, made real. When the
        reader's columns are not device-eligible (``docs/decode.md``), a
        device spec still runs on the host over the same columnar dict
        (jnp ops accept numpy arrays), so results do not depend on
        eligibility.
    :param reports_counts: ``func`` takes a second argument,
        ``record_count(name, n)``, through which it adds ``n`` to one of the
        reader's ``ReaderStats`` counters (columnar path only; e.g.
        ``packing.pack_transform``'s packing counters).
    """

    def __init__(self, func: Optional[Callable] = None,
                 edit_fields: Optional[List] = None,
                 removed_fields: Optional[List[str]] = None,
                 selected_fields: Optional[List[str]] = None,
                 device: bool = False, reports_counts: bool = False):
        self.func = func
        self.device = bool(device)
        self.reports_counts = bool(reports_counts)
        self.edit_fields = [self._as_field(f) for f in (edit_fields or [])]
        self.removed_fields = list(removed_fields or [])
        self.selected_fields = list(selected_fields) if selected_fields is not None else None
        if self.selected_fields is not None and self.removed_fields:
            raise ValueError('Only one of removed_fields and selected_fields can be specified')

    @staticmethod
    def _as_field(f):
        if isinstance(f, UnischemaField):
            return f
        name, dtype, shape, nullable = f
        return UnischemaField(name, dtype, shape, None, nullable)


def transform_schema(schema: Unischema, transform_spec: TransformSpec) -> Unischema:
    """Derive the post-transform :class:`Unischema`
    (reference ``transform.py:60-89``)."""
    removed = set(transform_spec.removed_fields)
    unknown = removed - set(schema.fields.keys())
    if unknown:
        raise ValueError('removed_fields names unknown fields: {}'.format(sorted(unknown)))
    fields = {name: field for name, field in schema.fields.items() if name not in removed}
    for edited in transform_spec.edit_fields:
        fields[edited.name] = edited
    if transform_spec.selected_fields is not None:
        unknown = set(transform_spec.selected_fields) - set(fields.keys())
        if unknown:
            raise ValueError('selected_fields names unknown fields: {}'.format(sorted(unknown)))
        fields = {name: field for name, field in fields.items()
                  if name in transform_spec.selected_fields}
    return Unischema(schema._name + '_transformed', list(fields.values()))


def apply_columnar_transform(transform_spec: TransformSpec,
                             transformed_schema: Unischema, columns,
                             record_count: Optional[Callable] = None):
    """The columnar transform contract, shared by the streaming columnar
    worker and the indexed loader: ``func`` receives a dict of column arrays
    (and, for a spec that ``reports_counts``, ``record_count``, ``None``
    where no counters are kept); the result is filtered to the transformed
    schema's fields."""
    if transform_spec.func is not None:
        columns = (transform_spec.func(columns, record_count)
                   if transform_spec.reports_counts
                   else transform_spec.func(columns))
    return {name: columns[name] for name in transformed_schema.fields
            if name in columns}
