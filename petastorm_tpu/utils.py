"""Small shared helpers used across reader/worker modules.

Reference analogue: ``petastorm/utils.py`` (its ``decode_row`` lives on
``unischema.decode_row`` here; this module holds cross-cutting value casts).
"""

from __future__ import annotations

import os

import numpy as np

_FALSY_STRINGS = frozenset(('false', '0', '', 'no'))


def atomic_write(path: str, write_fn) -> str:
    """Write a text artifact atomically: ``write_fn(file)`` runs against a
    sibling tmp file that is ``os.replace``d over ``path`` only on success,
    and never outlives a failed write. A crash mid-dump — exactly when
    diagnostic artifacts (chrome traces, flight records, ``.prom`` files)
    matter most — can neither leave truncated output that tooling rejects
    nor clobber a previous good artifact at the same path."""
    tmp = '{}.tmp.{}'.format(path, os.getpid())
    try:
        with open(tmp, 'w') as f:
            write_fn(f)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path


def parse_bool_string(value: str) -> bool:
    """Parse a hive-partition-style boolean string. ``bool('False')`` is True in
    python, which silently inverts ``flag=False`` partitions — hence this."""
    return value.strip().lower() not in _FALSY_STRINGS


def cast_partition_value(numpy_dtype, value: str):
    """Cast a hive partition directory value (always a string on disk) to the
    schema field's dtype. Single source of truth for partition-value coercion
    (used by the reader's partition-predicate pruning, the row worker, and the
    batch worker)."""
    if numpy_dtype is None or numpy_dtype is str:
        return value
    if numpy_dtype is bytes:
        return value.encode('utf-8')
    dtype = np.dtype(numpy_dtype)
    if dtype.kind == 'b':
        return np.bool_(parse_bool_string(value))
    return dtype.type(value)


def cast_string_to_type(target_type, value: str):
    """Cast a string to ``type(filter_value)`` for filter comparison, with
    correct bool semantics."""
    if target_type is bool:
        return parse_bool_string(value)
    return target_type(value)


def configure_compile_cache(checkout: str) -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself;
    otherwise the cache lives at the fixed ``<checkout>/.jax_cache``. The
    path is part of the cache key, so it is never built from a temp name, a
    pid or the time. Called by entry-point scripts, never on import."""
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if path:
        return path
    import jax
    path = os.path.join(os.path.abspath(checkout), '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path
