"""Mesh construction and sharding helpers.

Idiomatic GSPMD: pick a mesh, annotate shardings, let XLA insert collectives.
Axis vocabulary used across the framework:

- ``data``  — data parallelism (batch dim)
- ``seq``   — sequence/context parallelism (ring attention)
- ``model`` — tensor parallelism (hidden/heads dims)
- ``pipe``  — pipeline stages
- ``expert``— expert parallelism (MoE)
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np


def make_mesh(axis_sizes: Dict[str, int], devices: Optional[Sequence] = None):
    """Build a ``jax.sharding.Mesh`` with named axes.

    ``axis_sizes`` maps axis name → size; the product must equal the device
    count. Axis order follows dict insertion order: put the fastest-varying
    (innermost, highest-bandwidth) axis last — on TPU that is the axis you want
    riding ICI neighbors, typically ``model``.

    >>> mesh = make_mesh({'data': 2, 'model': 4})
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    sizes = list(axis_sizes.values())
    total = int(np.prod(sizes)) if sizes else 1
    if total != len(devices):
        raise ValueError('Mesh axes {} require {} devices, got {}'.format(
            axis_sizes, total, len(devices)))
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(axis_sizes.keys()))


def host_shard() -> Tuple[int, int]:
    """(cur_shard, shard_count) for the calling host: each TPU host reads only
    its own row-group shard; sample bytes never cross DCN (SURVEY §5.8)."""
    import jax
    return jax.process_index(), jax.process_count()


def batch_sharding(mesh, batch_axis: str = 'data'):
    """NamedSharding placing dim 0 on the data axis, rest replicated."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(batch_axis))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())
