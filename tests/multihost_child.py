"""Child process for the real multi-process ShardedIndexedLoader test.

Launched by ``tests/test_multihost_process.py`` with::

    python multihost_child.py <coordinator> <num_processes> <process_id> \
        <dataset_url> <batch_size> <num_epochs> <seed> <start_epoch> <start_batch> <max_steps>

Each process joins a real ``jax.distributed`` cluster (CPU backend, 2 local
virtual devices per process), builds the SAME ShardedIndexedLoader over the
global mesh, optionally restores a cursor, and prints one line per step::

    STEP <epoch> <batch> <sha256-of-global-id-column>

The hash is taken over the fully-replicated global batch (every process holds
a complete copy after an identity jit with replicated out_shardings), so
identical lines across processes prove identical GLOBAL streams, not merely
identical local shards.
"""

import hashlib
import os
import sys

import numpy as np

# Pin to the CPU and force 2 virtual CPU devices per process BEFORE jax
# loads (replacing any inherited device-count flag).
os.environ['JAX_PLATFORMS'] = 'cpu'
_kept = [f for f in os.environ.get('XLA_FLAGS', '').split()
         if not f.startswith('--xla_force_host_platform_device_count')]
os.environ['XLA_FLAGS'] = ' '.join(
    _kept + ['--xla_force_host_platform_device_count=2'])

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), '..'))


def main():
    (coordinator, num_processes, process_id, dataset_url, batch_size,
     num_epochs, seed, start_epoch, start_batch, max_steps) = sys.argv[1:11]
    import jax
    # CPU cross-process collectives need the gloo transport; without it each
    # process sees only its own devices (process_count stays 1).
    jax.config.update('jax_cpu_collectives_implementation', 'gloo')
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=int(num_processes),
                               process_id=int(process_id))
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from petastorm_tpu.indexed import IndexedDatasetReader, ShardedIndexedLoader
    from petastorm_tpu.parallel import make_mesh

    assert jax.process_count() == int(num_processes)
    mesh = make_mesh({'data': len(jax.devices())})
    dataset = IndexedDatasetReader(dataset_url)
    loader = ShardedIndexedLoader(dataset, batch_size=int(batch_size),
                                  mesh=mesh, num_epochs=int(num_epochs),
                                  seed=int(seed), workers_count=2)
    loader.load_state_dict({'epoch': int(start_epoch),
                            'batch': int(start_batch), 'version': 1})

    replicate = jax.jit(lambda x: x,
                        out_shardings=NamedSharding(mesh, PartitionSpec()))
    steps = 0
    for batch in loader:
        cursor = (loader.epoch, loader.batch)  # cursor of the NEXT batch
        full = replicate(batch['id'])
        # canonical int64 bytes: jax may have downcast int64 -> int32, and the
        # parent's ground truth hashes int64
        ids = np.ascontiguousarray(np.asarray(full.addressable_data(0)),
                                   dtype=np.int64)
        digest = hashlib.sha256(ids.tobytes()).hexdigest()[:24]
        # recover WHICH batch this was from the next-cursor
        print('STEP {} {}'.format(digest, '{}:{}'.format(*cursor)), flush=True)
        steps += 1
        if steps >= int(max_steps):
            break
    print('DONE {}'.format(steps), flush=True)


if __name__ == '__main__':
    main()
