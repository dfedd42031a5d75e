"""Driver for image-classification deployments: a JPEG store of photo-like
images, the reader path that decodes and crops them on the host, and the
repo's ``image_cnn`` train step at the configuration's widths.

The harness calls the functions below by name; see ``chipbench/harness.py``.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.drivers import seeded


def _schema():
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    # examples/imagenet/schema.py's ImageNet schema, stored as JPEG, plus
    # the row id that names a row in every batch
    return Unischema('ImagenetSchema', [
        UnischemaField('row_id', np.int64, (), ScalarCodec(), False),
        UnischemaField('noun_id', str, (), ScalarCodec(), False),
        UnischemaField('text', str, (), ScalarCodec(), False),
        UnischemaField('label', np.int64, (), ScalarCodec(), False),
        UnischemaField('image', np.uint8, (None, None, 3),
                       CompressedImageCodec('jpeg'), False),
    ])


def synthetic_row(cfg, seed, row_id):
    """One photo-like row, copied from ``examples/imagenet/generate_imagenet
    .synthetic_rows``: a low-frequency random field plus mild sensor-like
    noise at about 500x375 with a +-20% size jitter, so that the codec's
    cost tracks a real photo's entropy-coded bytes. Drawn from the seed and
    the row id alone, so rows can be made in any order."""
    import cv2
    rng = seeded.row_rng(seed, row_id, 0)
    base_h, base_w = cfg['source_hw']
    h = int(base_h * rng.uniform(0.8, 1.2))
    w = int(base_w * rng.uniform(0.8, 1.2))
    label = row_id % cfg['num_classes']
    small = rng.integers(0, 255, size=(24, 32, 3), dtype=np.uint8)
    img = cv2.resize(small, (w, h), interpolation=cv2.INTER_CUBIC)
    img = np.clip(img.astype(np.int16) + rng.integers(-8, 8, size=img.shape),
                  0, 255).astype(np.uint8)
    return {'row_id': np.int64(row_id), 'noun_id': 'n{:08d}'.format(label),
            'text': 'class {}'.format(label), 'label': np.int64(label),
            'image': img}


def write_store(cfg, seed, url):
    """Writes ``cfg['rows']`` rows; the JPEG encoding runs on a thread pool
    (cv2 releases the GIL). Returns facts for the log."""
    import pyarrow as pa

    from petastorm_tpu.etl.dataset_metadata import materialize_dataset
    from petastorm_tpu.unischema import encode_row
    schema = _schema()

    def encoded(row_id):
        return encode_row(schema, synthetic_row(cfg, seed, row_id))

    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        rows = list(pool.map(encoded, range(cfg['rows'])))
    table = pa.Table.from_pylist(rows, schema=schema.as_arrow_schema())
    with materialize_dataset(url, schema, row_group_size_mb=cfg['row_group_mb'],
                             file_size_mb=1 << 20) as writer:
        writer.write_encoded_table(table)
    return {'rows': len(rows),
            'mean_encoded_bytes_per_row': float(np.mean(
                [len(r['image']) for r in rows]))}


def reader_kwargs(cfg, seed):
    """The example's TransformSpec with the benchmark's seeded
    RandomResizedCrop and flip in place of its plain resize."""
    from petastorm_tpu.transform import TransformSpec
    size = cfg['image_size']

    def crop_batch(columns):
        images, ids = columns['image'], columns['row_id']
        out = np.empty((len(ids), size, size, 3), np.uint8)
        for i, (img, row_id) in enumerate(zip(images, ids)):
            box = seeded.crop_box(seed, row_id, img.shape[0], img.shape[1])
            out[i] = seeded.crop_resize(img, box, size)
        columns['image'] = out
        return columns

    return {'transform_spec': TransformSpec(
        crop_batch, edit_fields=[('image', np.uint8, (size, size, 3), False)],
        selected_fields=['row_id', 'image', 'label'])}


def init_state(cfg, seed, mesh):
    """The weights, made on the device in one jitted call from the seed."""
    import jax

    from petastorm_tpu.models import image_cnn
    make = jax.jit(lambda key: image_cnn.init(
        key, num_classes=cfg['num_classes'], widths=tuple(cfg['widths']),
        blocks_per_stage=cfg['blocks_per_stage']),
        out_shardings=jax.sharding.SingleDeviceSharding(mesh.devices.flat[0]))
    return make(jax.random.PRNGKey(seed % (1 << 32)))


def make_step(cfg, mesh, batch):
    """``(step, arg_shapes)``: the program's SGD train step under the stable
    name ``chipbench_train_step``, its state donated, and the shapes of the
    batch arguments it is compiled for."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models import image_cnn
    inner = image_cnn.make_train_step(lr=cfg['lr'])

    def chipbench_train_step(params, images, labels):
        return inner(params, images, labels)

    size = cfg['image_size']
    device = mesh.devices.flat[0]
    sharding = jax.sharding.SingleDeviceSharding(device)
    shapes = (jax.ShapeDtypeStruct((batch, size, size, 3), jnp.uint8,
                                   sharding=sharding),
              jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sharding))
    return jax.jit(chipbench_train_step, donate_argnums=0), shapes


def step_args(batch):
    return batch['image'], batch['label']


def values(batch):
    """The batch's columns that the data check compares with the reference."""
    return {'image': batch['image'], 'label': batch['label']}


def params_of(state):
    return state


def first_grad(cfg, params0, state1):
    """The gradient the optimizer applied in step 1, from its state: SGD's
    ``p1 = p0 - lr * g``."""
    import jax
    lr = cfg['lr']
    return jax.tree_util.tree_map(
        lambda p0, p1: (np.asarray(p0, np.float64) - np.asarray(p1, np.float64))
        / lr, params0, jax.device_get(state1))
