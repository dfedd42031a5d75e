"""Plain reference for the ``image_cnn`` driver: the stored rows read back
with pyarrow and cv2, the seeded crop-and-flip, and ResNet-style training
(He et al., arXiv:1512.03385, Table 1, with GroupNorm(1) in place of
BatchNorm and a 1x1 projection where the width changes) in float32 at
``Precision.HIGHEST``, SGD as the program's step applies it.

Imports nothing of ``petastorm_tpu``; takes nothing the program made. The
weights are drawn from the seed with the same ``jax.random`` calls, in the
same order, as ``image_cnn.init`` documents.
"""

import glob
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import precision
from chipbench.drivers.seeded import crop_box, crop_resize

class RowSource:
    """The store's rows as the batches should hold them, read straight from
    the parquet files: JPEG bytes decoded by cv2 into RGB, then cropped."""

    def __init__(self, cfg, store_path, seed):
        import pyarrow.parquet as pq
        tables = [pq.read_table(f, columns=['row_id', 'label', 'image'])
                  for f in sorted(glob.glob(os.path.join(store_path,
                                                         '*.parquet')))]
        self._ids = np.concatenate([t.column('row_id').to_numpy()
                                    for t in tables])
        self._labels = np.concatenate([t.column('label').to_numpy()
                                       for t in tables])
        self._images = [b for t in tables
                        for b in t.column('image').to_pylist()]
        self._where = {int(r): i for i, r in enumerate(self._ids)}
        self._seed = seed
        self._size = cfg['image_size']

    def __len__(self):
        return len(self._ids)

    def _image(self, row_id):
        import cv2
        raw = self._images[self._where[row_id]]
        bgr = cv2.imdecode(np.frombuffer(raw, np.uint8), cv2.IMREAD_COLOR)
        rgb = cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB)
        box = crop_box(self._seed, row_id, rgb.shape[0], rgb.shape[1])
        return crop_resize(rgb, box, self._size)

    def rows(self, row_ids):
        """``{'image': (N, S, S, 3) uint8, 'label': (N,) int32}``."""
        ids = [int(r) for r in np.asarray(row_ids).reshape(-1)]
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            images = list(pool.map(self._image, ids))
        labels = [int(self._labels[self._where[r]]) for r in ids]
        return {'image': np.stack(images),
                'label': np.asarray(labels, np.int32)}


def init(cfg, seed):
    """float32 weights keyed as ``image_cnn.init`` keys them."""
    import jax
    import jax.numpy as jnp
    widths, blocks = cfg['widths'], cfg['blocks_per_stage']

    def conv_w(key, kh, kw, cin, cout):
        return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
                * math.sqrt(2.0 / (kh * kw * cin)))

    def make(key):
        keys = iter(jax.random.split(key, 4 + 4 * len(widths) * blocks))
        p = {'stem': conv_w(next(keys), 7, 7, 3, widths[0]),
             'stem_scale': jnp.ones((widths[0],), jnp.float32),
             'stem_bias': jnp.zeros((widths[0],), jnp.float32),
             'stages': []}
        cin = widths[0]
        for width in widths:
            stage = []
            for _ in range(blocks):
                block = {'conv1': conv_w(next(keys), 3, 3, cin, width),
                         'scale1': jnp.ones((width,), jnp.float32),
                         'bias1': jnp.zeros((width,), jnp.float32),
                         'conv2': conv_w(next(keys), 3, 3, width, width),
                         'scale2': jnp.ones((width,), jnp.float32),
                         'bias2': jnp.zeros((width,), jnp.float32)}
                if cin != width:
                    block['proj'] = conv_w(next(keys), 1, 1, cin, width)
                stage.append(block)
                cin = width
            p['stages'].append(stage)
        p['head_w'] = (jax.random.normal(next(keys), (cin, cfg['num_classes']),
                                         jnp.float32) / math.sqrt(cin))
        p['head_b'] = jnp.zeros((cfg['num_classes'],), jnp.float32)
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed % (1 << 32)))


def loss(params, images_u8, labels, control=None):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def conv(x, w, stride=1):
        return precision.result(jax.lax.conv_general_dilated(
            precision.operand(x, control), precision.operand(w, control),
            (stride, stride), 'SAME', dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            precision=hi), control)

    def norm(x, scale, bias):
        mean = jnp.mean(x, axis=(1, 2, 3), keepdims=True)
        var = jnp.var(x, axis=(1, 2, 3), keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias

    x = images_u8.astype(jnp.float32) / 255.0
    x = jax.nn.relu(norm(conv(x, params['stem'], 2), params['stem_scale'],
                         params['stem_bias']))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), 'SAME')
    for s, stage in enumerate(params['stages']):
        for b, block in enumerate(stage):
            stride = 2 if (s > 0 and b == 0) else 1
            h = jax.nn.relu(norm(conv(x, block['conv1'], stride),
                                 block['scale1'], block['bias1']))
            h = norm(conv(h, block['conv2']), block['scale2'], block['bias2'])
            if 'proj' in block:
                shortcut = conv(x, block['proj'], stride)
            elif stride != 1:
                shortcut = x[:, ::stride, ::stride, :]
            else:
                shortcut = x
            x = jax.nn.relu(h + shortcut)
    x = jnp.mean(x, axis=(1, 2))
    logits = precision.result(jnp.matmul(
        precision.operand(x, control),
        precision.operand(params['head_w'], control),
        precision=hi), control) + params['head_b']
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def control_step(cfg, control):
    """The reference's SGD step on a whole batch, called as the program's
    step is, ``(params, images_u8, labels) -> (params, loss)``: with the
    cell's control, this is the control put in the program's place
    (``faults.py``)."""
    import jax
    lr = cfg['lr']

    def step(params, images_u8, labels):
        value, grads = jax.value_and_grad(
            lambda p: loss(p, images_u8, labels, control))(params)
        return jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                      grads), value

    return step


def train3(cfg, params, batches, control=None):
    """Steps the reference through ``batches`` (three host batches as
    :meth:`RowSource.rows` gives them) from ``params``, on the default
    device, in blocks of ``ref_block_rows`` rows (GroupNorm is per sample,
    so the blocks' mean is the batch's). Returns the losses, the first
    step's gradient and the parameters after the last step, on the host."""
    import jax
    import jax.numpy as jnp
    lr = cfg['lr']
    block = cfg['ref_block_rows']
    value_and_grad = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss(p, x, y, control)))
    losses, first_grad = [], None
    for batch in batches:
        n = len(batch['label'])
        total, grads = 0.0, None
        for start in range(0, n, block):
            part = slice(start, min(n, start + block))
            l, g = value_and_grad(params, jnp.asarray(batch['image'][part]),
                                  jnp.asarray(batch['label'][part]))
            w = (part.stop - part.start) / n
            total += float(l) * w
            g = jax.tree_util.tree_map(lambda a: a * w, g)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        losses.append(total)
        if first_grad is None:
            first_grad = jax.device_get(grads)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return {'losses': losses, 'grad': first_grad,
            'params': jax.device_get(params)}


def _conv_macs(out_hw, k, cin, cout):
    return out_hw * out_hw * k * k * cin * cout


def train_flops(cfg, batch):
    """Model FLOPs of one training step on ``batch`` images, counted from the
    shapes the step runs: every convolution and the head in the forward
    pass (2 FLOPs a multiply-add), twice that again for the backward pass,
    except that no gradient flows into the input images (the stem's
    input-gradient convolution is never computed). Norms, pooling and the
    loss are not counted."""
    size, widths, blocks = cfg['image_size'], cfg['widths'], cfg['blocks_per_stage']
    hw = -(-size // 2)                                   # stem, stride 2, SAME
    stem = _conv_macs(hw, 7, 3, widths[0])
    hw = -(-hw // 2)                                     # max-pool, stride 2
    body, cin = 0, widths[0]
    for s, width in enumerate(widths):
        for b in range(blocks):
            if s > 0 and b == 0:
                hw = -(-hw // 2)
            body += _conv_macs(hw, 3, cin, width) + _conv_macs(hw, 3, width, width)
            if cin != width:
                body += _conv_macs(hw, 1, cin, width)
            cin = width
    head = cin * cfg['num_classes']
    forward = 2 * (stem + body + head)
    return batch * (3 * forward - 2 * stem)
