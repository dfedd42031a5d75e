"""Compact residual CNN for image classification, written MXU-first.

Convs run in NHWC with bfloat16 compute (params float32), channel counts are
multiples of 8/128 where it matters, and the whole step jits to a single XLA
program — the image-side analogue of the transformer flagship. Used by
``examples/imagenet`` and the benchmark's image driver
(``chipbench/drivers/image_cnn.py``).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride), padding='SAME',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def _norm(x, scale, bias):
    # GroupNorm(1) == LayerNorm over (H, W, C): batch-size independent, no
    # running stats to shard — friendlier than BatchNorm under dp sharding.
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(1, 2, 3), keepdims=True)
    var = jnp.var(x32, axis=(1, 2, 3), keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + 1e-5)
    return (out * scale + bias).astype(x.dtype)


def init(rng, num_classes: int = 1000, widths=(64, 128, 256),
         blocks_per_stage: int = 2) -> Dict[str, Any]:
    """Parameters for a ResNet-style net: stem conv + ``len(widths)`` stages of
    ``blocks_per_stage`` residual blocks + linear head."""
    def conv_w(key, kh, kw, cin, cout):
        scale = math.sqrt(2.0 / (kh * kw * cin))
        return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * scale

    keys = iter(jax.random.split(rng, 4 + 4 * len(widths) * blocks_per_stage))
    params: Dict[str, Any] = {
        'stem': conv_w(next(keys), 7, 7, 3, widths[0]),
        'stem_scale': jnp.ones((widths[0],), jnp.float32),
        'stem_bias': jnp.zeros((widths[0],), jnp.float32),
        'stages': [],
    }
    cin = widths[0]
    for width in widths:
        stage = []
        for b in range(blocks_per_stage):
            block = {
                'conv1': conv_w(next(keys), 3, 3, cin, width),
                'scale1': jnp.ones((width,), jnp.float32),
                'bias1': jnp.zeros((width,), jnp.float32),
                'conv2': conv_w(next(keys), 3, 3, width, width),
                'scale2': jnp.ones((width,), jnp.float32),
                'bias2': jnp.zeros((width,), jnp.float32),
            }
            if cin != width:
                block['proj'] = conv_w(next(keys), 1, 1, cin, width)
            stage.append(block)
            cin = width
        params['stages'].append(stage)
    params['head_w'] = jax.random.normal(
        next(keys), (cin, num_classes), jnp.float32) / math.sqrt(cin)
    params['head_b'] = jnp.zeros((num_classes,), jnp.float32)
    return params


def forward(params, images, dtype=jnp.bfloat16):
    """images (B, H, W, 3) float in [0, 1] → logits (B, num_classes) f32."""
    x = images.astype(dtype)
    x = _conv(x, params['stem'], stride=2)
    x = _norm(x, params['stem_scale'], params['stem_bias'])
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), 'SAME')
    for s, stage in enumerate(params['stages']):
        for b, block in enumerate(stage):
            stride = 2 if (s > 0 and b == 0) else 1
            h = _conv(x, block['conv1'], stride=stride)
            h = _norm(h, block['scale1'], block['bias1'])
            h = jax.nn.relu(h)
            h = _conv(h, block['conv2'])
            h = _norm(h, block['scale2'], block['bias2'])
            shortcut = x
            if 'proj' in block:
                shortcut = _conv(x, block['proj'], stride=stride)
            elif stride != 1:
                shortcut = x[:, ::stride, ::stride, :]
            x = jax.nn.relu(h + shortcut)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))          # global pool
    return x @ params['head_w'] + params['head_b']


def loss_fn(params, images, labels, dtype=jnp.bfloat16):
    logits = forward(params, images, dtype)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def make_train_step(lr: float = 1e-3, dtype=jnp.bfloat16):
    """Jitted SGD step over uint8 NHWC batches (normalization fused in)."""
    @jax.jit
    def step(params, images_u8, labels):
        images = images_u8.astype(jnp.float32) / 255.0
        loss, grads = jax.value_and_grad(loss_fn)(params, images, labels, dtype)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    return step
