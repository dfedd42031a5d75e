#!/usr/bin/env python
"""Drive the Parquet -> reader -> loader -> train-step path once on a TPU.

One process drives the chip; the readers use thread pools, so nothing this
script starts touches JAX. Every store is generated from ``--seed`` into a
fresh temporary directory and removed at exit.

- ``lm`` (default): 4096 token windows of 2049 int32 tokens (vocab 32000)
  stored with ``NdarrayCodec``, so the bytes-through device decode
  (``ops/decode.py``) runs; ``make_columnar_reader`` -> ``JaxDataLoader`` ->
  ``prefetch_to_device`` -> the flagship LM's train step at its default
  widths with ``attention='flash'``. Step 0's loss and the first gradient
  are checked against the jnp ``'blockwise'`` attention on the same batch
  and params, and a control with a wrong attention mask must miss them.
- ``image`` (default): 512 ImageNet-shaped JPEG rows; ``make_reader`` ->
  ``JaxDataLoader(batch_size=64)`` -> the ``image_cnn`` train step at
  224x224, 1000 classes.
- ``--chips 4`` runs only the data-parallel phase: the ``lm`` store through
  ``ShardedJaxLoader`` over a ``{'data': 4}`` mesh into the same flash step,
  compared with the same global batch and params stepped on one chip; a
  control on half the batch must miss the one-chip numbers.

The last stdout line is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before it. Step times printed here are context, not results.
``--seed`` fixes the stores and the weights, not the order in which the
thread-pool readers deliver rows, so two runs may step different batches.
"""

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Limits on two sound computations of the bf16 LM on one batch: flash vs
#: blockwise attention on one chip, and four chips vs one. LOSS_RTOL bounds
#: the relative loss gap, GRAD_RTOL the gradient's relative L2 gap over all
#: parameters. On the chip sound runs read loss gaps up to 1.6e-5 and
#: gradient gaps up to 1.1e-2; an attention window of seq/2 reads 2.1e-5 and
#: 0.71 (at random init the loss barely sees attention, the gradient does),
#: half the batch 3.9e-4 and 1.0 (PERF.md, PR 21). The controls below prove
#: each run's limits tight enough to catch such faults.
LOSS_RTOL = 1e-4
GRAD_RTOL = 5e-2


class SmokeFailure(RuntimeError):
    pass


def _require(condition, what):
    if not condition:
        raise SmokeFailure(what)


def log(msg):
    print(msg, flush=True)


def _workers():
    return min(8, max(2, os.cpu_count() or 2))


def _loss_close(a, b):
    return math.isfinite(a) and math.isfinite(b) and (
        abs(a - b) <= LOSS_RTOL * max(abs(a), abs(b)))


def _grad_gap(got, want):
    """``||got - want|| / ||want||`` over every leaf, on the host."""
    import jax
    import numpy as np

    num = den = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(jax.device_get(got)),
                    jax.tree_util.tree_leaves(jax.device_get(want))):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        num += float(np.sum(np.square(g - w), dtype=np.float64))
        den += float(np.sum(np.square(w), dtype=np.float64))
    return math.sqrt(num / den) if den else math.inf


def _close_enough(what, got, want):
    """``got``/``want``: ``(loss, grads)`` on one batch. Logs both gaps; true
    when the loss is within LOSS_RTOL and the gradient within GRAD_RTOL."""
    loss, want_loss = float(got[0]), float(want[0])
    gap = _grad_gap(got[1], want[1])
    close = _loss_close(loss, want_loss) and gap <= GRAD_RTOL
    log('{}: loss {!r} vs {!r} (relative gap {!r}, limit {}); gradient gap '
        '{!r} (limit {}); {}'.format(
            what, loss, want_loss, abs(loss - want_loss) / abs(want_loss),
            LOSS_RTOL, gap, GRAD_RTOL, 'close' if close else 'apart'))
    return close


def _value_and_grad(config, mesh=None):
    import jax

    from petastorm_tpu.models import transformer_lm as tlm
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: tlm.loss_fn(p, x, y, config, mesh)))


def _token_store(workdir, seed, rows, seq_len, vocab):
    from petastorm_tpu.test_util.dataset_gen import generate_token_dataset

    url = 'file://' + os.path.join(workdir, 'tokens')
    start = time.perf_counter()
    generate_token_dataset(url, rows=rows, seq_len=seq_len, vocab=vocab,
                           seed=seed, ndarray_codec=True)
    log('store: {} windows x {} tokens in {:.2f} s'.format(
        rows, seq_len + 1, time.perf_counter() - start))
    return url


def _token_reader(url, seed):
    from petastorm_tpu import make_columnar_reader
    return make_columnar_reader(url, reader_pool_type='thread',
                                workers_count=_workers(), num_epochs=None,
                                seed=seed)


def lm_phase(workdir, config, *, seed=0, rows=4096, batch_size=8,
             steps=10):
    """Token store -> columnar reader -> JaxDataLoader -> prefetch_to_device
    -> ``config``'s train step. Fails on a non-finite loss, a step-0 loss or
    first gradient off the blockwise reference, a wrong-mask control that
    matches it, or no row decoded on the device."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from petastorm_tpu.jax_utils import JaxDataLoader, prefetch_to_device
    from petastorm_tpu.models import transformer_lm as tlm

    seq_len = config.max_seq_len
    url = _token_store(workdir, seed, rows, seq_len, config.vocab_size)
    params = tlm.init(jax.random.PRNGKey(seed), config)
    optimizer, step = tlm.make_train_step(config)
    opt_state = optimizer.init(params)
    batch = jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)
    start = time.perf_counter()
    compiled = step.lower(params, opt_state, batch, batch).compile()
    compile_s = time.perf_counter() - start
    kernel = 'tpu_custom_call' in compiled.as_text()
    log('lm: step compiled in {:.2f} s; tpu_custom_call in step: {}'.format(
        compile_s, kernel))
    ref_config = dataclasses.replace(config, attention='blockwise')
    # a mask fault: each token sees only the last seq_len // 2 positions
    control_config = dataclasses.replace(ref_config,
                                         attention_window=seq_len // 2)

    losses, step_s = [], []
    with _token_reader(url, seed) as reader:
        loader = JaxDataLoader(reader, batch_size=batch_size, drop_last=True)
        with contextlib.closing(prefetch_to_device(iter(loader))) as batches:
            for i in range(steps):
                tokens = next(batches)['tokens']
                x, y = tokens[:, :-1], tokens[:, 1:]
                if i == 0:
                    flash = _value_and_grad(config)(params, x, y)
                    ref = _value_and_grad(ref_config)(params, x, y)
                    control = _value_and_grad(control_config)(params, x, y)
                start = time.perf_counter()
                params, opt_state, loss = compiled(params, opt_state, x, y)
                loss.block_until_ready()
                step_s.append(time.perf_counter() - start)
                losses.append(float(loss))
                log('lm: step {} loss {!r} ({:.4f} s)'.format(
                    i, losses[-1], step_s[-1]))
        rows_device = reader.stats.snapshot().get('rows_decoded_device', 0)
    ref_loss = float(ref[0])
    log('lm: step-0 loss {!r} vs blockwise reference {!r} (relative gap {!r}, '
        'limit {})'.format(losses[0], ref_loss,
                           abs(losses[0] - ref_loss) / abs(ref_loss),
                           LOSS_RTOL))
    flash_close = _close_enough('lm: flash vs blockwise', flash, ref)
    control_close = _close_enough(
        'lm: control (window {}) vs blockwise'.format(
            control_config.attention_window), control, ref)
    log('lm: rows_decoded_device {}'.format(rows_device))
    _require(all(math.isfinite(v) for v in losses),
             'non-finite LM loss: {}'.format(losses))
    _require(_loss_close(losses[0], ref_loss),
             'step-0 loss {!r} off the blockwise reference {!r}'.format(
                 losses[0], ref_loss))
    _require(flash_close, 'flash loss or gradient off the blockwise reference')
    _require(not control_close, 'the wrong-mask control matched the blockwise '
                                'reference: the limits are too loose')
    _require(rows_device > 0, 'no row was decoded on the device')
    return {'compile_s': compile_s, 'kernel': kernel, 'losses': losses,
            'reference_loss': ref_loss, 'step_s': step_s,
            'rows_decoded_device': rows_device}


def _resize_transform(size):
    """Row TransformSpec: a ragged (H, W, 3) image -> (size, size, 3)."""
    import numpy as np

    from petastorm_tpu.transform import TransformSpec

    def resize(row):
        import cv2
        row['image'] = cv2.resize(row['image'], (size, size),
                                  interpolation=cv2.INTER_AREA)
        return row

    return TransformSpec(resize,
                         edit_fields=[('image', np.uint8, (size, size, 3),
                                       False)],
                         selected_fields=['image', 'label'])


def image_phase(workdir, *, seed=0, rows=512, batch_size=64, steps=3,
                image_size=224, num_classes=1000, widths=(64, 128, 256)):
    """JPEG store -> make_reader -> JaxDataLoader -> image_cnn train step.
    Fails on a non-finite loss."""
    import jax

    from petastorm_tpu import make_reader
    from petastorm_tpu.jax_utils import JaxDataLoader
    from petastorm_tpu.models import image_cnn
    from petastorm_tpu.test_util.dataset_gen import generate_imagenet_dataset

    url = 'file://' + os.path.join(workdir, 'imagenet')
    start = time.perf_counter()
    generate_imagenet_dataset(url, rows=rows, classes=num_classes, seed=seed,
                              row_group_size_mb=1.0, image_codec='jpeg')
    log('store: {} jpeg rows in {:.2f} s'.format(
        rows, time.perf_counter() - start))
    params = image_cnn.init(jax.random.PRNGKey(seed), num_classes=num_classes,
                            widths=widths)
    step = image_cnn.make_train_step()
    losses, step_s = [], []
    with make_reader(url, reader_pool_type='thread', workers_count=_workers(),
                     num_epochs=None, seed=seed,
                     transform_spec=_resize_transform(image_size)) as reader:
        batches = iter(JaxDataLoader(reader, batch_size=batch_size,
                                     drop_last=True))
        for i in range(steps):
            batch = next(batches)
            start = time.perf_counter()
            params, loss = step(params, batch['image'], batch['label'])
            loss.block_until_ready()
            step_s.append(time.perf_counter() - start)
            losses.append(float(loss))
            log('image: step {} loss {!r} ({:.4f} s{})'.format(
                i, losses[-1], step_s[-1], ', compile included' if i == 0
                else ''))
    _require(all(math.isfinite(v) for v in losses),
             'non-finite image loss: {}'.format(losses))
    return {'losses': losses, 'step_s': step_s}


def data_parallel_phase(workdir, config, devices, *, seed=0, rows=4096,
                        global_batch=8, steps=3):
    """Token store -> ShardedJaxLoader over ``{'data': len(devices)}`` ->
    ``config``'s train step, params replicated; each step is repeated on
    ``devices[0]`` alone with the same global batch and params. Fails
    unless the losses and the first gradient match, a control on half the
    batch misses them, and the batch sits on every device."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from petastorm_tpu.jax_utils import ShardedJaxLoader, prefetch_to_device
    from petastorm_tpu.models import transformer_lm as tlm
    from petastorm_tpu.parallel import make_mesh

    n = len(devices)
    url = _token_store(workdir, seed, rows, config.max_seq_len,
                       config.vocab_size)
    mesh = make_mesh({'data': n}, devices=devices)
    params = tlm.init(jax.random.PRNGKey(seed), config)
    dp_optimizer, dp_step = tlm.make_train_step(config, mesh)
    one_optimizer, one_step = tlm.make_train_step(config)
    dp_params = jax.device_put(params, NamedSharding(mesh, P()))
    dp_opt = dp_optimizer.init(dp_params)
    one_params = jax.device_put(params, devices[0])
    one_opt = one_optimizer.init(one_params)

    dp_losses, one_losses = [], []
    with _token_reader(url, seed) as reader:
        loader = ShardedJaxLoader(reader, mesh, global_batch
                                  // jax.process_count())
        with contextlib.closing(prefetch_to_device(iter(loader))) as batches:
            for i in range(steps):
                tokens = next(batches)['tokens']
                host = jax.device_put(np.asarray(tokens), devices[0])
                if i == 0:
                    shard_devices = {s.device for s in tokens.addressable_shards}
                    log('dp: batch {} on {} distinct devices'.format(
                        tokens.shape, len(shard_devices)))
                    _require(len(shard_devices) == n,
                             'batch shards sit on {} devices, not {}'.format(
                                 len(shard_devices), n))
                    one_vg = _value_and_grad(config)
                    want = one_vg(one_params, host[:, :-1], host[:, 1:])
                    first = _close_enough(
                        'dp: {} chips vs one'.format(n),
                        _value_and_grad(config, mesh)(
                            dp_params, tokens[:, :-1], tokens[:, 1:]), want)
                    # the fault it stands for: a step on half the batch
                    half = host[:global_batch // 2]
                    control = _close_enough(
                        'dp: control (half the batch) vs one chip',
                        one_vg(one_params, half[:, :-1], half[:, 1:]), want)
                start = time.perf_counter()
                dp_params, dp_opt, loss = dp_step(dp_params, dp_opt,
                                                  tokens[:, :-1], tokens[:, 1:])
                dp_losses.append(float(loss))
                dp_s = time.perf_counter() - start
                one_params, one_opt, loss = one_step(one_params, one_opt,
                                                     host[:, :-1], host[:, 1:])
                one_losses.append(float(loss))
                log('dp: step {} loss {!r} on {} chips ({:.4f} s{}), {!r} on '
                    'one'.format(i, dp_losses[-1], n, dp_s,
                                 ', compile included' if i == 0 else '',
                                 one_losses[-1]))
        rows_device = reader.stats.snapshot().get('rows_decoded_device', 0)
    bytes_in_use = [(d.memory_stats() or {}).get('bytes_in_use')
                    for d in devices]
    log('dp: rows_decoded_device {}; bytes_in_use per device {}'.format(
        rows_device, bytes_in_use))
    _require(all(_loss_close(a, b) for a, b in zip(dp_losses, one_losses)),
             'data-parallel losses {} off the one-chip losses {}'.format(
                 dp_losses, one_losses))
    _require(first, 'data-parallel loss or gradient off the one-chip step')
    _require(not control, 'the half-batch control matched the full batch: '
                          'the limits are too loose')
    return {'losses': dp_losses, 'one_chip_losses': one_losses,
            'bytes_in_use': bytes_in_use, 'rows_decoded_device': rows_device}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--chips', type=int, choices=(1, 4), default=1,
                        help='4 runs only the data-parallel phase')
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != 'tpu':
        print('chip_smoke: no TPU found (JAX reports platform {!r})'.format(
            platform), file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print('chip_smoke: --chips {} needs {} TPU devices, JAX has {}'.format(
            args.chips, args.chips, len(devices)), file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    log('device: {} x {}'.format(len(devices), devices[0].device_kind))

    from petastorm_tpu.models import transformer_lm as tlm
    from petastorm_tpu.utils import configure_compile_cache
    log('compile cache: {}'.format(configure_compile_cache(ROOT)))

    config = tlm.TransformerConfig(attention='flash')
    with tempfile.TemporaryDirectory(prefix='chip_smoke_') as workdir:
        if args.chips == 1:
            lm = lm_phase(workdir, config, seed=args.seed)
            _require(lm['kernel'], 'no Pallas kernel (tpu_custom_call) in '
                                   'the compiled LM step')
            log('lm: peak_bytes_in_use {}'.format(
                devices[0].memory_stats()['peak_bytes_in_use']))
            image_phase(workdir, seed=args.seed)
        else:
            dp = data_parallel_phase(workdir, config, devices, seed=args.seed)
            _require(all(b for b in dp['bytes_in_use']),
                     'a device holds no bytes: {}'.format(dp['bytes_in_use']))
    print(json.dumps({'ok': True, 'device': {
        'platform': platform, 'kind': devices[0].device_kind,
        'count': len(devices)}}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
