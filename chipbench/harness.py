"""One run of one cell: set-up, a measured window, and the check.

The cell names its configuration and traffic mix in ``BENCHMARK.json``; this
module finds every part by name under the search directories (``configs/``,
``traffic/``, ``limits/``, ``metrics/``, ``drivers/``), so a later PR adds a
cell, a configuration or a metric with new files only.

A run is one process that holds the chips:

1. set-up (``setup_s``): the weights on the device, the step compiled for
   the cell's shapes, the reader opened, then the first ``warmup_steps``
   steps through the window's own step and feed. Steps 1-3 are the ones the
   reference follows, so the program's state is read around them; those
   copies are the check's and are left out of ``setup_s``. So is the store
   from the seed, written by a seed's first run in a checkout and kept
   (keyed by its generator): it is the dataset a deployment already has,
   and a seed's first run would otherwise read seconds slower than its
   second.
2. the window: ``--seconds`` of steps, one dispatched ahead, each completion
   on the host clock after ``block_until_ready``. With ``--trace 1`` the
   window is traced and is at most ``TRACE_SECONDS`` long.
3. the device's memory peak is read, the program's state and reader are
   released, and the check runs: the plain reference decodes the rows the
   batches named and follows the first three steps (``chipbench/checks.py``).
"""

import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: The longest traced window: a trace of every op on four chips grows by
#: tens of MB a second, and it is read back in the same run.
TRACE_SECONDS = 10
#: The name the train step is compiled under, so the trace finds it.
PROGRAM = 'chipbench_train_step'
#: Steps the reference follows.
CHECKED_STEPS = 3


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Lookup:
    """Finds a part by kind and name in the first search directory that
    has it."""

    def __init__(self, search):
        self.search = list(search)
        self._modules = {}

    def path(self, kind, name, ext):
        for base in self.search:
            path = os.path.join(base, kind, name + ext)
            if os.path.exists(path):
                return path
        raise LookupError('no {}/{}{} under {}'.format(kind, name, ext,
                                                       self.search))

    def json(self, kind, name):
        with open(self.path(kind, name, '.json')) as f:
            return json.load(f)

    def module(self, kind, name):
        path = self.path(kind, name, '.py')
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                'chipbench_{}_{}'.format(kind, name.replace('.', '_')), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[path] = module
        return self._modules[path]


def find(entries, name, what):
    for entry in entries:
        if entry['name'] == name:
            return entry
    raise LookupError('BENCHMARK.json has no {} {!r}'.format(what, name))


def load_peaks(kind, path=os.path.join(BENCH_DIR, 'peaks.json')):
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table['devices']:
        raise KeyError('peaks.json has no device_kind {!r} (has {})'.format(
            kind, sorted(table['devices'])))
    return table['devices'][kind]


def workers_for(rule, cpu_count):
    """``examples/imagenet/main.py``'s rule: min(8, max(2, cpu_count))."""
    return min(rule['max'], max(rule['min'], cpu_count))


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


class Sampler:
    """Keeps ``k`` of the window's batches, drawn from the seed by reservoir
    sampling (``k`` 0 keeps every batch)."""

    def __init__(self, k, seed):
        self.k = k
        self.rng = np.random.default_rng([seed % (1 << 63), 7])
        self.kept = {}
        self.seen = 0

    def offer(self, index, batch):
        self.seen += 1
        if self.k == 0 or len(self.kept) < self.k:
            self.kept[index] = batch
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            del self.kept[sorted(self.kept)[j]]
            self.kept[index] = batch


#: Benchmark code that every driver and reference may draw rows from.
SEEDED = os.path.join(BENCH_DIR, 'drivers', 'seeded.py')


def store_path(store_root, config_name, cfg, seed, driver, ref):
    """The store's directory, keyed by the configuration, the seed and a
    digest of the generator (the driver, its reference and the seeded
    helpers they share) and the configuration's sizes."""
    digest = hashlib.sha256()
    for path in (driver.__file__, ref.__file__, SEEDED):
        with open(path, 'rb') as f:
            digest.update(f.read())
    digest.update(json.dumps(cfg, sort_keys=True).encode())
    return os.path.join(store_root, config_name,
                        '{}-{}'.format(seed, digest.hexdigest()[:16]))


def ensure_store(path, cfg, seed, driver):
    """Writes the store once per key; a crash mid-write leaves no store."""
    if os.path.exists(os.path.join(path, '_done.json')):
        with open(os.path.join(path, '_done.json')) as f:
            return json.load(f), False
    tmp = path + '.tmp'
    shutil.rmtree(tmp, ignore_errors=True)
    facts = driver.write_store(cfg, seed, 'file://' + tmp)
    with open(os.path.join(tmp, '_done.json'), 'w') as f:
        json.dump(facts, f)
    shutil.rmtree(path, ignore_errors=True)
    os.replace(tmp, path)
    return facts, True


def _open_reader(traffic, url, seed, driver, cfg, cpu_count):
    from petastorm_tpu import make_columnar_reader, make_reader
    factory = {'columnar': make_columnar_reader,
               'row': make_reader}[traffic['reader']]
    return factory(url, reader_pool_type=traffic['pool'],
                   workers_count=workers_for(traffic['workers'], cpu_count),
                   num_epochs=None, seed=seed % (1 << 32),
                   shuffle_row_groups=True, cache_type=traffic['cache_type'],
                   **driver.reader_kwargs(cfg, seed))


def _open_batches(traffic, reader, mesh, global_batch):
    from petastorm_tpu.jax_utils import (JaxDataLoader, ShardedJaxLoader,
                                         prefetch_to_device)
    if traffic['loader'] == 'sharded':
        loader = ShardedJaxLoader(reader, mesh, global_batch)
    else:
        loader = JaxDataLoader(reader, batch_size=global_batch,
                               drop_last=True)
    return prefetch_to_device(iter(loader), size=traffic['prefetch'])


def _host(tree):
    import jax
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


class Run:
    """What a run measured; the per-layer metric readers read it."""


def run(manifest, workload, seed, seconds, trace, search, store_root,
        started=None, fault=None, trace_dir=None, readings=None):
    """One run of ``workload``; returns the result dict the CLI prints.

    ``fault`` (tests and ``calibrate.py`` only) breaks the timed path: see
    ``chipbench/faults.py``. ``readings``, a dict, receives every number the
    check computed, compared or not (``calibrate.py``)."""
    import jax
    from jax.sharding import Mesh

    from chipbench import checks
    from chipbench import trace as trace_mod

    started = time.perf_counter() if started is None else started
    lookup = Lookup(search)
    cell = find(manifest['workloads'], workload, 'workload')
    config_name = cell['config']
    cfg = lookup.json('configs', config_name)
    traffic = lookup.json('traffic', cell['traffic'])
    limits = lookup.json('limits', workload)
    driver = lookup.module('drivers', cfg['driver'])
    ref = lookup.module('drivers', cfg['driver'] + '_ref')
    if fault is not None:
        from chipbench import faults
        driver = faults.broken(driver, fault, ref)

    chips = cell['chips']
    devices = jax.devices()[:chips]
    platform, kind = devices[0].platform, devices[0].device_kind
    cpu_count = os.cpu_count() or 1
    log('device: {} x {} ({}); cpu_count {}'.format(len(devices), kind,
                                                     platform, cpu_count))
    global_batch = cfg['batch_per_chip'] * chips
    times = {}

    t = time.perf_counter()
    path = store_path(store_root, config_name, cfg, seed, driver, ref)
    facts, wrote = ensure_store(path, cfg, seed, driver)
    times['store_s'] = time.perf_counter() - t
    log('store: {} ({}), {} rows, mean encoded bytes per row {!r}'.format(
        path, 'written' if wrote else 'reused', facts['rows'],
        facts['mean_encoded_bytes_per_row']))

    mesh = Mesh(np.asarray(devices).reshape(
        [traffic['mesh'][a] for a in traffic['mesh']]), tuple(traffic['mesh']))
    t = time.perf_counter()
    state = driver.init_state(cfg, seed, mesh)
    jax.block_until_ready(state)
    times['init_s'] = time.perf_counter() - t

    t = time.perf_counter()
    step, shapes = driver.make_step(cfg, mesh, global_batch)
    step = step.lower(state, *shapes).compile()
    times['compile_s'] = time.perf_counter() - t

    check_s = 0.0
    t = time.perf_counter()
    reader = _open_reader(traffic, 'file://' + path, seed, driver, cfg,
                          cpu_count)
    times['reader_open_s'] = time.perf_counter() - t
    log('reader: {} pool, {} workers, loader {}, prefetch {}'.format(
        traffic['pool'], workers_for(traffic['workers'], cpu_count),
        traffic['loader'], traffic['prefetch']))

    pipeline = _open_batches(traffic, reader, mesh, global_batch)
    batches = (pipeline if fault is None
               else faults.broken_feed(pipeline, fault))
    try:
        t = time.perf_counter()
        checked, warm_losses = [], []
        params0 = grad1 = params3 = None
        for i in range(traffic['warmup_steps']):
            batch = next(batches)
            if i < CHECKED_STEPS:
                checked.append(batch)
                c = time.perf_counter()
                if i == 0:
                    params0 = _host(driver.params_of(state))
                check_s += time.perf_counter() - c
            state, loss = step(state, *driver.step_args(batch))
            warm_losses.append(loss)
            if i < CHECKED_STEPS:
                c = time.perf_counter()
                jax.block_until_ready(loss)
                if i == 0:
                    grad1 = driver.first_grad(cfg, params0, state)
                if i == CHECKED_STEPS - 1:
                    params3 = _host(driver.params_of(state))
                check_s += time.perf_counter() - c
        jax.block_until_ready(warm_losses)
        times['warmup_s'] = time.perf_counter() - t - check_s
        setup_s = (time.perf_counter() - started - check_s
                   - times['store_s'])
        log('setup: {!r} s = init {init_s!r} + compile {compile_s!r} + '
            'reader open {reader_open_s!r} + warm-up ({} steps) '
            '{warmup_s!r} + the rest; the store {store_s!r} s and the '
            'check\'s copies of the state {!r} s are left out'.format(
                setup_s, traffic['warmup_steps'], check_s, **times))

        recorder = (trace_mod.Recorder(devices[0])
                    if trace and platform == 'tpu' else None)
        window = _window(step, state, batches, driver, seconds, recorder,
                         seed, cfg, reader, trace_dir)
        state = window.pop('state')
        peak = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                   for d in devices)
        sampled = window.pop('sampled')
        row_ids = window.pop('row_ids')
        losses = window.pop('losses')
        del state, batches
    finally:
        pipeline.close()
        reader.stop()
        reader.join()

    c = time.perf_counter()
    warm_losses = [float(x) for x in jax.device_get(warm_losses)]
    losses = [float(x) for x in jax.device_get(losses)]
    checked = [_host(driver.values(b)) | {'row_id': _host(b['row_id'])}
               for b in checked]
    sampled = {i: _host(driver.values(b)) | {'row_id': _host(b['row_id'])}
               for i, b in sampled.items()}
    row_ids = [np.asarray(r) for r in jax.device_get(row_ids)]
    numbers = checks.compare(
        cfg, ref, path, seed, checked, sampled, row_ids, facts['rows'],
        warm_losses[:CHECKED_STEPS], grad1, params0, params3, losses)
    if readings is not None:
        readings.update(numbers)
    for name in sorted(set(numbers) - set(limits['limits'])):
        log('not compared: {} {!r} (no limit; see PERF.md)'.format(
            name, numbers[name]))
    numbers = {k: {'value': numbers[k], 'limit': v}
               for k, v in limits['limits'].items()}
    check_s += time.perf_counter() - c
    log('check: {!r} s'.format(check_s))

    run_ = Run()
    run_.__dict__.update(window)
    run_.chips, run_.global_batch = chips, global_batch
    run_.flops_per_step = ref.train_flops(cfg, global_batch)
    run_.peak = load_peaks(kind) if platform == 'tpu' else None
    run_.program = PROGRAM
    trace_summary = None
    if window['trace_path'] is not None:
        trace_summary = trace_mod.reduce(window['trace_path'], PROGRAM,
                                         window['host'])
        shutil.rmtree(trace_dir, ignore_errors=True)
    run_.trace = trace_summary

    result = {
        'correct': all(v['value'] <= v['limit'] for v in numbers.values()),
        'attempted': len(losses),
        'failed': sum(1 for x in losses if not math.isfinite(x)),
        'metrics': {},
        'device': {'platform': platform, 'kind': kind, 'count': len(devices),
                   'memory_peak_bytes': int(peak)},
    }
    metric_entries = manifest['per_layer'] if trace else manifest['end_to_end']
    values = (_per_layer(metric_entries, workload, lookup, run_) if trace
              else _end_to_end(window, setup_s, chips, global_batch))
    if platform == 'tpu':
        for entry in metric_entries:
            if 'workloads' in entry and workload not in entry['workloads']:
                continue
            if values.get(entry['name']) is not None:
                result['metrics'][entry['name']] = {
                    'value': values[entry['name']], 'unit': entry['unit']}
    else:
        # a CPU rehearsal never writes a device metric's name
        result['rehearsal'] = {'platform': platform, 'steps': window['steps'],
                               'window_s': window['window_s']}
    if trace and trace_summary is not None:
        result['device']['busy_s'] = trace_summary.busy_s
        result['device']['window_s'] = trace_summary.window_s
        result['breakdown'] = {'device_ops': trace_summary.top_ops[:10],
                               'idle_gaps': trace_summary.idle_gaps[:10]}
    result['checks'] = numbers
    return result


def _window(step, state, batches, driver, seconds, recorder, seed, cfg,
            reader, trace_dir):
    """The measured window: steps dispatched one ahead, each completion
    timed on the host after ``block_until_ready``. With a ``recorder``
    (``trace.Recorder``) the window is traced; the host spans are kept
    either way, on ``perf_counter_ns``."""
    stats0 = reader.stats.snapshot() if reader.stats is not None else {}
    sampler = Sampler(cfg['check_batches'], seed)
    row_ids, losses, spans, completions = [], [], [], []
    clock = time.perf_counter_ns
    trace_path = None
    if recorder is not None:
        seconds = min(seconds, TRACE_SECONDS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        recorder.start(trace_dir)
    limit = int(seconds * 1e9)
    t0 = clock()
    pending, index = None, 0
    while True:
        a = clock()
        batch = next(batches)
        b = clock()
        sampler.offer(index, batch)
        row_ids.append(batch['row_id'])
        state, loss = step(state, *driver.step_args(batch))
        c = clock()
        spans += [('infeed_wait', a, b), ('dispatch', b, c)]
        losses.append(loss)
        index += 1
        if pending is not None:
            pending.block_until_ready()
            completions.append(clock())
            spans.append(('block', c, completions[-1]))
            if completions[-1] - t0 >= limit:
                break
        pending = loss
    t1 = completions[-1]
    if recorder is not None:
        trace_path = recorder.stop(trace_dir)
    loss.block_until_ready()     # the step in flight when the window closed
    stats1 = reader.stats.snapshot() if reader.stats is not None else {}
    return {'state': state, 'sampled': sampler.kept, 'row_ids': row_ids,
            'losses': losses, 'steps': len(completions),
            'window_s': (t1 - t0) / 1e9,
            'intervals': (np.diff([t0] + completions) / 1e9).tolist(),
            'infeed_waits': [(e - s) / 1e9 for n, s, e in spans
                             if n == 'infeed_wait'],
            'fetched': index, 'trace_path': trace_path,
            'host': {'window': [t0, t1], 'spans': spans,
                     'marks': recorder.marks if recorder else []},
            'stats_delta': {k: stats1[k] - stats0.get(k, 0)
                            for k in stats1
                            if isinstance(stats1[k], (int, float))}}


def _end_to_end(window, setup_s, chips, global_batch):
    values = {
        'samples_per_s': window['steps'] * global_batch / window['window_s']
        / chips,
        'step_p95_ms': 1e3 * percentile(window['intervals'], 95),
        'setup_s': setup_s,
    }
    # a cell that does not report one of these still logs it
    log('window: {} steps in {!r} s; samples per s per chip {!r}; step p95 '
        '{!r} ms'.format(window['steps'], window['window_s'],
                         values['samples_per_s'], values['step_p95_ms']))
    return values


def _per_layer(entries, workload, lookup, run_):
    log('window: {} steps in {!r} s (traced)'.format(run_.steps,
                                                     run_.window_s))
    values = {}
    for entry in entries:
        if 'workloads' in entry and workload not in entry['workloads']:
            continue
        value = lookup.module('metrics', entry['name']).read(run_)
        values[entry['name']] = None if value is None else float(value)
    return values
