#!/usr/bin/env python
"""Headline benchmark.

Output contract (round-5 postmortem: the driver's tail capture lost
``BENCH_r05.json``'s headline because this script printed one huge JSON
line): the LAST stdout line is a **bounded compact summary** (< 4 KB,
asserted by ``tests/test_profiler.py``) containing the headline value and
every per-line rate; the FULL summary goes to ``--out`` (written atomically
via the shared ``utils.atomic_write``) and to stderr. A normalized entry of
each run is appended to the local ``PERF_TRAJECTORY.jsonl``
(``ci/check_perf_regression.py`` reads it as non-gating context next to the
committed ``BENCH_*.json`` trajectory).

Full-summary keys: ``{"metric", "value", "unit", "vs_baseline",
"dispersion", "roofline_bench", "northstar", ...}``.

- Primary metric: reader throughput on the hello-world schema with the same
  reader configuration as the reference's tool (3 thread workers, python
  read path — ``petastorm-throughput.py``), but measured READ-BOUND: a
  10k-row store, 1k warmup + 10k measured samples, MEDIAN of 5 runs (the
  'statistic' field says so; rounds <=4 headlined the best run) with a
  recorded dispersion block. ``vs_baseline`` anchors against the
  reference's published tutorial figure (709.84 samples/sec on unspecified
  hardware, ``docs/benchmarks_tutorial.rst:20-21``) — a rough cross-tool
  anchor, not a same-protocol comparison (the reference store is 10 rows
  and its number is epoch-reset-bound by construction).
- ``northstar``: the BASELINE.md target metric — samples/sec/chip +
  infeed-stall % of real train steps (MLP on png images, transformer LM on
  token windows) fed through make_reader -> JaxDataLoader ->
  prefetch_to_device, on the TPU (a CPU rehearsal needs an explicit
  ``JAX_PLATFORMS=cpu`` and is labelled by ``platform``).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_SAMPLES_PER_SEC = 709.84   # reference docs/benchmarks_tutorial.rst:20-21

DATASET_PATH = '/tmp/petastorm_tpu_hello_world_bench'
MNIST_PATH = '/tmp/petastorm_tpu_northstar_mnist'
TOKENS_PATH = '/tmp/petastorm_tpu_northstar_tokens'
# '_photo' suffix: regenerated when the synthetic content changed from
# uniform noise to photo-like fields (stale noise stores must not be reused)
IMAGENET_PATH = '/tmp/petastorm_tpu_northstar_imagenet_photo'


def _platform():
    """The platform JAX runs on, read in this process. Off the TPU the
    bench runs only under an explicit ``JAX_PLATFORMS=cpu``, as a rehearsal
    labelled ``cpu``; any other failure to find the chip is an error."""
    import jax
    platform = jax.devices()[0].platform
    if platform != 'tpu' and os.environ.get('JAX_PLATFORMS') != 'cpu':
        raise SystemExit('bench.py: no TPU found (JAX reports {!r}); set '
                         'JAX_PLATFORMS=cpu for a CPU rehearsal'.format(
                             platform))
    return platform


def _ensure(path, marker, generate):
    if not os.path.exists(os.path.join(path, marker)):
        generate()


def _store_roofline(url):
    """Calibrated serial io+decode ceiling (samples/sec) for one bench
    store, via the roofline profiler's micro-probes (cached per
    host+dataset digest — see docs/profiling.md). This is the denominator
    the decode-wall lines are judged against; ``None`` when probing fails
    (a broken probe must not sink the whole bench)."""
    try:
        from petastorm_tpu import profiler
        from petastorm_tpu.etl.dataset_metadata import (
            infer_or_load_unischema, load_row_groups)
        from petastorm_tpu.fs import get_filesystem_and_path_or_paths
        fs, path, _ = get_filesystem_and_path_or_paths(url)
        pieces = load_row_groups(fs, path)
        schema, _ = infer_or_load_unischema(fs, path)
        cal = profiler.get_calibration(fs, path, pieces, schema, mode='auto')
        ceilings = cal['ceilings']
        # io+decode ONLY: this number is labeled as the serial io+decode
        # ceiling, so the staging/serializer probes must not silently cap it
        serial = profiler.predict_throughput(
            {'io': ceilings.get('io'), 'decode': ceilings.get('decode')},
            workers=1, cpu_count=1, io_overlap=False)
        return {
            'io_decode_ceiling_samples_per_sec': round(serial, 1)
            if serial else None,
            'decode_ceiling_samples_per_sec': ceilings.get('decode'),
            'io_ceiling_samples_per_sec': ceilings.get('io'),
            'cpu_count': cal.get('cpu_count'),
        }
    except Exception as e:  # noqa: BLE001 - report, never sink the bench
        print('store roofline probe failed for {}: {!r}'.format(url, e),
              file=sys.stderr)
        return None


def _with_roofline(line: dict, roofline) -> dict:
    """Attach the store's measured ceiling and this line's %-of-ceiling —
    the VERDICT.md ask: every decode-bound/cached samples/sec judged
    against a measured number, not vibes. Cached lines legitimately exceed
    100% (they skip the io+decode the ceiling measures)."""
    out = dict(line)
    if not roofline:
        return out
    ceiling = roofline.get('io_decode_ceiling_samples_per_sec')
    sps = out.get('samples_per_sec')
    out['roofline'] = dict(roofline)
    if ceiling and sps:
        out['roofline_pct'] = round(100.0 * sps / ceiling, 2)
    return out


def compact_summary(summary: dict, out_path=None) -> dict:
    """The bounded stdout summary: headline + per-line rates, nothing
    free-text. ``tests/test_profiler.py`` asserts the serialized form
    stays far inside a 4 KB tail-capture window."""
    northstar = summary.get('northstar') or {}
    lines = {}
    for name, line in northstar.items():
        if not isinstance(line, dict):
            continue
        sps = line.get('samples_per_sec')
        if sps is None:
            continue
        brief = {'sps': round(sps, 1)}
        if line.get('overlap_pct') is not None:
            brief['ov'] = line['overlap_pct']
        if line.get('roofline_pct') is not None:
            brief['roof'] = line['roofline_pct']
        lines[name] = brief
    dispersion = dict(summary.get('dispersion') or {})
    dispersion.pop('protocol', None)
    roofline_bench = summary.get('roofline_bench') or {}
    compact = {
        'metric': summary.get('metric'),
        'value': summary.get('value'),
        'statistic': summary.get('statistic'),
        'unit': summary.get('unit'),
        'vs_baseline': summary.get('vs_baseline'),
        'dispersion': dispersion,
        'platform': northstar.get('platform'),
        'roofline': {
            'binding_stage': (roofline_bench.get('roofline') or {})
            .get('binding_stage'),
            'pct': (roofline_bench.get('roofline') or {})
            .get('roofline_pct'),
            'measured_sps': roofline_bench.get('measured_samples_per_sec'),
        },
        'northstar': lines,
        'out': out_path,
    }
    return compact


def emit(summary: dict, out_path=None) -> None:
    """Full summary -> stderr + atomic ``--out`` file + local trajectory
    append; bounded compact summary -> the LAST stdout line (the only line
    a tail capture needs)."""
    print(json.dumps(summary, sort_keys=True), file=sys.stderr)
    if out_path:
        from petastorm_tpu.utils import atomic_write
        atomic_write(out_path,
                     lambda f: json.dump(summary, f, indent=2,
                                         sort_keys=True))
    try:
        # load the gate by path (same pattern as check_bench_docs): a bare
        # sys.path.insert would let ci/ module names shadow stdlib/package
        # imports for the rest of the process
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            'check_perf_regression',
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         'ci', 'check_perf_regression.py'))
        gate = sys.modules.get('check_perf_regression')
        if gate is None:
            gate = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(gate)
        entries, _ = gate.normalize_artifact('bench.py', {'parsed': summary})
        gate.append_entries(entries)
    except Exception as e:  # noqa: BLE001 - trajectory append is best-effort
        print('perf-trajectory append failed: {!r}'.format(e),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(compact_summary(summary, out_path), sort_keys=True))


def main(argv=None):
    import argparse
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--out', default=None, metavar='PATH',
                        help='write the FULL summary JSON here atomically '
                             '(stdout carries only the bounded compact '
                             'summary line)')
    args = parser.parse_args(argv)
    platform = _platform()
    from petastorm_tpu.utils import configure_compile_cache
    configure_compile_cache(os.path.dirname(os.path.abspath(__file__)))

    from petastorm_tpu.benchmark import northstar
    from petastorm_tpu.benchmark.hello_world import generate_hello_world_dataset
    from petastorm_tpu.benchmark.throughput import reader_throughput

    # Read-bound headline protocol (round-3 verdict: the old 10-row store
    # with 1000 measured reads was epoch-reset-bound — noise swamped a 30%
    # swing). A 10k-row store with 32MB row groups keeps 3 thread workers
    # decoding continuously; 5 runs of 10k measured samples give a best +
    # dispersion record so the artifact can defend a perf claim.
    hello_rows = 10000
    hello_path = '{}_{}'.format(DATASET_PATH, hello_rows)
    url = 'file://' + hello_path
    _ensure(hello_path, '_common_metadata',
            lambda: generate_hello_world_dataset(url, rows_count=hello_rows,
                                                 row_group_size_mb=32))

    # one discarded priming run: the 10k-row store is ~1.4GB and the first
    # pass after generation streams from cold page cache — disk speed, not
    # reader speed
    reader_throughput(url, warmup_cycles=100, measure_cycles=10000,
                      pool_type='thread', workers_count=3,
                      read_method='python')
    runs = []
    for _ in range(5):
        result = reader_throughput(url, warmup_cycles=1000,
                                   measure_cycles=10000,
                                   pool_type='thread', workers_count=3,
                                   read_method='python')
        runs.append(result.samples_per_sec)
    runs.sort()
    best = runs[-1]
    median = runs[len(runs) // 2]
    dispersion = {
        'runs': len(runs),
        'min': round(runs[0], 2),
        'median': round(median, 2),
        'max': round(best, 2),
        'spread_pct': round(100.0 * (runs[-1] - runs[0]) / median, 2),
        'protocol': {'rows': hello_rows, 'warmup_samples': 1000,
                     'measured_samples': 10000, 'workers': 3,
                     'pool': 'thread'},
    }

    # -- transport: pickle vs zero-copy worker->loader path -----------------
    # Quick mode keeps this to a few seconds; the copy counters and the
    # in-process MB/s ratio are the stable signals (the pool-stream MB/s is
    # spawn-dominated at this item count and is reported for context only).
    from petastorm_tpu.benchmark.transport import run_transport_bench
    transport = run_transport_bench(quick=True)

    # -- readahead: serial vs prefetched row-group reads --------------------
    # Slow-IO shim pins io:decode at ~1:1; the quick mode keeps the stable
    # signals (speedup over serial, overlap fraction, hit rate) in seconds.
    from petastorm_tpu.benchmark.readahead import run_readahead_bench
    readahead = run_readahead_bench(quick=True)

    # -- tracing: span-tracer overhead (items/s on vs off) ------------------
    # The quick mode is the smoke signal (sub-second passes are noisy); the
    # defensible <5% figure lives in BENCH_r08.json from the full run.
    from petastorm_tpu.benchmark.trace_overhead import run_trace_overhead_bench
    trace_overhead = run_trace_overhead_bench(quick=True)

    # -- lineage: default-on provenance/audit overhead (items/s on vs off) --
    # Same smoke-vs-headline split: the <5% figure lives in BENCH_r10.json.
    from petastorm_tpu.benchmark.lineage_overhead import \
        run_lineage_overhead_bench
    lineage_overhead = run_lineage_overhead_bench(quick=True)

    # -- latency: default-on histogram/SLO plane overhead (on vs off) -------
    # Same smoke-vs-headline split: the <5% figure lives in BENCH_r14.json.
    from petastorm_tpu.benchmark.latency_overhead import \
        run_latency_overhead_bench
    latency_overhead = run_latency_overhead_bench(quick=True)

    # -- shared cache: K readers x one dataset, decoded once ----------------
    # Quick mode asserts the decode-once invariant and warm-vs-roofline; the
    # >=2x aggregate headline lives in BENCH_r11.json from the full run.
    from petastorm_tpu.benchmark.shared_cache import run_shared_cache_bench
    shared_cache = run_shared_cache_bench(quick=True)
    # per_reader detail is full-run/artifact material, not headline JSON
    shared_cache['shared'].pop('per_reader', None)
    shared_cache['local_disk_baseline'].pop('per_reader', None)

    # -- roofline: calibrated ceilings + attribution on the mnist decode line
    # Quick mode asserts binding-stage/monotonicity/model-replay; the
    # headline roofline record lives in BENCH_r12.json from the full run.
    from petastorm_tpu.benchmark.roofline import run_roofline_bench
    roofline_bench = run_roofline_bench(quick=True)
    # span-level detail is artifact material, not headline JSON
    roofline_bench.pop('attribution', None)
    roofline_bench.pop('probes', None)

    # -- batched decode: vectorized vs per-cell codec decode ----------------
    # Quick mode asserts bit-identity + the path-split counters; the
    # headline roofline record lives in BENCH_r13.json from the full run.
    from petastorm_tpu.benchmark.decode_batch import run_decode_batch_bench
    decode_batch = run_decode_batch_bench(quick=True)
    # per-run detail is artifact material, not headline JSON
    for line in decode_batch.get('lines', {}).values():
        line.pop('runs', None)
        line.pop('roofline', None)

    # -- autotune: mis-tuned recovery + steady guard ------------------------
    # Quick mode asserts the controller's own graded move helped and the
    # steady guard held; the headline recovery record lives in
    # BENCH_r15.json from the full run.
    from petastorm_tpu.benchmark.autotune import run_autotune_bench
    autotune_bench = run_autotune_bench(quick=True)
    # per-sample detail is artifact material, not headline JSON
    autotune_bench.get('recovered', {}).pop('timeline', None)

    # -- chaos: hedged vs unhedged reads under injected tail latency --------
    # Quick mode asserts hedges fire and recover the e2e p99; the headline
    # >=2x recovery + <5% clean-path overhead live in BENCH_r16.json.
    from petastorm_tpu.benchmark.chaos import run_chaos_bench
    chaos_bench = run_chaos_bench(quick=True)

    # -- north-star: train-step infeed overlap ------------------------------
    # Chip-scale configs on the TPU; dataset paths carry the size parameters
    # so a platform change can't reuse a stale store.
    on_tpu = platform == 'tpu'
    mnist_rows = 16384 if on_tpu else 2048
    mnist_batch = 512 if on_tpu else 128
    seq_len = 256 if on_tpu else 128
    mnist_path = '{}_{}'.format(MNIST_PATH, mnist_rows)
    tokens_rows = 2048 if on_tpu else 512
    # small row groups: the train benches bound read-ahead in CHUNKS, so a
    # chunk must be far smaller than the measured window for the bound to bite
    tokens_path = '{}_{}x{}_rg05'.format(TOKENS_PATH, tokens_rows, seq_len)
    mnist_url = 'file://' + mnist_path
    tokens_url = 'file://' + tokens_path
    _ensure(mnist_path, '_common_metadata',
            lambda: northstar.generate_mnist_images_dataset(
                mnist_url, rows=mnist_rows))
    _ensure(tokens_path, '_common_metadata',
            lambda: northstar.generate_token_dataset(
                tokens_url, rows=tokens_rows, seq_len=seq_len,
                row_group_size_mb=0.5))

    # NGram pipeline store: timestamped token chunks assembled into windows
    # at read time (the reference's sequence-model input path, SURVEY §5.7)
    ngram_chunk = 64
    ngram_rows = 8192 if on_tpu else 256
    ngram_path = '/tmp/petastorm_tpu_northstar_ngram_{}x{}_g256'.format(
        ngram_rows, ngram_chunk)
    ngram_url = 'file://' + ngram_path
    _ensure(ngram_path, '_common_metadata',
            lambda: northstar.generate_timeseries_token_dataset(
                ngram_url, rows=ngram_rows, chunk=ngram_chunk,
                rows_per_group=256))

    imagenet_rows = 2048 if on_tpu else 48
    imagenet_path = '{}_{}'.format(IMAGENET_PATH, imagenet_rows)
    imagenet_url = 'file://' + imagenet_path
    _ensure(imagenet_path, '_common_metadata',
            lambda: northstar.generate_imagenet_dataset(
                imagenet_url, rows=imagenet_rows, row_group_size_mb=1.0))
    # Real ImageNet is jpeg; a second store exercises the DCT-scaled decode
    # fast path (decode_hints={'image': {'scale': 2}}) against the png line.
    imagenet_jpeg_path = '{}_{}_jpeg'.format(IMAGENET_PATH, imagenet_rows)
    imagenet_jpeg_url = 'file://' + imagenet_jpeg_path
    _ensure(imagenet_jpeg_path, '_common_metadata',
            lambda: northstar.generate_imagenet_dataset(
                imagenet_jpeg_url, rows=imagenet_rows, image_codec='jpeg',
                row_group_size_mb=1.0))
    scale_hints = {'image': {'scale': 2}}
    # The decoded-columns disk-cache line uses its own store with BIG row
    # groups: the row group is the cache-replay unit, and 1MB-encoded groups
    # (~4 rows) make epochs 2+ pay per-chunk pool overhead ~8x more often
    # than 8MB groups. (Tiny groups remain right for decode-bound epoch 1
    # parallelism — that is the other stores' protocol.)
    imagenet_rg8_path = '{}_{}_rg8'.format(IMAGENET_PATH, imagenet_rows)
    imagenet_rg8_url = 'file://' + imagenet_rg8_path
    _ensure(imagenet_rg8_path, '_common_metadata',
            lambda: northstar.generate_imagenet_dataset(
                imagenet_rg8_url, rows=imagenet_rows, row_group_size_mb=8.0))

    if on_tpu:
        mnist = northstar.run_mnist_train_bench(
            mnist_url, batch_size=mnist_batch, num_steps=120, hidden=2048)
        mnist_cached = northstar.run_mnist_cached_train_bench(
            mnist_url, rows=mnist_rows, batch_size=mnist_batch, num_steps=60,
            hidden=2048)
        lm = northstar.run_transformer_train_bench(
            tokens_url, batch_size=64, num_steps=40, seq_len=seq_len)
        lm_ngram = northstar.run_ngram_transformer_train_bench(
            ngram_url, window=4, chunk=ngram_chunk, batch_size=64,
            num_steps=40)
        lm_ngram_indexed = northstar.run_indexed_ngram_transformer_train_bench(
            ngram_url, window=4, chunk=ngram_chunk, batch_size=64,
            num_steps=40)
        # image_size must be COVERED by the scale-2 decode of every image
        # (smallest is ~150 px after halving the 0.8x-jittered 375 px base):
        # otherwise the hinted lines would train on upscaled, degraded inputs
        # while the png line decodes full-res — not a fair comparison.
        img_decode = northstar.run_image_decode_bench(
            imagenet_url, image_size=128)
        # warmup_steps=12 drains the read-ahead surplus (queue chunks +
        # prefetch buffers filled while jit compiles) so the measured window
        # is steady state — without it the train line can read ABOVE the
        # decode-only ceiling (round-2/3 invariant violation)
        imagenet = northstar.run_imagenet_train_bench(
            imagenet_url, batch_size=32, num_steps=200, warmup_steps=12,
            image_size=128)
        img_decode_jpeg = northstar.run_image_decode_bench(
            imagenet_jpeg_url, image_size=128, decode_hints=scale_hints)
        imagenet_jpeg = northstar.run_imagenet_train_bench(
            imagenet_jpeg_url, batch_size=32, num_steps=200, warmup_steps=12,
            image_size=128, decode_hints=scale_hints)
        imagenet_cached = northstar.run_imagenet_cached_train_bench(
            imagenet_rg8_url, rows=imagenet_rows, batch_size=32,
            num_steps=120, image_size=128)
    else:
        mnist = northstar.run_mnist_train_bench(
            mnist_url, batch_size=mnist_batch, num_steps=15, hidden=256)
        mnist_cached = northstar.run_mnist_cached_train_bench(
            mnist_url, rows=mnist_rows, batch_size=mnist_batch, num_steps=15,
            hidden=256)
        lm = northstar.run_transformer_train_bench(
            tokens_url, batch_size=8, num_steps=8, seq_len=seq_len,
            d_model=128, n_layers=2, d_ff=512)
        lm_ngram = northstar.run_ngram_transformer_train_bench(
            ngram_url, window=2, chunk=ngram_chunk, batch_size=8,
            num_steps=8, d_model=128, n_layers=2, d_ff=512)
        lm_ngram_indexed = northstar.run_indexed_ngram_transformer_train_bench(
            ngram_url, window=2, chunk=ngram_chunk, batch_size=8,
            num_steps=8, d_model=128, n_layers=2, d_ff=512)
        img_decode = northstar.run_image_decode_bench(imagenet_url,
                                                     image_size=96)
        imagenet = northstar.run_imagenet_train_bench(
            imagenet_url, batch_size=8, num_steps=4, image_size=96)
        img_decode_jpeg = northstar.run_image_decode_bench(
            imagenet_jpeg_url, image_size=96, decode_hints=scale_hints)
        imagenet_jpeg = northstar.run_imagenet_train_bench(
            imagenet_jpeg_url, batch_size=8, num_steps=4, image_size=96,
            decode_hints=scale_hints)
        imagenet_cached = northstar.run_imagenet_cached_train_bench(
            imagenet_rg8_url, rows=imagenet_rows, batch_size=8,
            num_steps=8, image_size=96)
    columnar = northstar.run_columnar_read_bench(mnist_url)

    # measured io+decode ceilings for the decode-wall stores: every
    # decode-bound and cached line below records its %-of-ceiling so the
    # next decode-wall PR is judged against a measured number (the jpeg
    # hinted lines are excluded — DCT-scaled decode does strictly less
    # work than the full-resolution decode the probe measures, so a % of
    # that ceiling would mislead)
    mnist_roofline = _store_roofline(mnist_url)
    imagenet_roofline = _store_roofline(imagenet_url)
    imagenet_rg8_roofline = _store_roofline(imagenet_rg8_url)

    # Internal consistency: decode-only throughput must upper-bound
    # decode+train on the same store. Checked per store and recorded in the
    # artifact itself so BENCH JSON is self-consistent without the docs.
    def _consistency(decode, train):
        d, t = decode['samples_per_sec'], train.samples_per_sec
        margin = 100.0 * (d - t) / d if d else None
        return {'decode_only': round(d, 2), 'train': round(t, 2),
                'decode_ge_train': d >= t,
                # a decode-bound train line measures the same decode ceiling
                # as the decode-only line: equality within measurement noise
                # satisfies the invariant
                'consistent_within_1pct': d >= t or (d > 0 and (t - d) / d < 0.01),
                'margin_pct': round(margin, 2) if margin is not None else None}

    consistency = {
        'png': _consistency(img_decode, imagenet),
        'jpeg_hinted': _consistency(img_decode_jpeg, imagenet_jpeg),
    }

    # The cached line's own context rides in the artifact: the claim is the
    # throughput multiple over the decode-bound line (decode+resize skipped
    # on epochs 2+), NOT the overlap figure — on a 1-core host the remaining
    # per-byte work (cache read, collate, H2D staging, all GIL-shared with
    # step dispatch) bounds overlap far below the >=90% target that the
    # zero-host-work device cache reaches (mnist_train_cached). Measured
    # r05: one-dispatch transfer protocols can print ~99% overlap here only
    # by collapsing throughput ~10x (transfer riding inside "compute"), so
    # this line keeps the throughput-optimal protocol and reports honestly.
    cached_dict = _with_roofline(imagenet_cached.as_dict(),
                                 imagenet_rg8_roofline)
    if imagenet.samples_per_sec:
        cached_dict['vs_decode_bound'] = round(
            imagenet_cached.samples_per_sec / imagenet.samples_per_sec, 1)
    # the claim/caveat prose lives in docs/benchmarks.md (keeping notes out
    # of the artifact bounds the summary line — the r05 capture lesson)

    summary = {
        'metric': 'hello_world_reader_throughput',
        # the MEDIAN run: the honest central figure on a host with tens-of-
        # percent run variance (the throughput CLI's --runs mode headlines
        # the same statistic; best/min stay in the dispersion block).
        # Rounds <=4 headlined the best run — compare cross-round via the
        # dispersion medians.
        'value': round(median, 2),
        'statistic': 'median',
        'unit': 'samples/sec',
        'vs_baseline': round(median / BASELINE_SAMPLES_PER_SEC, 3),
        'dispersion': dispersion,
        'transport': transport,
        'readahead': readahead,
        'trace_overhead': trace_overhead,
        'lineage_overhead': lineage_overhead,
        'latency_overhead': latency_overhead,
        'shared_cache': shared_cache,
        'roofline_bench': roofline_bench,
        'decode_batch': decode_batch,
        'autotune': autotune_bench,
        'chaos': chaos_bench,
        'northstar': {
            'platform': platform,
            'mnist_train': _with_roofline(mnist.as_dict(), mnist_roofline),
            'mnist_train_cached': _with_roofline(mnist_cached.as_dict(),
                                                 mnist_roofline),
            'transformer_train': lm.as_dict(),
            'transformer_train_ngram': lm_ngram.as_dict(),
            'transformer_train_ngram_indexed': lm_ngram_indexed.as_dict(),
            'image_decode': _with_roofline(img_decode, imagenet_roofline),
            'imagenet_train': _with_roofline(imagenet.as_dict(),
                                             imagenet_roofline),
            'image_decode_jpeg_hinted': img_decode_jpeg,
            'imagenet_train_jpeg_hinted': imagenet_jpeg.as_dict(),
            'imagenet_train_cached': cached_dict,
            'columnar_read': _with_roofline(columnar, mnist_roofline),
            'decode_train_consistency': consistency,
        },
    }
    emit(summary, args.out)


if __name__ == '__main__':
    main()
