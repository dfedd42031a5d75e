"""Launch a python function in a brand-new interpreter (not a fork).

Reference parity: ``petastorm/workers_pool/exec_in_new_process.py:26-69``. The
reference avoids fork because it broke JVM-based HDFS drivers
(``process_pool.py:15-17``); we avoid it because **libtpu must only initialize
in the main process** — spawned clean interpreters are pinned to
``JAX_PLATFORMS=cpu`` so a worker can never grab the TPU (SURVEY.md §7
"hard parts").
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile


def exec_in_new_process(func, args=(), kwargs=None) -> subprocess.Popen:
    """Serialize ``(func, args, kwargs)`` with dill to a temp file and launch
    ``python -S -m petastorm_tpu.workers.exec_in_new_process <file>``.

    ``-S`` skips ``site`` initialization in the worker; the parent's
    fully-resolved ``sys.path`` is passed via PYTHONPATH, so everything
    importable in the parent (including ``.pth``-added entries) stays
    importable in the worker. Set ``PETASTORM_TPU_WORKER_SITE=1`` to restore
    normal site initialization if a worker dependency needs it."""
    import dill
    fd, path = tempfile.mkstemp(prefix='petastorm_tpu_bootstrap_', suffix='.dill')
    with os.fdopen(fd, 'wb') as f:
        dill.dump((func, tuple(args), dict(kwargs or {})), f)
    env = dict(os.environ)
    # Workers stay pure-CPU: the TPU runtime belongs to the main process only.
    env['JAX_PLATFORMS'] = 'cpu'
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    use_site = env.get('PETASTORM_TPU_WORKER_SITE') == '1'
    interpreter = [sys.executable] if use_site else [sys.executable, '-S']
    if use_site:
        paths = [repo_root] + env.get('PYTHONPATH', '').split(os.pathsep)
    else:
        paths = [repo_root] + [p for p in sys.path if p]
    seen, deduped = set(), []
    for p in paths:
        if p and p not in seen:
            seen.add(p)
            deduped.append(p)
    env['PYTHONPATH'] = os.pathsep.join(deduped)
    return subprocess.Popen(
        interpreter + ['-m', 'petastorm_tpu.workers.exec_in_new_process', path],
        env=env)


def _main():
    import dill
    path = sys.argv[1]
    try:
        try:
            with open(path, 'rb') as f:
                func, args, kwargs = dill.load(f)
        finally:
            try:
                os.remove(path)
            except OSError:
                pass
        func(*args, **kwargs)
    except ImportError as e:
        if not sys.flags.no_site:
            raise
        # -S skips .pth execution, which PEP 660 editable installs rely on for
        # their meta-path finders; point the user at the escape hatch.
        raise ImportError(
            '{} (worker started with -S to skip site initialization; if the '
            'missing module comes from an editable install or a .pth hook, '
            'set PETASTORM_TPU_WORKER_SITE=1 to restore normal site '
            'startup)'.format(e)) from e


if __name__ == '__main__':
    _main()
