"""The comparison that decides ``correct``.

Two parts, both against the configuration's plain reference
(``drivers/<driver>_ref.py``), which imports nothing of the program:

- the data: the rows each compared batch names by ``row_id`` are read
  straight from the store and transformed by the reference; the batch the
  step consumed must hold exactly those values (limit 0). The compared
  batches are the three the reference trains on and a sample of the
  window's drawn from the seed (all of them where ``check_batches`` is 0).
- the training: the reference follows the run's first three steps from
  weights it draws from the seed itself. Compared are each step's loss, the
  first gradient as the optimizer got it (read from its state after step 1)
  and the parameters' change after step 3, each norm by the worst leaf and
  by the median leaf.
"""

import math

import numpy as np

#: Leaves whose reference gradient is under this share of the median
#: leaf's move by round-off alone under Adam; the change leaves them out.
NEGLIGIBLE_GRAD = 1e-3


def leaves(tree):
    """``{path: array}`` of a pytree."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def norms(tree):
    """Each leaf's L2 norm, its squares summed in float64."""
    return {k: math.sqrt(float(np.sum(np.square(v), dtype=np.float64)))
            for k, v in leaves(tree).items()}


def leaf_gaps(got, want, keep=None):
    """Per leaf, ``| |got| - |want| |`` over the larger of ``|want|`` and
    the median leaf's ``|want|``."""
    got, want = norms(got), norms(want)
    if set(got) != set(want):
        raise ValueError('the program and the reference hold different '
                         'leaves: {}'.format(sorted(set(got) ^ set(want))))
    median = float(np.median(list(want.values())))
    keys = sorted(want) if keep is None else sorted(keep)
    return [abs(got[k] - want[k]) / max(want[k], median) for k in keys]


def loss_gap(got, want):
    return max(abs(g - w) / abs(w) if math.isfinite(g) else math.inf
               for g, w in zip(got, want))


def change(params3, params0):
    """The parameters' change, in float32: a change of about 1e-3 on
    weights of about 3e-2 keeps 1e-6 of its precision there."""
    import jax
    return jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
        params3, params0)


def moved_leaves(ref_grad):
    """The leaves whose change is compared: a rule on the reference's first
    gradient, not a list of names."""
    g = norms(ref_grad)
    median = float(np.median(list(g.values())))
    return {k for k, v in g.items() if v >= NEGLIGIBLE_GRAD * median}


def training_numbers(got, want):
    """``got``/``want``: ``{'losses', 'grad', 'params0', 'params'}`` of the
    program (or a control) and of the reference, after three steps. The
    gradient and the change are each read by the worst leaf and by the
    median leaf: the worst is an early layer whose rounding the whole
    backward pass has carried, and swings 2-3x from seed to seed; the
    median is steady (PERF.md)."""
    grad = leaf_gaps(got['grad'], want['grad'])
    moved = leaf_gaps(change(got['params'], got['params0']),
                      change(want['params'], want['params0']),
                      moved_leaves(want['grad']))
    return {
        'loss_gap': loss_gap(got['losses'], want['losses']),
        'grad_gap': max(grad),
        'grad_gap_median': float(np.median(grad)),
        'change_gap': max(moved),
        'change_gap_median': float(np.median(moved)),
    }


def reference_run(cfg, ref, source, batches, seed, control=None):
    """The reference's three steps over the rows ``batches`` named."""
    import jax
    params0 = ref.init(cfg, seed)
    host0 = jax.device_get(params0)
    out = ref.train3(cfg, params0, [source.rows(b['row_id']) for b in batches],
                     control=control)
    out['params0'] = host0
    return out


def data_numbers(source, batches, row_ids, rows):
    """Rows whose values differ from the reference, over the compared
    batches; row ids outside the store, over every batch of the window."""
    mismatched = 0
    for batch in batches:
        want = source.rows(batch['row_id'])
        ok = np.ones(len(batch['row_id']), bool)
        for name, value in want.items():
            got = np.asarray(batch[name])
            if got.shape != value.shape:
                ok[:] = False
                continue
            ok &= (got == value).reshape(len(ok), -1).all(axis=1)
        mismatched += int((~ok).sum())
    ids = np.concatenate([np.asarray(r).reshape(-1) for r in row_ids])
    invalid = int(((ids < 0) | (ids >= rows)).sum())
    return {'rows_mismatched': float(mismatched),
            'row_ids_invalid': float(invalid)}


def compare(cfg, ref, store, seed, checked, sampled, row_ids, rows,
            program_losses, grad1, params0, params3, window_losses):
    """Every number ``correct`` is decided on, by name."""
    source = ref.RowSource(cfg, store, seed)
    numbers = data_numbers(source, checked + list(sampled.values()),
                           row_ids, rows)
    want = reference_run(cfg, ref, source, checked, seed)
    numbers.update(training_numbers(
        {'losses': program_losses, 'grad': grad1, 'params0': params0,
         'params': params3}, want))
    numbers['losses_nonfinite'] = float(sum(
        1 for x in window_losses if not math.isfinite(x)))
    return numbers
