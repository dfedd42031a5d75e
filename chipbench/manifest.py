"""Checks ``BENCHMARK.json`` against the benchmark's contract: its keys,
names, units and limits, and that every part a cell names exists."""

import os
import re

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_./-]{1,200}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
TOP = {'command', 'paths', 'run_seconds', 'configs', 'workloads',
       'end_to_end', 'per_layer'}
KEYS = {
    'configs': {'name', 'source', 'file', 'reduced', 'why'},
    'workloads': {'name', 'config', 'traffic', 'chips', 'why'},
    'end_to_end': {'name', 'unit', 'better', 'bound', 'source'},
    'per_layer': {'name', 'unit', 'better', 'source', 'layer', 'moves'},
}
WIDTH = re.compile(r'(_dim|_rank)$|hidden|intermediate|latent|state_size|'
                   r'proj|head|expansion|experts_per_tok|d_model|d_ff|width')


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and '\n' not in text and '\t' not in text)


def check_budget(run_seconds, cells=24, total=43200):
    """A full check of ``cells`` cells fits its time: 2 + 14 runs a cell of
    ``run_seconds + 60`` s, 2 x 90 s of compile a cell, 1200 s spare."""
    runs = 2 + 14 * cells
    return runs * (run_seconds + 60) + cells * 2 * 90 + 1200 <= total


def errors(manifest, root):
    """Every way ``manifest`` breaks the contract, as readable strings."""
    out = []

    def need(ok, what):
        if not ok:
            out.append(what)

    need(set(manifest) == TOP, 'top-level keys {}'.format(sorted(manifest)))
    command = manifest.get('command', [])
    need(isinstance(command, list) and 1 <= len(command) <= 32
         and all(_line(w) for w in command), 'command')
    paths = manifest.get('paths', [])
    need(1 <= len(paths) <= 16 and all(
        PATH.match(p) and not p.startswith('/') and '..' not in p.split('/')
        for p in paths), 'paths')
    for word in command[1:]:
        if '/' in word:
            need(any(word.startswith(p.rstrip('/') + '/') for p in paths),
                 'command names {} outside paths'.format(word))
    rs = manifest.get('run_seconds')
    need(isinstance(rs, int) and 1 <= rs <= 51 and check_budget(rs),
         'run_seconds {}'.format(rs))

    names = set()
    for kind, keys in KEYS.items():
        entries = manifest.get(kind, [])
        for e in entries:
            extra = set(e) - keys - ({'workloads'} if kind in (
                'end_to_end', 'per_layer') else set())
            need(keys <= set(e) and not extra,
                 '{} {}: keys {}'.format(kind, e.get('name'), sorted(e)))
            name = e.get('name', '')
            need(bool(NAME.match(name)), 'name {!r}'.format(name))
            need((kind, name) not in names, 'duplicate {!r}'.format(name))
            names.add((kind, name))
            if kind in ('end_to_end', 'per_layer'):
                need(bool(UNIT.match(e.get('unit', ''))),
                     'unit of {}'.format(name))
                need(e.get('better') in ('lower', 'higher'),
                     'better of {}'.format(name))
                need(e.get('source') in SOURCES, 'source of {}'.format(name))
    configs = {c['name']: c for c in manifest.get('configs', [])}
    cells = {w['name']: w for w in manifest.get('workloads', [])}
    e2e = {m['name']: m for m in manifest.get('end_to_end', [])}
    need(1 <= len(configs) <= 24 and 1 <= len(cells) <= 24, 'counts')
    need(1 <= len(e2e) <= 16 and 'setup_s' in e2e, 'end_to_end')
    for c in configs.values():
        need(_line(c['source']) and _line(c['why']), 'config text')
        need(c['file'].startswith(tuple(p + '/' for p in paths))
             and os.path.exists(os.path.join(root, c['file'])),
             'config file {}'.format(c['file']))
        need(len(c['reduced']) <= 16 and all(
            NAME.match(k) and not WIDTH.search(k) for k in c['reduced']),
            'reduced of {}'.format(c['name']))
        need(any(w['config'] == c['name'] for w in cells.values()),
             'config {} unused'.format(c['name']))
    need(len({c['file'] for c in configs.values()}) == len(configs),
         'config files shared')
    four = sum(1 for w in cells.values() if w['chips'] == 4)
    need(four <= max(1, len(cells) // 2), 'too many 4-chip cells')
    pairs = set()
    for w in cells.values():
        need(w['config'] in configs, 'cell {} config'.format(w['name']))
        need(w['chips'] in (1, 4) and _line(w['why']),
             'cell {}'.format(w['name']))
        need(NAME.match(w['traffic']) is not None, 'traffic name')
        need((w['config'], w['traffic']) not in pairs, 'pair repeated')
        pairs.add((w['config'], w['traffic']))
    for m in e2e.values():
        need(m['source'] in ('host_clock', 'device_trace'),
             'source of {}'.format(m['name']))
        need(0 < m['bound'] <= 0.25 and m['bound'] >= 0.01,
             'bound of {}'.format(m['name']))
    for m in manifest.get('per_layer', []):
        need(_line(m['layer']), 'layer of {}'.format(m['name']))
        need(m['moves'] in e2e, 'moves of {}'.format(m['name']))
        moved = e2e.get(m['moves'], {}).get('workloads', cells)
        for w in m.get('workloads', cells):
            need(w in cells, '{} lists {}'.format(m['name'], w))
            need(w in moved, '{} does not report {}'.format(w, m['moves']))
    for w in cells:
        reported = [m for m in e2e.values()
                    if w in m.get('workloads', cells)]
        need(len(reported) >= 2, '{} end-to-end metrics'.format(w))
        need(any(w in m.get('workloads', cells)
                 for m in manifest.get('per_layer', [])),
             '{} per-layer metrics'.format(w))
    return out
