"""petalint rules R1-R6: the repo's proven failure classes, machine-checked.

Each rule descends from a concrete incident this repo already paid for
(CHANGES.md, PRs 1-7); the catalog with incident references and the
suppression workflow lives in ``docs/static_analysis.md``. Rules are
deliberately *syntactic* approximations of the invariants — cheap, zero
dependencies, no type inference — tuned so that the current first-party
code passes with an empty baseline and every historical bug shape fails.
"""

from __future__ import annotations

import ast
import fnmatch
from typing import Iterable, List, Optional, Tuple

from ci.analysis.engine import (Finding, ModuleContext, Rule, call_name,
                                dotted_name, walk_excluding_defs)


def _scoped(relpath: str, patterns) -> bool:
    return any(fnmatch.fnmatch(relpath, p) for p in patterns)


def _terminal_name(node: ast.AST) -> Optional[str]:
    """The last identifier of a receiver expression: ``self._results_queue``
    -> ``_results_queue``, ``lock`` -> ``lock``."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _contains_raise(body) -> bool:
    for stmt in body:
        for node in [stmt] + list(walk_excluding_defs(stmt)):
            if isinstance(node, ast.Raise):
                return True
    return False


def _open_mode(call: ast.Call) -> Optional[str]:
    """The literal mode of an ``open()``-style call, or None when dynamic."""
    mode_node = None
    if len(call.args) >= 2:
        mode_node = call.args[1]
    for kw in call.keywords:
        if kw.arg == 'mode':
            mode_node = kw.value
    if mode_node is None:
        return 'r'
    if isinstance(mode_node, ast.Constant) and isinstance(mode_node.value,
                                                          str):
        return mode_node.value
    return None


class AtomicPublishRule(Rule):
    """R1 — artifact writes must publish atomically.

    Incident: PR 5 found chrome traces / flight records / ``.prom`` files
    written with a bare ``open(path, 'w')`` — a crash mid-dump (exactly when
    the artifact matters) left truncated JSON that tooling rejected, or
    clobbered the previous good artifact. The fix became
    ``utils.atomic_write`` (tmp + ``os.replace``), and the same shape turned
    up again in a benchmark's ``--out`` writer. This rule makes the *next*
    bare artifact write a CI failure: any ``open()`` in write/create mode
    must live in a function that also publishes via ``os.replace``/
    ``os.rename``/``os.link`` (tmp-file pattern) or calls
    ``atomic_write``.

    Carve-outs: append mode (``'a'`` is a line-append protocol, e.g. the
    JSONL metrics emitter), and a ``with open(path, 'w'): pass`` touch (a
    zero-byte marker is atomic by nature, e.g. pin files).
    """

    name = 'atomic-publish'
    description = ('artifact writes go through utils.atomic_write or '
                   'tmp + os.replace/os.link')
    interests = (ast.Call,)

    SCOPE = ('petastorm_tpu/*', 'ci/*')
    _PUBLISHERS = ('os.replace', 'os.rename', 'os.link', 'atomic_write',
                   'utils.atomic_write')

    def applies_to(self, relpath: str) -> bool:
        return _scoped(relpath, self.SCOPE)

    def visit(self, node: ast.Call, ctx: ModuleContext) -> Iterable[Finding]:
        if call_name(node) != 'open':
            return
        mode = _open_mode(node)
        if mode is None or not any(c in mode for c in 'wx'):
            return
        if self._is_touch(node, ctx):
            return
        func = ctx.enclosing_function(node)
        scope = func if func is not None else ctx.tree
        for sub in walk_excluding_defs(scope):
            if isinstance(sub, ast.Call):
                name = call_name(sub)
                if name in self._PUBLISHERS or (
                        name and name.endswith('.atomic_write')):
                    return
        yield ctx.finding(
            self.name, node,
            "open(..., '{}') without atomic publication: write to a tmp "
            'sibling and os.replace it, or use utils.atomic_write (a crash '
            'mid-write must not leave a truncated artifact)'.format(mode))

    @staticmethod
    def _is_touch(node: ast.Call, ctx: ModuleContext) -> bool:
        parent = ctx.parent(node)
        if isinstance(parent, ast.withitem):
            with_node = ctx.parent(parent)
            if isinstance(with_node, ast.With):
                return (len(with_node.body) == 1
                        and isinstance(with_node.body[0], ast.Pass))
        return False


class MonotonicClockRule(Rule):
    """R2 — heartbeat/stall/span/timeout code uses the monotonic clock.

    Incident: the PR 4 health layer's whole design hangs on heartbeat ages
    being computed on ``time.perf_counter()`` (CLOCK_MONOTONIC) — an NTP
    step against ``time.time()`` would fire a false stall dump (forward
    jump) or mask a real wedge forever (backward jump). The PR 6 shared
    cache aged single-flight locks with wall-clock arithmetic against file
    mtimes, the same hazard cross-process. In the scoped modules any
    ``time.time()`` / naive ``datetime.now()`` is flagged; a *deliberate*
    wall-clock timestamp (human-facing artifact fields like ``written_at``)
    carries an explicit ``# petalint: disable=monotonic-clock`` suppression
    stating so.
    """

    name = 'monotonic-clock'
    description = ('no time.time()/naive datetime.now() in heartbeat/stall/'
                   'span/timeout code')
    interests = (ast.Call,)

    SCOPE = ('petastorm_tpu/health.py', 'petastorm_tpu/tracing.py',
             'petastorm_tpu/sharedcache.py', 'petastorm_tpu/lineage.py',
             'petastorm_tpu/latency.py', 'petastorm_tpu/profiler.py',
             'petastorm_tpu/autotune.py', 'petastorm_tpu/workers/*',
             'petastorm_tpu/readers/readahead.py',
             'petastorm_tpu/resilience.py', 'petastorm_tpu/faultfs.py',
             'petastorm_tpu/ops/decode.py', 'petastorm_tpu/objectstore.py',
             'petastorm_tpu/podobs.py', 'petastorm_tpu/podelastic.py',
             'petastorm_tpu/goodput.py')
    _WALL_CALLS = ('time.time', 'datetime.now', 'datetime.datetime.now',
                   'datetime.utcnow', 'datetime.datetime.utcnow')

    def applies_to(self, relpath: str) -> bool:
        return _scoped(relpath, self.SCOPE)

    def visit(self, node: ast.Call, ctx: ModuleContext) -> Iterable[Finding]:
        name = call_name(node)
        if name not in self._WALL_CALLS:
            return
        if name.endswith('.now') and (node.args or node.keywords):
            return      # tz-aware now(tz) is an explicit choice
        yield ctx.finding(
            self.name, node,
            '{}() is wall-clock: stall/heartbeat/span/timeout arithmetic '
            'must use time.perf_counter()/time.monotonic() (clock steps '
            'fire false stalls or mask real ones); a deliberate human-facing '
            'timestamp needs an explicit wall-clock suppression'.format(name))


class LockDisciplineRule(Rule):
    """R3 — no blocking work inside a ``with <lock>:`` body; no bare
    ``acquire()``.

    Incident: the PR 6 shared cache originally flushed counter files and ran
    eviction I/O under its instance lock — one slow disk stalled every
    thread's telemetry path; the PR 4 review moved all I/O out of lock
    bodies ("lock-free reads" contract). The rule flags queue ``put``/
    ``get``, socket send/recv, ``subprocess`` use, file opens, ``time.sleep``
    and thread joins lexically inside a ``with``-block whose context
    expression looks like a lock (terminal identifier contains ``lock``),
    and any bare ``.acquire()`` on a lock-like receiver outside a ``with``
    header (acquisition must be ``finally``-safe: ``with lock:``).

    Condition variables (``cv``/``cond`` names) are exempt — ``wait()``
    releases them by design.
    """

    name = 'lock-discipline'
    description = ('no blocking calls (queue/socket/file/subprocess/sleep/'
                   'join) inside `with lock:`; no bare acquire()')
    interests = (ast.Call,)

    SCOPE = ('petastorm_tpu/*',)

    _SOCKET_METHODS = frozenset({'send', 'recv', 'send_multipart',
                                 'recv_multipart', 'send_pyobj', 'recv_pyobj',
                                 'sendall', 'sendto', 'recvfrom'})

    def applies_to(self, relpath: str) -> bool:
        return _scoped(relpath, self.SCOPE)

    @staticmethod
    def _lock_like(expr: ast.AST) -> bool:
        name = _terminal_name(expr)
        if name is None:
            return False
        lowered = name.lower()
        return 'lock' in lowered and 'lockdep' not in lowered

    def _held_locks(self, node: ast.AST, ctx: ModuleContext) -> List[str]:
        held: List[str] = []
        child = node
        for anc in ctx.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                break
            if isinstance(anc, ast.With) and child not in [
                    item.context_expr for item in anc.items]:
                for item in anc.items:
                    if self._lock_like(item.context_expr):
                        held.append(dotted_name(item.context_expr)
                                    or _terminal_name(item.context_expr)
                                    or '<lock>')
            child = anc
        return held

    def _blocking_desc(self, node: ast.Call) -> Optional[str]:
        name = call_name(node)
        if name == 'time.sleep':
            return 'time.sleep'
        if name in ('open', 'os.fdopen'):
            return '{}() file I/O'.format(name)
        if name and (name.startswith('subprocess.')
                     or name.endswith('.subprocess')):
            return name
        if not isinstance(node.func, ast.Attribute):
            return None
        attr = node.func.attr
        receiver = _terminal_name(node.func.value) or ''
        lowered = receiver.lower()
        if attr in ('put', 'get') and ('queue' in lowered
                                       or lowered in ('q', '_q')):
            return '{}.{}'.format(receiver, attr)
        if attr in self._SOCKET_METHODS and not lowered.endswith('cv'):
            return '{}.{}'.format(receiver, attr)
        if attr == 'join' and ('thread' in lowered or 'proc' in lowered):
            return '{}.join'.format(receiver)
        if attr == 'wait' and 'event' in lowered:
            return '{}.wait'.format(receiver)
        return None

    def visit(self, node: ast.Call, ctx: ModuleContext) -> Iterable[Finding]:
        # bare acquire(): not finally-safe unless it IS the with-header
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == 'acquire'
                and self._lock_like(node.func.value)):
            parent = ctx.parent(node)
            if not isinstance(parent, ast.withitem):
                yield ctx.finding(
                    self.name, node,
                    'bare {}.acquire(): an exception between acquire and '
                    'release leaks the lock — use `with {}:`'.format(
                        _terminal_name(node.func.value),
                        dotted_name(node.func.value) or 'lock'))
            return
        desc = self._blocking_desc(node)
        if desc is None:
            return
        held = self._held_locks(node, ctx)
        if held:
            yield ctx.finding(
                self.name, node,
                '{} inside `with {}:` — blocking work under a lock wedges '
                'every other acquirer (move the call outside the critical '
                'section; collect under the lock, act after)'.format(
                    desc, held[-1]))


class ExceptionHygieneRule(Rule):
    """R4 — ``except Exception`` in decode/worker paths must keep infra
    errors loud.

    Incident: the PR 5 quarantine layer's NEVER_QUARANTINE contract —
    ``OSError``/``MemoryError`` are *infrastructure* failures and must never
    be recorded as "bad sample" or silently swallowed by a worker funnel
    (the review round caught a tolerant decode path demoting an OSError to
    a quarantined row). Generalized: any ``except Exception:`` handler in
    the decode/worker modules must either contain a ``raise`` (conditional
    is fine — ``if isinstance(e, NEVER_QUARANTINE): raise`` or the policy
    funnel's ``if not self._quarantine_item(...): raise``), or be preceded
    by a handler for ``OSError``/``MemoryError``/``NEVER_QUARANTINE`` that
    re-raises. Teardown paths where swallow-everything is load-bearing
    carry justified suppressions.
    """

    name = 'exception-hygiene'
    description = ('except Exception in decode/worker paths must re-raise '
                   'infra errors (NEVER_QUARANTINE contract)')
    interests = (ast.Try,)

    SCOPE = ('petastorm_tpu/workers/*', 'petastorm_tpu/readers/*',
             'petastorm_tpu/codecs.py', 'petastorm_tpu/sharedcache.py')
    _INFRA = frozenset({'OSError', 'MemoryError', 'KeyboardInterrupt',
                        'NEVER_QUARANTINE', 'IOError', 'EnvironmentError'})

    def applies_to(self, relpath: str) -> bool:
        return _scoped(relpath, self.SCOPE)

    @classmethod
    def _handler_names(cls, handler: ast.ExceptHandler) -> List[str]:
        t = handler.type
        nodes = t.elts if isinstance(t, ast.Tuple) else ([t] if t else [])
        out = []
        for n in nodes:
            name = dotted_name(n)
            if name:
                out.append(name.rsplit('.', 1)[-1])
        return out

    def visit(self, node: ast.Try, ctx: ModuleContext) -> Iterable[Finding]:
        infra_reraised = False
        for handler in node.handlers:
            names = self._handler_names(handler)
            if 'Exception' not in names:
                if (set(names) & self._INFRA) and _contains_raise(
                        handler.body):
                    infra_reraised = True
                continue
            if _contains_raise(handler.body) or infra_reraised:
                continue
            yield ctx.finding(
                self.name, handler,
                'except Exception swallows OSError/MemoryError here: infra '
                'failures must stay loud (re-raise NEVER_QUARANTINE, or add '
                'a preceding except (OSError, MemoryError): raise) — see '
                'docs/lineage.md NEVER_QUARANTINE contract')


class ThreadLifecycleRule(Rule):
    """R5 — every thread is named ``petastorm-tpu-*`` and, when owned by an
    object, joined by it.

    Incident: PR 4's shutdown-lifecycle hardening — the "no dangling
    ``petastorm-tpu-*`` threads" teardown assertion only works because every
    pipeline thread *is* named ``petastorm-tpu-*``; an unnamed thread is
    invisible to the leak check, the flight recorder's stack dump labels,
    and ``/stacks``. And a thread stored on ``self`` without a joining
    method is exactly the "reader leaks its watchdog on unclean pool death"
    bug PR 4 fixed. The rule checks every ``threading.Thread(...)`` call for
    a ``name='petastorm-tpu-...'`` argument, and — when the thread is
    assigned to a ``self`` attribute — that some method of the same class
    calls ``.join`` on that attribute.
    """

    name = 'thread-lifecycle'
    description = ("threading.Thread needs name='petastorm-tpu-*'; "
                   'self-held threads need a joining method')
    interests = (ast.Call,)

    SCOPE = ('petastorm_tpu/*', 'ci/*')
    _PREFIX = 'petastorm-tpu-'

    def applies_to(self, relpath: str) -> bool:
        return _scoped(relpath, self.SCOPE)

    def _name_ok(self, call: ast.Call) -> Optional[bool]:
        """True/False when decidable from the literal; None = dynamic."""
        for kw in call.keywords:
            if kw.arg != 'name':
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return v.value.startswith(self._PREFIX)
            if isinstance(v, ast.JoinedStr) and v.values:
                first = v.values[0]
                if isinstance(first, ast.Constant) and isinstance(
                        first.value, str):
                    return first.value.startswith(self._PREFIX)
                return None
            if (isinstance(v, ast.Call)
                    and isinstance(v.func, ast.Attribute)
                    and v.func.attr == 'format'
                    and isinstance(v.func.value, ast.Constant)
                    and isinstance(v.func.value.value, str)):
                return v.func.value.value.startswith(self._PREFIX)
            return None
        return False    # no name= at all

    def visit(self, node: ast.Call, ctx: ModuleContext) -> Iterable[Finding]:
        if call_name(node) != 'threading.Thread':
            return
        name_ok = self._name_ok(node)
        if name_ok is False:
            yield ctx.finding(
                self.name, node,
                "threading.Thread without a name='petastorm-tpu-*' kwarg: "
                'unnamed threads are invisible to the thread-leak teardown '
                'check, flight-record stack labels and /stacks')
        yield from self._check_joined(node, ctx)

    def _check_joined(self, node: ast.Call,
                      ctx: ModuleContext) -> Iterable[Finding]:
        parent = ctx.parent(node)
        if not isinstance(parent, ast.Assign) or len(parent.targets) != 1:
            return
        target = parent.targets[0]
        if not (isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == 'self'):
            return
        cls = ctx.enclosing_class(node)
        if cls is None:
            return
        attr = target.attr
        # locals assigned FROM self.<attr> anywhere in the class: joining
        # the alias counts (`thread = self._thread; thread.join()`, the
        # idempotent-stop pattern) — but an unrelated Name-receiver join
        # (`sep.join(parts)`) must not vouch for the thread
        aliases = set()

        def _is_self_attr(expr: ast.AST) -> bool:
            return (isinstance(expr, ast.Attribute)
                    and isinstance(expr.value, ast.Name)
                    and expr.value.id == 'self' and expr.attr == attr)

        for sub in ast.walk(cls):
            if not isinstance(sub, ast.Assign):
                continue
            if _is_self_attr(sub.value):
                aliases.update(t.id for t in sub.targets
                               if isinstance(t, ast.Name))
                continue
            # parallel unpack — the swap form of the idempotent-stop
            # pattern: `thread, self._thread = self._thread, None`
            for tgt in sub.targets:
                if (isinstance(tgt, ast.Tuple)
                        and isinstance(sub.value, ast.Tuple)
                        and len(tgt.elts) == len(sub.value.elts)):
                    aliases.update(
                        t.id for t, v in zip(tgt.elts, sub.value.elts)
                        if isinstance(t, ast.Name) and _is_self_attr(v))
        for sub in ast.walk(cls):
            if (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == 'join'):
                recv = sub.func.value
                if (isinstance(recv, ast.Attribute) and recv.attr == attr):
                    return
                if isinstance(recv, ast.Name) and recv.id in aliases:
                    return
        yield ctx.finding(
            self.name, node,
            'thread stored on self.{} is never join()ed by this class: add '
            'an idempotent stop()/join() (Reader teardown must be able to '
            'call it even after unclean pool death)'.format(attr))


class KillSwitchRule(Rule):
    """R6 — importing a module must create nothing; kill-switched modules
    especially.

    Incident: the PR 5/6 kill-switch acceptance tests
    (``PETASTORM_TPU_LINEAGE=0`` / ``_SHARED_CACHE=0`` "creates no files at
    all") — the runtime half of the contract. The static half checked here:
    a module guarded by a ``PETASTORM_TPU_*`` switch is *imported*
    regardless of the switch, so any file/thread/socket/directory creation
    at import time runs on the disabled path by construction. The rule
    flags such calls in any first-party module's import-time code (module
    or class body); the disabled-path behaviour *inside* functions is
    asserted by the runtime tests.
    """

    name = 'kill-switch'
    description = ('no file/thread/socket/dir creation at import time '
                   '(disabled subsystems must create nothing)')
    interests = (ast.Call,)

    SCOPE = ('petastorm_tpu/*',)
    _CREATORS: Tuple[str, ...] = (
        'open', 'os.fdopen', 'os.makedirs', 'os.mkdir', 'os.mkfifo',
        'threading.Thread', 'socket.socket', 'zmq.Context',
        'tempfile.mkstemp', 'tempfile.mkdtemp', 'tempfile.TemporaryFile',
        'tempfile.NamedTemporaryFile', 'subprocess.Popen', 'subprocess.run',
        'subprocess.check_call', 'subprocess.check_output')

    def applies_to(self, relpath: str) -> bool:
        return _scoped(relpath, self.SCOPE)

    def visit(self, node: ast.Call, ctx: ModuleContext) -> Iterable[Finding]:
        name = call_name(node)
        if name not in self._CREATORS:
            return
        if not ctx.at_import_time(node):
            return
        yield ctx.finding(
            self.name, node,
            '{}() at import time: importing a module must create no files/'
            'threads/sockets — a kill-switched subsystem is imported even '
            'when disabled (move this into the gated runtime path)'
            .format(name))


DEFAULT_RULES = (AtomicPublishRule, MonotonicClockRule, LockDisciplineRule,
                 ExceptionHygieneRule, ThreadLifecycleRule, KillSwitchRule)
