"""Static invariant checker — project-specific AST lints.

The correctness of this codebase rests on conventions that no
general-purpose tool knows about: the PR 5/6 gang determinism contract,
owner-side write legs replaying through ``*_local`` entry points (PR 6
shipped with ``import_values`` silently bypassing gang replay — a bug
class only a dryrun caught), jit purity, donated-buffer non-reuse, and
lock discipline across ten-plus mutex-holding modules. Engler et al.'s
"deviant behavior" observation applies directly: each convention is a
mechanically checkable pattern, so this module checks them on every CI
run instead of relying on review memory.

Rules (ids are what ``# check: disable=<rule>`` names):

* ``lock-discipline`` — no blocking calls (future ``.result()``,
  ``block_until_ready``, socket/HTTP I/O, ``time.sleep``, event waits,
  thread joins, device transfers) inside a ``with <lock>:`` body; and
  no call to a same-class method that re-acquires the lock already
  held (static self-deadlock — the dynamic detector's
  ``LockOrderError`` shape, caught at lint time).
* ``lock-wrapper`` — module-level locks, and every lock in the
  instrumented modules (dispatch engine, pipeline, stager, plan cache,
  multihost lifecycle), must be ``analysis.locks.OrderedLock`` so the
  lock graph sees them.
* ``gang-routing`` — inside a cluster owner-routing loop
  (``for node in …shard_nodes(…)``), fragment/field mutations must go
  through a ``self.*_local`` gang-replicating entry point or the
  internal client — never directly (the PR 6 ``import_values`` bug).
* ``dispatch-bypass`` — executor entry points must consult the
  engine-eligibility predicate; code outside the engine must not call
  ``._execute`` directly.
* ``jit-purity`` — ``@jax.jit`` bodies must not touch wall-clock, host
  RNG, metrics, locks, or print.
* ``donation-safety`` — an operand passed to a donated-argnums kernel
  (``zeros_like_donated``) is dead after the call; any later read of
  that name is flagged.
* ``metrics-sync`` — every metric name passed to
  ``metrics.count/gauge/observe`` (literal or ``metrics.CONSTANT``)
  exists in the ``utils/metrics.py`` registry — the docs-sync test
  extended to code sites.
* ``fault-spec`` — string-literal fault schedules handed to the three
  injector families (``install_storage_faults`` /
  ``install_device_faults`` / multihost ``FaultSpec.parse`` /
  ``maybe_faulty``) parse under that family's knob grammar. A typo'd
  knob in a chaos schedule otherwise surfaces as a ValueError at the
  worst time: inside the fault window it was supposed to open.
* ``env-config`` — no ``os.environ`` / ``os.getenv`` under
  ``pilosa_tpu/executor/``, ``pilosa_tpu/plan/`` or
  ``pilosa_tpu/server/pipeline.py``: the hot path is configured by
  constructor arguments the server fills from its config
  (``PILOSA_TPU_*``, read in ``server/config.py``), never by a second
  set of switches read where they act.

Suppressions: ``# check: disable=<rule>[,<rule>…] (<reason>)`` on the
flagged line or alone on the line above. ``--strict`` additionally
requires every suppression to carry a reason and to name known rules.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Callable, Optional

RULES = (
    "lock-discipline",
    "lock-wrapper",
    "gang-routing",
    "dispatch-bypass",
    "jit-purity",
    "donation-safety",
    "metrics-sync",
    "fault-spec",
    "env-config",
)

# modules migrated to OrderedLock — the five lock-heaviest (ISSUE 9);
# lock-wrapper keeps them migrated
INSTRUMENTED_MODULES = (
    "executor/dispatch.py",
    "server/pipeline.py",
    "executor/stager.py",
    "plan/cache.py",
    "parallel/multihost.py",
)

# fragment/field state mutators that must ride a *_local entry point on
# an owner-side cluster leg (gang replication, parallel/federation.py)
_MUTATORS = frozenset(
    {
        "import_bits",
        "import_values",
        "import_value",
        "bulk_import",
        "import_block_pairs",
        "set_bit",
        "clear_bit",
    }
)

# call names that block (or are unbounded I/O) — forbidden under a lock
_BLOCKING_ATTR_CALLS = frozenset(
    {
        "result",  # concurrent.futures / dispatch item futures
        "block_until_ready",
        "urlopen",
        "getresponse",
        "create_connection",
        "recv",
        "recv_frame",
        "recv_message",
        "sendall",
        "device_put",  # host->device transfer: real I/O
    }
)

_SUPPRESS_RE = re.compile(
    r"#\s*check:\s*disable=([A-Za-z0-9_,-]+)\s*(?:\(([^)]*)\))?"
)


@dataclass
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"


# -- helpers ----------------------------------------------------------------


def _dotted(node: ast.AST) -> str:
    """'a.b.c' for Name/Attribute chains, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif parts:
        parts.append("?")
    return ".".join(reversed(parts))


_LOCKISH_RE = re.compile(r"(?:^|_)(?:mu|mutex|lock|cond|cv)(?:$|_)|lock$|^mu$")
_CONDISH_RE = re.compile(r"(?:^|_)(?:cond|cv)(?:$|_)")
_EVENTISH_RE = re.compile(r"(?:^|_)(?:event|ev|done|ready)(?:$|_)")
_THREADISH_RE = re.compile(r"(?:^|_)(?:thread|threads|loop|proc|worker)s?(?:$|_)")


def _last_seg(dotted: str) -> str:
    return dotted.rsplit(".", 1)[-1] if dotted else ""


def _is_lockish(expr: ast.AST) -> bool:
    return bool(_LOCKISH_RE.search(_last_seg(_dotted(expr))))


def _walk_no_nested_funcs(node: ast.AST):
    """Yield descendants without descending into nested function /
    class definitions (their bodies run at some other time, under some
    other lock state)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(
            n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)
        ):
            stack.extend(ast.iter_child_nodes(n))


def _is_jit_decorated(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        d = _dotted(dec)
        if d in ("jax.jit", "jit"):
            return True
        if isinstance(dec, ast.Call):
            f = _dotted(dec.func)
            if f in ("jax.jit", "jit"):
                return True
            if f in ("functools.partial", "partial") and dec.args:
                if _dotted(dec.args[0]) in ("jax.jit", "jit"):
                    return True
    return False


# -- rule: lock-discipline ---------------------------------------------------


def _blocking_reason(call: ast.Call) -> Optional[str]:
    f = call.func
    d = _dotted(f)
    if d in ("time.sleep",):
        return "time.sleep"
    if isinstance(f, ast.Attribute):
        recv = _last_seg(_dotted(f.value))
        if f.attr in _BLOCKING_ATTR_CALLS:
            return f".{f.attr}()"
        if f.attr == "wait" and _EVENTISH_RE.search(recv) and not _CONDISH_RE.search(recv):
            # Event.wait does NOT release the enclosing lock (unlike
            # Condition.wait) — a waiter under a lock starves whoever
            # must set the event
            return f"{recv}.wait()"
        if f.attr == "join" and _THREADISH_RE.search(recv):
            return f"{recv}.join()"
    return None


def _methods_acquiring(cls: ast.ClassDef) -> dict[str, set[str]]:
    """method name -> set of self.<attr> lock names it acquires (via
    ``with self.<attr>`` or ``self.<attr>.acquire()``)."""
    out: dict[str, set[str]] = {}
    for item in cls.body:
        if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        acquired: set[str] = set()
        for n in ast.walk(item):
            if isinstance(n, ast.With):
                for w in n.items:
                    d = _dotted(w.context_expr)
                    if d.startswith("self.") and _is_lockish(w.context_expr):
                        acquired.add(d)
            elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                if n.func.attr == "acquire":
                    d = _dotted(n.func.value)
                    if d.startswith("self."):
                        acquired.add(d)
        if acquired:
            out[item.name] = acquired
    return out


def _reentrant_lock_attrs(cls: ast.ClassDef) -> set[str]:
    """self.<attr> names assigned an RLock (or reentrant OrderedLock)
    anywhere in the class — self-call nesting on those is legal."""
    out: set[str] = set()
    for n in ast.walk(cls):
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
            f = _dotted(n.value.func)
            reent = f.endswith("RLock") or (
                f.endswith("OrderedLock")
                and any(
                    kw.arg == "reentrant"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value
                    for kw in n.value.keywords
                )
            )
            if reent:
                for t in n.targets:
                    d = _dotted(t)
                    if d.startswith("self."):
                        out.add(d)
    return out


def rule_lock_discipline(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    findings: list[Finding] = []

    def scan_with(w: ast.With, lock_name: str, acquirers, reentrant) -> None:
        for n in _walk_no_nested_funcs(w):
            if not isinstance(n, ast.Call):
                continue
            why = _blocking_reason(n)
            if why is not None:
                findings.append(
                    ctx.finding(
                        n.lineno,
                        "lock-discipline",
                        f"blocking call {why} inside `with {lock_name}:` — "
                        "move the wait/IO outside the critical section",
                    )
                )
            # static self-deadlock: self.m() where m re-acquires this lock
            d = _dotted(n.func)
            if (
                d.startswith("self.")
                and "." not in d[5:]
                and lock_name.startswith("self.")
                and lock_name not in reentrant
            ):
                m = d[5:]
                if lock_name in acquirers.get(m, ()):
                    findings.append(
                        ctx.finding(
                            n.lineno,
                            "lock-discipline",
                            f"self.{m}() re-acquires {lock_name} already "
                            "held here (self-deadlock on a non-reentrant "
                            "lock)",
                        )
                    )

    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        acquirers = _methods_acquiring(cls)
        reentrant = _reentrant_lock_attrs(cls)
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for n in ast.walk(fn):
                if isinstance(n, ast.With):
                    for w in n.items:
                        if _is_lockish(w.context_expr):
                            scan_with(n, _dotted(w.context_expr), acquirers, reentrant)
    # module/function-level (non-class) with-lock bodies: blocking-call
    # scan only (no self-deadlock analysis without a class)
    class_lines: set[int] = set()
    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        end = getattr(cls, "end_lineno", cls.lineno)
        class_lines.update(range(cls.lineno, end + 1))
    for n in ast.walk(tree):
        if isinstance(n, ast.With) and n.lineno not in class_lines:
            for w in n.items:
                if _is_lockish(w.context_expr):
                    scan_with(n, _dotted(w.context_expr), {}, set())
    # dedup (a with nested in a with over the same lines)
    seen: set[tuple] = set()
    out = []
    for f in findings:
        k = (f.line, f.rule, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


# -- rule: lock-wrapper ------------------------------------------------------


def rule_lock_wrapper(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    findings: list[Finding] = []
    instrumented = ctx.relpath.replace(os.sep, "/").endswith(INSTRUMENTED_MODULES)

    def bare_lock(call: ast.Call) -> Optional[str]:
        d = _dotted(call.func)
        if d in ("threading.Lock", "threading.RLock"):
            return d
        if d == "threading.Condition" and not call.args:
            # Condition() conjures a hidden bare lock
            return "threading.Condition()"
        return None

    # module-level statements (assignments at module scope)
    for stmt in tree.body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Call) and not isinstance(
                stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                kind = bare_lock(n)
                if kind is not None:
                    findings.append(
                        ctx.finding(
                            n.lineno,
                            "lock-wrapper",
                            f"module-level {kind} — create it via "
                            "analysis.locks.OrderedLock so the lock graph "
                            "sees it",
                        )
                    )
    if instrumented:
        for n in ast.walk(tree):
            if isinstance(n, ast.Call):
                kind = bare_lock(n)
                if kind is not None and not any(
                    f.line == n.lineno for f in findings
                ):
                    findings.append(
                        ctx.finding(
                            n.lineno,
                            "lock-wrapper",
                            f"{kind} in an instrumented module — use "
                            "analysis.locks.OrderedLock (lock-order "
                            "detection is migrated here)",
                        )
                    )
    return findings


# -- rule: gang-routing ------------------------------------------------------


def rule_gang_routing(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    findings: list[Finding] = []

    def contains_shard_nodes(expr: ast.AST) -> bool:
        for n in ast.walk(expr):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                if n.func.attr == "shard_nodes":
                    return True
        return False

    for loop in [n for n in ast.walk(tree) if isinstance(n, ast.For)]:
        if not contains_shard_nodes(loop.iter):
            continue
        for n in _walk_no_nested_funcs(loop):
            if not (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)):
                continue
            if n.func.attr not in _MUTATORS:
                continue
            recv = _dotted(n.func.value)
            if recv == "self":
                continue  # self.import_*_local-style entry points
            if "client" in recv.split("."):
                continue  # remote leg via the internal HTTP client
            findings.append(
                ctx.finding(
                    n.lineno,
                    "gang-routing",
                    f"owner-side write leg calls {recv}.{n.func.attr}() "
                    "directly inside a shard_nodes() routing loop — on a "
                    "federated gang leader this bypasses gang replay "
                    "(followers diverge; the PR 6 import_values bug). "
                    f"Route through self.{n.func.attr}_local(...)",
                )
            )
    return findings


# -- rule: dispatch-bypass ---------------------------------------------------

# modules allowed to call Executor._execute directly: the executor
# itself and the engine that IS the dispatch loop
_EXECUTE_WHITELIST = ("executor/executor.py", "executor/dispatch.py")


def rule_dispatch_bypass(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    findings: list[Finding] = []
    rel = ctx.relpath.replace(os.sep, "/")
    if not rel.endswith(_EXECUTE_WHITELIST):
        for n in ast.walk(tree):
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "_execute"
                and _dotted(n.func.value) != "self"
            ):
                findings.append(
                    ctx.finding(
                        n.lineno,
                        "dispatch-bypass",
                        "direct ._execute() call bypasses Executor.execute "
                        "— new entry points must go through execute() so "
                        "the engine-eligibility predicate "
                        "(gang/cluster/remote/serial/write/re-entrant) is "
                        "consulted",
                    )
                )
    if rel.endswith("executor/executor.py") or ctx.fixture_role == "executor":
        for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
            if cls.name != "Executor":
                continue
            for fn in cls.body:
                if not isinstance(fn, ast.FunctionDef):
                    continue
                if not fn.name.startswith("execute"):
                    continue
                body_names = {
                    x.attr
                    for x in ast.walk(fn)
                    if isinstance(x, ast.Attribute)
                }
                if not ({"_engine_eligible", "dispatch_engine"} & body_names):
                    findings.append(
                        ctx.finding(
                            fn.lineno,
                            "dispatch-bypass",
                            f"executor entry point {fn.name}() never "
                            "consults the engine-eligibility predicate "
                            "(_engine_eligible / dispatch_engine) — "
                            "eligible local reads must route through the "
                            "continuous-batching engine",
                        )
                    )
    return findings


# -- rule: jit-purity --------------------------------------------------------

_IMPURE_CALLS = {
    "time.time": "wall-clock",
    "time.monotonic": "wall-clock",
    "time.perf_counter": "wall-clock",
    "time.sleep": "blocking sleep",
    "datetime.now": "wall-clock",
    "print": "host I/O (use jax.debug.print)",
}


def rule_jit_purity(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    findings: list[Finding] = []
    for fn in [
        n
        for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef) and _is_jit_decorated(n)
    ]:
        for n in ast.walk(fn):
            if isinstance(n, ast.Call):
                d = _dotted(n.func)
                why = _IMPURE_CALLS.get(d)
                if why is None and d.endswith(".now") and "datetime" in d:
                    why = "wall-clock"
                if why is not None:
                    findings.append(
                        ctx.finding(
                            n.lineno,
                            "jit-purity",
                            f"@jax.jit body calls {d}() — {why}; traced "
                            "once at compile time, then baked into the "
                            "kernel forever",
                        )
                    )
            d = _dotted(n) if isinstance(n, (ast.Attribute, ast.Name)) else ""
            if d.startswith(("random.", "np.random.", "numpy.random.")):
                findings.append(
                    ctx.finding(
                        n.lineno,
                        "jit-purity",
                        f"@jax.jit body touches host RNG {d} — use "
                        "jax.random with an explicit key",
                    )
                )
            elif d.startswith(("metrics.", "REGISTRY.")) or d.startswith(
                "threading."
            ):
                findings.append(
                    ctx.finding(
                        n.lineno,
                        "jit-purity",
                        f"@jax.jit body references {d} — metrics/locks are "
                        "host side effects; they run at trace time only",
                    )
                )
            if isinstance(n, ast.With):
                for w in n.items:
                    if _is_lockish(w.context_expr):
                        findings.append(
                            ctx.finding(
                                n.lineno,
                                "jit-purity",
                                f"@jax.jit body takes lock "
                                f"{_dotted(w.context_expr)} — host side "
                                "effect, runs at trace time only",
                            )
                        )
    # dedup Attribute-chain double reports (np.random.default_rng hits
    # both the Attribute and its parent)
    seen: set[tuple] = set()
    out = []
    for f in findings:
        k = (f.line, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


# -- rule: donation-safety ---------------------------------------------------


def rule_donation_safety(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    findings: list[Finding] = []
    for fn in [
        n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]:
        donations: list[tuple[int, str]] = []  # (line, operand name)
        rebinds: dict[str, list[int]] = {}
        loads: dict[str, list[int]] = {}
        for n in _walk_no_nested_funcs(fn):
            if isinstance(n, ast.Call):
                f = n.func
                name = f.attr if isinstance(f, ast.Attribute) else (
                    f.id if isinstance(f, ast.Name) else ""
                )
                if name in ("zeros_like_donated", "_zeros_like_donated"):
                    for a in n.args:
                        if isinstance(a, ast.Name):
                            donations.append((n.lineno, a.id))
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, ast.Store):
                    rebinds.setdefault(n.id, []).append(n.lineno)
                elif isinstance(n.ctx, ast.Load):
                    loads.setdefault(n.id, []).append(n.lineno)
        for dline, var in donations:
            for lline in loads.get(var, ()):
                if lline <= dline:
                    continue
                # a rebind between donation and load makes the name a
                # fresh value — the donated buffer is no longer reachable
                if any(dline <= r <= lline for r in rebinds.get(var, ())):
                    continue
                findings.append(
                    ctx.finding(
                        lline,
                        "donation-safety",
                        f"{var!r} read after being donated to a "
                        f"donate_argnums kernel at line {dline} — the "
                        "buffer is deleted on TPU/GPU; this raises (or "
                        "silently reads freed memory) off-CPU",
                    )
                )
    return findings


# -- rule: metrics-sync ------------------------------------------------------


def _metric_registry():
    from pilosa_tpu.utils import metrics as m

    return m


def rule_metrics_sync(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    m = _metric_registry()
    if ctx.relpath.replace(os.sep, "/").endswith("utils/metrics.py"):
        return []  # the registry itself
    findings: list[Finding] = []
    for n in ast.walk(tree):
        if not (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)):
            continue
        if n.func.attr not in ("count", "gauge", "observe"):
            continue
        recv = _last_seg(_dotted(n.func.value))
        if recv not in ("metrics", "REGISTRY"):
            continue
        if not n.args:
            continue
        arg = n.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            if arg.value not in m.METRICS:
                findings.append(
                    ctx.finding(
                        arg.lineno,
                        "metrics-sync",
                        f"metric name {arg.value!r} is not declared in the "
                        "utils/metrics.py registry — add it there (and to "
                        "the docs table) or fix the name",
                    )
                )
        elif isinstance(arg, ast.Attribute) and _dotted(arg.value) == "metrics":
            const = arg.attr
            val = getattr(m, const, None)
            if not isinstance(val, str) or val not in m.METRICS:
                findings.append(
                    ctx.finding(
                        arg.lineno,
                        "metrics-sync",
                        f"metrics.{const} does not resolve to a registered "
                        "metric name in utils/metrics.py",
                    )
                )
    return findings


# -- rule: fault-spec --------------------------------------------------------

# knob grammar per injector family: knob -> value kind. Kept LOCAL (no
# core/fragment, utils/chaos, or multihost import — lint also runs in
# the no-jax check job); tests parse these same grammars with the real
# spec classes to keep both directions honest.
_FAULT_KNOBS: dict[str, dict[str, str]] = {
    "storage": {
        "fsync_fail_every": "int",
        "torn_at": "int",
        "enospc_after": "int",
        "corrupt_at": "int",
        "bitrot": "int",
        "snapshot_kill": "enum:pre|post",
    },
    "device": {
        "oom_every": "int",
        "stall_every": "int",
        "stall_s": "float",
        "poison_every": "int",
        "after": "int",
    },
    "distributed": {
        "drop_every": "int",
        "dup_every": "int",
        "delay": "float",
        "after": "int",
    },
}

# call-site shape -> (family, positional index of the spec argument)
_FAULT_CALLS: dict[str, tuple[str, int]] = {
    "install_storage_faults": ("storage", 0),
    "install_device_faults": ("device", 0),
    "StorageFaultSpec.parse": ("storage", 0),
    "DeviceFaultSpec.parse": ("device", 0),
    "FaultSpec.parse": ("distributed", 0),
    "maybe_faulty": ("distributed", 1),
}


def _fault_spec_errors(family: str, text: str) -> list[str]:
    knobs = _FAULT_KNOBS[family]
    errors: list[str] = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        key, sep, value = part.partition("=")
        key = key.strip()
        if key not in knobs:
            errors.append(
                f"unknown {family} fault knob {key!r} "
                f"(known: {', '.join(sorted(knobs))})"
            )
            continue
        if not sep:
            errors.append(f"{family} fault knob {key!r} missing '=value'")
            continue
        kind = knobs[key]
        if kind.startswith("enum:"):
            allowed = kind[len("enum:"):].split("|")
            if value.strip() not in allowed:
                errors.append(
                    f"{family} fault knob {key!r} must be one of "
                    f"{' | '.join(allowed)}, got {value.strip()!r}"
                )
            continue
        try:
            (int if kind == "int" else float)(value.strip())
        except ValueError:
            errors.append(
                f"{family} fault knob {key!r} needs "
                f"{'an integer' if kind == 'int' else 'a number'}, "
                f"got {value.strip()!r}"
            )
    return errors


def rule_fault_spec(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    findings: list[Finding] = []
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        d = _dotted(n.func)
        name = _last_seg(d)
        hit = _FAULT_CALLS.get(name)
        if hit is None and "." in d:
            # Klass.parse form — match on the last two segments
            hit = _FAULT_CALLS.get(".".join(d.split(".")[-2:]))
        if hit is None:
            continue
        family, argidx = hit
        if len(n.args) <= argidx:
            continue
        arg = n.args[argidx]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            continue  # dynamic specs are the parser's problem at runtime
        for msg in _fault_spec_errors(family, arg.value):
            findings.append(ctx.finding(arg.lineno, "fault-spec", msg))
    return findings


# -- rule: env-config --------------------------------------------------------

_ENV_FREE = ("pilosa_tpu/executor/", "pilosa_tpu/plan/", "pilosa_tpu/server/pipeline.py")


def rule_env_config(tree: ast.Module, ctx: "FileContext") -> list[Finding]:
    rel = ctx.relpath.replace(os.sep, "/")
    if not any(p in rel for p in _ENV_FREE):
        return []
    findings: list[Finding] = []
    for n in ast.walk(tree):
        if isinstance(n, ast.Attribute):
            hit = _dotted(n) in ("os.environ", "os.getenv")
        elif isinstance(n, ast.ImportFrom):
            hit = n.module == "os" and any(
                a.name in ("environ", "getenv") for a in n.names
            )
        else:
            continue
        if hit:
            findings.append(
                ctx.finding(
                    n.lineno,
                    "env-config",
                    "environment read on the hot path — take the value as "
                    "a constructor argument; the server fills it from its "
                    "config (server/config.py reads PILOSA_TPU_*)",
                )
            )
    return findings


_RULE_FNS: dict[str, Callable] = {
    "lock-discipline": rule_lock_discipline,
    "lock-wrapper": rule_lock_wrapper,
    "gang-routing": rule_gang_routing,
    "dispatch-bypass": rule_dispatch_bypass,
    "jit-purity": rule_jit_purity,
    "donation-safety": rule_donation_safety,
    "metrics-sync": rule_metrics_sync,
    "fault-spec": rule_fault_spec,
    "env-config": rule_env_config,
}


# -- engine -----------------------------------------------------------------


class FileContext:
    def __init__(self, relpath: str, fixture_role: str = "") -> None:
        self.relpath = relpath
        # tests feed fixture snippets with a role hint ("executor") so
        # path-scoped rules can be exercised on synthetic files
        self.fixture_role = fixture_role

    def finding(self, line: int, rule: str, message: str) -> Finding:
        return Finding(self.relpath, line, rule, message)


class Suppressions:
    """``# check: disable=<rule>[,<rule>] (<reason>)`` markers, applying
    to their own line and (for standalone comment lines) the next
    line."""

    def __init__(self, source: str) -> None:
        self.by_line: dict[int, set[str]] = {}
        self.entries: list[tuple[int, tuple[str, ...], str]] = []
        for i, text in enumerate(source.splitlines(), 1):
            mobj = _SUPPRESS_RE.search(text)
            if mobj is None:
                continue
            rules = tuple(
                r.strip() for r in mobj.group(1).split(",") if r.strip()
            )
            reason = (mobj.group(2) or "").strip()
            self.entries.append((i, rules, reason))
            target = i
            if text.lstrip().startswith("#"):
                target = i + 1  # standalone comment guards the next line
            for line in (i, target):
                self.by_line.setdefault(line, set()).update(rules)

    def covers(self, line: int, rule: str) -> bool:
        return rule in self.by_line.get(line, ())


def check_source(
    source: str,
    relpath: str,
    rules: Optional[tuple] = None,
    strict: bool = False,
    fixture_role: str = "",
) -> list[Finding]:
    """Run the rule set over one file's source. Returns surviving
    findings (suppressed ones removed; strict adds suppression-hygiene
    findings)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(relpath, e.lineno or 0, "parse", f"syntax error: {e.msg}")]
    ctx = FileContext(relpath, fixture_role=fixture_role)
    sup = Suppressions(source)
    findings: list[Finding] = []
    for rule in rules or RULES:
        findings.extend(_RULE_FNS[rule](tree, ctx))
    findings = [f for f in findings if not sup.covers(f.line, f.rule)]
    if strict:
        for line, names, reason in sup.entries:
            for r in names:
                if r not in RULES:
                    findings.append(
                        Finding(
                            relpath,
                            line,
                            "suppression",
                            f"unknown rule {r!r} in disable marker",
                        )
                    )
            if not reason:
                findings.append(
                    Finding(
                        relpath,
                        line,
                        "suppression",
                        "suppression without a reason — write "
                        "`# check: disable=<rule> (<why this is safe>)`",
                    )
                )
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


_SKIP_DIRS = {
    "__pycache__",
    ".git",
    "native",
    ".claude",
    "node_modules",
}


def iter_py_files(roots: list[str]) -> list[str]:
    out: list[str] = []
    for root in roots:
        if os.path.isfile(root):
            out.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in sorted(dirnames) if d not in _SKIP_DIRS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def repo_root() -> str:
    """The tree `pilosa_tpu check` (no args) checks: the repo when the
    package sits inside one (tests/ alongside), else the package dir."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    parent = os.path.dirname(pkg)
    if os.path.isdir(os.path.join(parent, "tests")) and os.path.isdir(
        os.path.join(parent, "pilosa_tpu")
    ):
        return parent
    return pkg


def check_paths(
    paths: Optional[list[str]] = None, strict: bool = False
) -> list[Finding]:
    """Run every rule over the given files/dirs (default: the repo)."""
    if not paths:
        paths = [repo_root()]
    base = repo_root()
    findings: list[Finding] = []
    for path in iter_py_files(paths):
        rel = os.path.relpath(path, base)
        if rel.startswith(".."):
            rel = path
        with open(path, encoding="utf-8") as f:
            src = f.read()
        findings.extend(check_source(src, rel, strict=strict))
    return findings
