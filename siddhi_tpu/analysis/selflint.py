"""Self-lint: an AST checker over siddhi_tpu's OWN source.

Two bug classes keep coming back in review rounds, and both are
mechanical enough to gate in CI (`scripts/smoke.sh` runs
``python -m siddhi_tpu.analysis --self``):

SL01 — silent demotion.  In a plan-lowering file, an ``except`` handler
  that catches a broad or lowering-related exception and neither
  re-raises nor records a ``Demotion`` (a call named ``demote`` /
  ``record_demotion``) is exactly the bug class PR 5 shipped: a whole
  query class quietly losing its device path.  A legitimate swallow
  (best-effort metrics sampling, probes) must say so on the ``except``
  line with ``# lint: allow-swallow (<why>)`` — the why is mandatory
  culture, not syntax.

SL02 — unguarded shared-counter mutation (the PR-9 lock-discipline
  class).  In a class that owns a ``threading.Lock``/``RLock``
  attribute, an augmented assignment to a counter-named ``self``
  attribute outside a ``with self.<lock>:`` block is a data race with
  whatever thread scrapes or also bumps it.  Methods whose NAME carries
  the convention that the caller holds the lock (``*_locked``) are
  exempt, as is ``# lint: unlocked-ok (<why>)`` on the statement line.

The linter is deliberately lexical: it proves nothing, it just makes
the two recurring mistakes impossible to commit *silently*.
"""
from __future__ import annotations

import ast as pyast
import os
import re
from typing import Optional

from .rules import Finding
from .walker import (class_lock_attrs, has_pragma,  # noqa: F401 (re-export)
                     iter_package, package_root)

# files whose except-handlers are on a plan-lowering path (SL01 scope)
LOWERING_FILES = (
    "core/build.py",
    "core/planner.py",
    "core/partition.py",
    "core/pattern_plan.py",
    "core/window_device.py",
    "core/join_device.py",
    "core/multi_query.py",
    "core/nfa_device.py",
    "core/nfa_parallel.py",
)

# exception type names whose swallow demotes a plan (broad catches plus
# the lowering-unsupported family)
_CHECKED_TYPES = {
    "Exception", "BaseException",
    "DeviceNFAUnsupported", "DeviceWindowUnsupported",
    "DeviceJoinUnsupported", "ParallelUnsupported",
    "PlanError", "ExprError", "TableError",
}

_DEMOTE_CALLS = {"demote", "record_demotion"}

_COUNTER_RE = re.compile(
    r"(count|total|hits|misses|dropped|stored|shed|evict|frames|events"
    r"|bytes|errors|retri|publish|fail|credit|pending|admitted|blocked"
    r"|corrupt|demotion)", re.I)

_SL01_PRAGMA = "lint: allow-swallow"
_SL02_PRAGMA = "lint: unlocked-ok"


def _sl(rule_id: str, message: str, subject: str) -> Finding:
    return Finding(rule_id, "error", message, subject)


# shared pragma helper (analysis/walker.py)
_has_pragma = has_pragma


def _etype_names(node) -> set:
    if node is None:                  # bare `except:` — maximally broad
        return {"BaseException"}
    if isinstance(node, pyast.Tuple):
        return set().union(*(_etype_names(e) for e in node.elts))
    if isinstance(node, pyast.Name):
        return {node.id}
    if isinstance(node, pyast.Attribute):
        return {node.attr}
    return set()


def _body_walk(handler: pyast.ExceptHandler):
    for stmt in handler.body:
        yield from pyast.walk(stmt)


def _records_demotion(handler: pyast.ExceptHandler) -> bool:
    for n in _body_walk(handler):
        if isinstance(n, pyast.Call):
            f = n.func
            name = f.attr if isinstance(f, pyast.Attribute) else \
                f.id if isinstance(f, pyast.Name) else None
            if name in _DEMOTE_CALLS:
                return True
    return False


def lint_sl01(tree, lines: list, relpath: str) -> list:
    out: list = []
    for node in pyast.walk(tree):
        if not isinstance(node, pyast.ExceptHandler):
            continue
        if not (_etype_names(node.type) & _CHECKED_TYPES):
            continue
        if _has_pragma(lines, node.lineno, _SL01_PRAGMA):
            continue
        if any(isinstance(n, pyast.Raise) for n in _body_walk(node)):
            continue
        if _records_demotion(node):
            continue
        out.append(_sl(
            "SL01",
            f"except handler swallows a lowering exception without "
            f"re-raising or recording a Demotion "
            f"(rt.placement.demote(...)); if the swallow is legitimate, "
            f"annotate the except line with "
            f"`# {_SL01_PRAGMA} (<why>)`",
            f"{relpath}:{node.lineno}"))
    return out


# ---------------------------------------------------------------------------
# SL02: unguarded counter mutation in lock-owning classes
# ---------------------------------------------------------------------------

def _lock_attrs(cls: pyast.ClassDef) -> set:
    """self attributes assigned a lock anywhere in the class body —
    raw threading.Lock()/RLock() AND the engine's named factories
    (utils.locks new_lock/new_rlock), via the shared walker."""
    return {attr for attr, (kind, _node) in class_lock_attrs(cls).items()
            if kind in ("lock", "rlock")}


def _with_guards(stack: list, locks: set) -> bool:
    """Is any enclosing `with` statement entered on one of the lock
    attributes (`with self._lock:` / `with self._lock, other:`)?"""
    for node in stack:
        if not isinstance(node, pyast.With):
            continue
        for item in node.items:
            e = item.context_expr
            if isinstance(e, pyast.Call):       # e.g. self._lock.acquire()?
                e = e.func
            if isinstance(e, pyast.Attribute) and \
                    isinstance(e.value, pyast.Name) and \
                    e.value.id == "self" and e.attr in locks:
                return True
    return False


def lint_sl02(tree, lines: list, relpath: str) -> list:
    out: list = []

    def visit(node, stack, cls, locks, fn):
        if isinstance(node, pyast.ClassDef):
            cls, locks, fn = node, _lock_attrs(node), None
        elif isinstance(node, (pyast.FunctionDef, pyast.AsyncFunctionDef)):
            fn = node
        elif (isinstance(node, pyast.AugAssign) and cls is not None
                and locks and fn is not None
                and isinstance(node.target, pyast.Attribute)
                and isinstance(node.target.value, pyast.Name)
                and node.target.value.id == "self"
                and _COUNTER_RE.search(node.target.attr)
                and "locked" not in fn.name
                and not _with_guards(stack, locks)
                and not _has_pragma(lines, node.lineno, _SL02_PRAGMA)):
            out.append(_sl(
                "SL02",
                f"augmented assignment to `self.{node.target.attr}` in "
                f"lock-owning class {cls.name!r} outside `with "
                f"self.<lock>:` — shared-counter mutation races the "
                f"scraper/other writers (PR-9 class); guard it, rename "
                f"the method `*_locked`, or annotate "
                f"`# {_SL02_PRAGMA} (<why>)`",
                f"{relpath}:{node.lineno}"))
        stack = stack + [node]
        for child in pyast.iter_child_nodes(node):
            visit(child, stack, cls, locks, fn)

    visit(tree, [], None, set(), None)
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def lint_source(text: str, relpath: str) -> list:
    """Lint one module's source.  `relpath` is the package-relative
    POSIX path (e.g. ``core/build.py``) — it decides SL01 scope."""
    try:
        tree = pyast.parse(text)
    except SyntaxError as e:
        return [_sl("SL00", f"does not parse: {e}", relpath)]
    lines = text.splitlines()
    out: list = []
    if relpath.replace(os.sep, "/") in LOWERING_FILES:
        out += lint_sl01(tree, lines, relpath)
    out += lint_sl02(tree, lines, relpath)
    return out


def lint_package(root: Optional[str] = None) -> list:
    """Lint every .py under the siddhi_tpu package (the CI gate)."""
    out: list = []
    for rel, text in iter_package(root):
        out += lint_source(text, rel)
    return out
