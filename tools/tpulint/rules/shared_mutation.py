"""unguarded-shared-mutation: a lightweight cross-thread race detector.

The host side of the framework is genuinely concurrent — the async
checkpoint writer, the watchdog monitor, the elastic heartbeat/watch
loops, the metrics exporter — and its locking discipline was, until
this rule, hand-audited convention. The contract it machine-checks:

    an instance attribute mutated from thread-target-reachable code
    and also accessed from other methods must have ONE lock held at
    every one of those sites.

Per class the rule uses the Project facts: thread reachability
(transitive from ``threading.Thread(target=...)``, cross-module),
lexically-held ``with self.<lock>:`` regions, and the entry-held
fixpoint (a private helper only ever called under the lock counts as
guarded). Exemptions: ``__init__`` and methods only reachable from it
(no thread exists yet), lock attributes themselves, attributes holding
internally-synchronized objects (queue.Queue, threading.Event, ...),
and ``threading.local`` subclasses.

One finding per (class, attribute), anchored at the first offending
thread-reachable mutation site, so fingerprints stay stable while the
fix lands.

Scope: only modules under the paths in ``SCOPE`` are *reported on*
(observability, checkpointing, serving, elastic, the watchdog) —
reachability is still computed over the whole tree, which is how the
ckpt writer thread is seen reaching the goodput ledger two modules
away.
"""
from __future__ import annotations

import ast
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from ..core import Finding, ModuleInfo
from ..project import ClassInfo, Project, ProjectRule

SCOPE = ("observability/", "distributed/checkpoint/",
         "distributed/watchdog.py", "inference/serving.py",
         "inference/kv_cache.py",
         "inference/router.py", "inference/disagg.py",
         "fleet/elastic/")


def _in_scope(relpath: str) -> bool:
    return any(s in relpath for s in SCOPE)


class SharedMutationRule(ProjectRule):
    id = "unguarded-shared-mutation"
    description = ("attribute mutated from a Thread-target-reachable "
                   "method and accessed elsewhere without a common lock")

    def check_project(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            if not _in_scope(mod.relpath):
                continue
            for ci in project.classes(mod):
                if ci.is_threadlocal:
                    continue
                yield from self._check_class(project, mod, ci)

    def _check_class(self, project: Project, mod: ModuleInfo,
                     ci: ClassInfo) -> Iterator[Finding]:
        init = ci.methods.get("__init__")
        init_only = ci.init_only_methods()
        entry_held = ci.entry_held()

        def excluded(meth: ast.AST) -> bool:
            return meth is init or id(meth) in init_only

        skip_attrs = ci.lock_attrs | ci.threadsafe_attrs
        for attr, sites in sorted(ci.accesses.items()):
            if attr in skip_attrs or attr.startswith("__"):
                continue
            live = [(node, meth, mut) for node, meth, mut in sites
                    if not excluded(meth)]
            t_mut = [(node, meth) for node, meth, mut in live
                     if mut and project.is_thread_reachable(mod, meth)]
            other = [(node, meth) for node, meth, _mut in live
                     if not project.is_thread_reachable(mod, meth)]
            if not t_mut or not other:
                continue
            guards: List[FrozenSet[str]] = []
            for node, meth in t_mut + other:
                guards.append(ci.locks_held_at(node)
                              | entry_held.get(id(meth), frozenset()))
            common = frozenset(ci.lock_attrs)
            for g in guards:
                common &= g
            if common:
                continue
            anchor, anchor_meth = min(
                t_mut, key=lambda s: (getattr(s[0], "lineno", 0),
                                      getattr(s[0], "col_offset", 0)))
            entry = project.thread_entry_of(mod, anchor_meth) or "?"
            others = sorted({mod.qualname_of(m) for _n, m in other})
            locks = sorted(ci.lock_attrs)
            hint = (f"hold self.{locks[0]} at every site"
                    if locks else "add a lock attribute and hold it at "
                                  "every site")
            yield self.finding(
                mod, anchor,
                f"'self.{attr}' is mutated in "
                f"'{mod.qualname_of(anchor_meth)}' (reachable from "
                f"thread target {entry}) and accessed from "
                f"{', '.join(others[:4])} without a common lock — "
                f"data race; {hint}")
