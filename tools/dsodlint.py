#!/usr/bin/env python
"""dsodlint — AST invariant linter for the codebase's own hard-won
rules (docs/STATIC_ANALYSIS.md).

Thirteen PRs accreted invariants that lived only in CHANGES.md and
reviewers' heads.  This tool makes five of them machine-checked on
every ``tools/t1.sh`` run (pure-CPU, no imports of the checked code —
everything is ``ast`` over source text):

- ``traced-purity`` — no host synchronization or environment reads
  inside traced code: ``jax.device_get`` / ``.item()`` / ``float()`` /
  ``np.asarray`` / ``print`` / ``time.time`` / ``os.environ`` (and
  ``envvars.read``) calls reachable from any function passed to
  ``jit`` / ``shard_map`` / ``lax.scan`` / ``pallas_call`` — the PR-4
  one-device_get-per-chunk contract.  Env must be read at
  program-BUILD time; host syncs belong to the sanctioned flush seams
  (``TRACED_SEAMS`` below).
- ``lock-discipline`` — for classes in ``serve/`` / ``utils/`` that
  own a ``threading.Lock``/``RLock`` (or spawn threads), a ``self.*``
  attribute written both from a thread-entry call graph (Thread
  targets, executor submits, background loops) and elsewhere — or
  written locked in one place and unlocked in another — must only be
  mutated under ``with self._lock`` (the PR-7 check-then-put and PR-8
  inflight-gauge bug class).
- ``env-coherence`` — every ``DSOD_*`` env read goes through
  ``utils/envvars.py::read`` and every name read is registered there.
- ``metrics-coherence`` — every ``dsod_*`` metric-family literal in
  source exists in ``tools/metrics_inventory.json`` and every
  inventory family is constructible from source literals (the static
  complement of the runtime ``tools/metrics_lint.py``).
- ``accounting-seams`` — the terminal counters
  (served/shed/expired/errors/submitted) may only move inside their
  declared booking seams (``BOOKING_SEAMS`` below), so the
  ``served + shed + expired + errors == submitted`` identity has
  exactly one owner per tier.

Waivers: ``# dsodlint: disable=<check>[,<check>] -- <reason>`` on the
finding's line, the line above, or the enclosing ``def`` line (scope
waiver).  A pragma without a reason is itself a finding.

Baseline discipline (the hlo_guard/metrics_lint conventions): one JSON
summary line, findings diffed against the checked-in
``tools/dsodlint_baseline.json``, ``--fail-on-new`` exit 2,
``--update-baseline`` re-seeds — and a run where any checker CRASHED
never writes a baseline (a crashed pass sees zero findings and would
seed an empty lie).

Usage:
    python tools/dsodlint.py                    # print delta line
    python tools/dsodlint.py --human            # readable findings
    python tools/dsodlint.py --fail-on-new      # gate (t1.sh leg)
    python tools/dsodlint.py --update-baseline  # re-seed the file
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys
from typing import Dict, List, Optional, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHECKS = ("traced-purity", "lock-discipline", "env-coherence",
          "metrics-coherence", "accounting-seams", "pragma")

# What the suite scans (repo-relative).  Tests are deliberately out of
# scope: fixture code violates invariants on purpose.
SCAN_ROOTS = ("distributed_sod_project_tpu", "tools")

PKG = "distributed_sod_project_tpu"

# -- declared seams ----------------------------------------------------

# Host-reads sanctioned inside otherwise-traced reachability:
# (file, qualname).  Add a row ONLY with a comment saying why; the
# default posture is that the step builders stay pure.
TRACED_SEAMS: Set[Tuple[str, str]] = {
    # Build-time-only read: the flash block shapes are static ints
    # baked into the program at trace time (both vars are registered
    # program-affecting in utils/envvars.py).
    (f"{PKG}/pallas/flash_attention.py", "_env_block"),
}

# The ONLY places a terminal counter may move, per tier
# (docs/SERVING.md "Failure semantics"; docs/STATIC_ANALYSIS.md).  A
# nested function inherits its enclosing seam (qualname prefix match).
BOOKING_SEAMS: Set[Tuple[str, str]] = {
    (f"{PKG}/serve/engine.py", "InferenceEngine.submit"),
    (f"{PKG}/serve/engine.py", "InferenceEngine.stop"),
    (f"{PKG}/serve/engine.py", "InferenceEngine._dispatch_group"),
    (f"{PKG}/serve/engine.py", "InferenceEngine._complete"),
    (f"{PKG}/serve/engine.py", "InferenceEngine._finish"),
    (f"{PKG}/serve/router.py", "RouterHandler.do_POST"),
    # Router-cache booking seam (serve/cache.py): the ONE place an
    # exact / near-dup / coalesced hit enters the router book as the
    # cache_hit terminal class — the fifth identity bucket
    # (served+shed+expired+errors+cache_hit == submitted).
    (f"{PKG}/serve/router.py", "RouterHandler._serve_cache_hit"),
    # Stream booking seam (serve/streams.py): the ONE place the
    # temporal-coherence fast path enters the router book as the
    # stream_reuse terminal class — the sixth identity bucket
    # (served+shed+expired+errors+cache_hit+stream_reuse == submitted).
    (f"{PKG}/serve/router.py", "RouterHandler._serve_stream_reuse"),
    # Control-plane decision seams: every autoscale/rollout counter
    # moves through ONE _record per plane, which also emits the
    # flight-recorder event — book and evidence cannot drift apart.
    (f"{PKG}/serve/controller.py", "FleetController._record"),
    (f"{PKG}/serve/rollout.py", "RolloutManager._record"),
}

# Terminal-counter families (the accounting identity's terms).
TERMINAL_COUNTERS = {"submitted", "served", "shed", "expired", "errors"}
# Router-book / arm-stat booking methods that move a terminal counter.
# The ctrl/rollout trio are the control-plane decision books — a stray
# inc_decision/inc_verdict outside the _record seams is exactly the
# book-without-evidence drift the seam exists to prevent.
TERMINAL_BOOKING_CALLS = {"inc_submitted", "inc_shed", "inc_response",
                          "inc_served", "inc_decision", "inc_restart",
                          "inc_verdict"}

# Functions that open a traced scope when a function object is passed
# to them (matched on the callee's terminal name: jax.jit, pl.jit,
# lax.scan, jax.shard_map, pl.pallas_call all resolve).
TRACE_ENTRY_NAMES = {"jit", "shard_map", "scan", "pallas_call"}

_ENVVARS_FILE = f"{PKG}/utils/envvars.py"
_INVENTORY = os.path.join(REPO, "tools", "metrics_inventory.json")

_PRAGMA_RE = re.compile(
    r"#\s*dsodlint:\s*disable=([A-Za-z0-9_,-]+)(?:\s*--\s*(.+?))?\s*$")
# A metric-family-shaped fragment: word-start ``dsod_`` (so
# ``libdsod_host.so`` / ``~/.cache/dsod_xla`` never match mid-token).
_DSOD_METRIC_RE = re.compile(r"(?<![A-Za-z0-9_])dsod_[a-z0-9_]+")


class Finding:
    __slots__ = ("check", "file", "line", "symbol", "detail", "msg")

    def __init__(self, check: str, file: str, line: int, symbol: str,
                 detail: str, msg: str):
        self.check = check
        self.file = file
        self.line = line
        self.symbol = symbol
        self.detail = detail
        self.msg = msg

    def key(self) -> str:
        """Line-number-free identity, so the baseline survives
        unrelated edits above a finding."""
        return f"{self.check} {self.file} {self.symbol} {self.detail}"

    def human(self) -> str:
        return (f"{self.file}:{self.line}: [{self.check}] {self.symbol}: "
                f"{self.msg}")


class SourceFile:
    """One parsed file: AST with parent/qualname annotations, raw
    lines, and pragma map."""

    def __init__(self, rel: str, text: str):
        self.rel = rel
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=rel)
        self._annotate()
        # line → {check_or_*: reason_or_None}
        self.pragmas: Dict[int, Dict[str, Optional[str]]] = {}
        for i, line in enumerate(self.lines, 1):
            m = _PRAGMA_RE.search(line)
            if m:
                checks = {c.strip() for c in m.group(1).split(",")}
                reason = m.group(2)
                self.pragmas[i] = {c: reason for c in checks}

    def _annotate(self) -> None:
        scopes = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)

        def walk(node, qual: str):
            for child in ast.iter_child_nodes(node):
                child._dsod_parent = node  # noqa: SLF001
                if isinstance(child, scopes):
                    q = f"{qual}.{child.name}" if qual else child.name
                    child._dsod_qualname = q  # noqa: SLF001
                    walk(child, q)
                else:
                    walk(child, qual)

        walk(self.tree, "")

    def qualname_at(self, node: ast.AST) -> str:
        n = node
        while n is not None:
            q = getattr(n, "_dsod_qualname", None)
            if q is not None:
                return q
            n = getattr(n, "_dsod_parent", None)
        return "<module>"

    def enclosing_def_lines(self, node: ast.AST) -> List[int]:
        out = []
        n = node
        while n is not None:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                out.append(n.lineno)
            n = getattr(n, "_dsod_parent", None)
        return out

    def waiver(self, check: str, line: int,
               scope_lines: List[int]) -> Optional[Tuple[str, str]]:
        """A matching pragma for (check, line) — same line, the line
        above, or an enclosing def/class line.  Returns
        (reason_or_MISSING, at_line) or None."""
        for ln in [line, line - 1] + list(scope_lines):
            prag = self.pragmas.get(ln)
            if not prag:
                continue
            for key in (check, "*", "all"):
                if key in prag:
                    return (prag[key] if prag[key] is not None
                            else "__MISSING__"), str(ln)
        return None


# -- file discovery ----------------------------------------------------

def discover(root: str) -> List[str]:
    out = []
    for entry in SCAN_ROOTS:
        path = os.path.join(root, entry)
        if os.path.isfile(path):
            out.append(entry)
        elif os.path.isdir(path):
            for dirpath, _dirs, files in os.walk(path):
                for f in sorted(files):
                    if f.endswith(".py"):
                        rel = os.path.relpath(os.path.join(dirpath, f),
                                              root)
                        out.append(rel.replace(os.sep, "/"))
    return sorted(set(out))


def load_files(root: str) -> Tuple[Dict[str, SourceFile], List[str]]:
    files, errors = {}, []
    for rel in discover(root):
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as f:
                text = f.read()
            files[rel] = SourceFile(rel, text)
        except (SyntaxError, UnicodeDecodeError) as e:
            errors.append(f"{rel}: {type(e).__name__}: {e}")
    return files, errors


# -- shared name-resolution engine -------------------------------------

def _dotted(rel: str) -> Optional[str]:
    """Repo-relative path → dotted module name (package files only)."""
    if not rel.endswith(".py"):
        return None
    mod = rel[:-3].replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


class ModuleIndex:
    """Cross-module symbol table: top-level functions + import map per
    file, so call edges can be followed into the package."""

    def __init__(self, files: Dict[str, SourceFile]):
        self.files = files
        self.by_module: Dict[str, SourceFile] = {}
        for rel, sf in files.items():
            mod = _dotted(rel)
            if mod:
                self.by_module[mod] = sf
        # rel → {name: FunctionDef} (module top level)
        self.top_funcs: Dict[str, Dict[str, ast.AST]] = {}
        # rel → {local_name: (module, original_name_or_None)}
        self.imports: Dict[str, Dict[str, Tuple[str, Optional[str]]]] = {}
        for rel, sf in files.items():
            funcs, imps = {}, {}
            for node in sf.tree.body:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    funcs[node.name] = node
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ImportFrom):
                    mod = self._resolve_from(rel, node)
                    if mod:
                        for alias in node.names:
                            imps[alias.asname or alias.name] = \
                                (mod, alias.name)
                elif isinstance(node, ast.Import):
                    for alias in node.names:
                        imps[alias.asname or alias.name] = \
                            (alias.name, None)
            self.top_funcs[rel] = funcs
            self.imports[rel] = imps

    def _resolve_from(self, rel: str, node: ast.ImportFrom
                      ) -> Optional[str]:
        if node.level == 0:
            return node.module
        base = _dotted(rel) or ""
        parts = base.split(".")
        # level=1 is the CONTAINING package: for a plain module that
        # strips the module name; for a package __init__ it strips
        # nothing (the dotted name already IS the package).
        strip = node.level if not rel.endswith("/__init__.py") \
            else node.level - 1
        parts = parts[: len(parts) - strip] if strip <= len(parts) else []
        if node.module:
            parts += node.module.split(".")
        return ".".join(parts) if parts else None

    def module_file(self, mod: str) -> Optional[SourceFile]:
        return self.by_module.get(mod)

    def resolve_func(self, rel: str, name: str, _seen: Optional[Set] = None
                     ) -> Optional[Tuple[str, ast.AST]]:
        """A bare name at module scope of ``rel`` → (file, FunctionDef)
        within the repo, following from-import chains (packages
        re-export through __init__.py — recurse with a cycle guard)."""
        _seen = _seen if _seen is not None else set()
        if (rel, name) in _seen:
            return None
        _seen.add((rel, name))
        f = self.top_funcs.get(rel, {}).get(name)
        if f is not None:
            return rel, f
        imp = self.imports.get(rel, {}).get(name)
        if imp is not None:
            mod, orig = imp
            if orig is None:
                return None  # plain module import, not a function
            sf = self.module_file(mod)
            if sf is not None:
                hit = self.resolve_func(sf.rel, orig, _seen)
                if hit is not None:
                    return hit
            # from package import module?  (name is a module)
            sub = self.module_file(f"{mod}.{orig}")
            if sub is not None:
                return None
        return None

    def resolve_attr_func(self, rel: str, mod_alias: str, attr: str
                          ) -> Optional[Tuple[str, ast.AST]]:
        """``alias.attr(...)`` where alias is an imported repo module."""
        imp = self.imports.get(rel, {}).get(mod_alias)
        if imp is None:
            return None
        mod, orig = imp
        target = mod if orig is None else f"{mod}.{orig}"
        sf = self.module_file(target)
        if sf is None:
            return None
        f = self.top_funcs.get(sf.rel, {}).get(attr)
        return (sf.rel, f) if f is not None else None


def _callee_tail(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _local_defs(fn: ast.AST) -> Dict[str, ast.AST]:
    """Nested function defs immediately inside ``fn`` (any depth below
    fn but not inside deeper defs is fine to include — name lookup)."""
    out = {}
    for node in ast.walk(fn):
        if node is fn:
            continue
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, node)
    return out


# -- checker: traced-purity --------------------------------------------

_SYNC_TIME_ATTRS = {"time", "monotonic", "perf_counter", "process_time"}


def _is_env_read(node: ast.Call) -> bool:
    """os.environ.get(...) / os.getenv(...) / envvars.read[...]()."""
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "get" and isinstance(f.value, ast.Attribute) \
                and f.value.attr == "environ":
            return True
        if f.attr == "getenv" and isinstance(f.value, ast.Name) \
                and f.value.id == "os":
            return True
        if f.attr in ("read", "read_int") and \
                isinstance(f.value, ast.Name) and \
                f.value.id == "envvars":
            return True
    elif isinstance(f, ast.Name) and f.id in ("getenv",):
        return True
    return False


def _sync_violation(node: ast.AST) -> Optional[str]:
    """The traced-purity violation a node constitutes, or None."""
    if isinstance(node, ast.Call):
        f = node.func
        tail = _callee_tail(f)
        if tail == "device_get":
            return "jax.device_get"
        if tail == "item" and isinstance(f, ast.Attribute):
            return ".item()"
        if isinstance(f, ast.Name) and f.id in ("print", "float"):
            return f"{f.id}()"
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if f.value.id in ("np", "numpy") and \
                    f.attr in ("asarray", "array"):
                return f"np.{f.attr}"
            if f.value.id == "time" and f.attr in _SYNC_TIME_ATTRS:
                return f"time.{f.attr}"
        if _is_env_read(node):
            return "environment read"
    elif isinstance(node, ast.Attribute) and node.attr == "environ" \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "os":
        return "os.environ"
    return None


def check_traced_purity(files: Dict[str, SourceFile], index: ModuleIndex,
                        report) -> None:
    # 1. Collect traced roots: functions passed to jit/shard_map/scan/
    #    pallas_call, with one level of wrapper unwrapping (body =
    #    chunked_step_fn(step_fn, ...) → step_fn is a root too).
    roots: List[Tuple[str, ast.AST]] = []   # (file, funcdef)
    seen_ids: Set[int] = set()

    def add_root(rel: str, fn: ast.AST) -> None:
        if id(fn) not in seen_ids:
            seen_ids.add(id(fn))
            roots.append((rel, fn))

    for rel, sf in files.items():
        # local name → def node, per enclosing function scope
        for scope in ast.walk(sf.tree):
            if not isinstance(scope, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Module)):
                continue
            local = _local_defs(scope) if not isinstance(scope, ast.Module) \
                else dict(index.top_funcs.get(rel, {}))
            # name → wrapped function args (body = wrapper(step_fn))
            assigned_from: Dict[str, ast.Call] = {}
            for node in ast.walk(scope):
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and isinstance(node.value, ast.Call):
                    assigned_from[node.targets[0].id] = node.value
            for node in ast.walk(scope):
                if not (isinstance(node, ast.Call)
                        and _callee_tail(node.func) in TRACE_ENTRY_NAMES):
                    continue
                args = list(node.args) + [kw.value for kw in node.keywords]
                for a in args:
                    if isinstance(a, ast.Lambda):
                        add_root(rel, a)
                    elif isinstance(a, ast.Name):
                        if a.id in local:
                            add_root(rel, local[a.id])
                        elif a.id in assigned_from:
                            # one unwrap: the wrapper call's own
                            # function-name args become roots
                            inner = assigned_from[a.id]
                            for ia in (list(inner.args)
                                       + [k.value for k in inner.keywords]):
                                if isinstance(ia, ast.Name) \
                                        and ia.id in local:
                                    add_root(rel, local[ia.id])

    # 2. Reachability through the call graph (nested defs + module
    #    functions + one import hop), collecting violations per
    #    reached function body.
    visited: Set[Tuple[str, int]] = set()
    work = list(roots)
    while work:
        rel, fn = work.pop()
        if (rel, id(fn)) in visited:
            continue
        visited.add((rel, id(fn)))
        sf = files[rel]
        if (rel, sf.qualname_at(fn)) in TRACED_SEAMS:
            continue
        local = _local_defs(fn)
        # scan this function's own body, not nested defs' (they are
        # queued separately when actually called)
        nested = set()
        for name, nd in local.items():
            for sub in ast.walk(nd):
                nested.add(id(sub))
        body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if id(node) in nested:
                    continue
                v = _sync_violation(node)
                if v is not None:
                    qn = sf.qualname_at(fn) if not isinstance(
                        fn, ast.Lambda) else sf.qualname_at(node)
                    report(Finding(
                        "traced-purity", rel, node.lineno, qn, v,
                        f"{v} reachable inside traced code (host "
                        "sync/IO belongs at the declared flush seams; "
                        "env is read at program-BUILD time)"))
                if isinstance(node, ast.Call):
                    f = node.func
                    target = None
                    if isinstance(f, ast.Name):
                        if f.id in local:
                            target = (rel, local[f.id])
                        else:
                            target = index.resolve_func(rel, f.id)
                    elif isinstance(f, ast.Attribute) and \
                            isinstance(f.value, ast.Name):
                        target = index.resolve_attr_func(
                            rel, f.value.id, f.attr)
                    if target is not None:
                        work.append(target)
                    # function-valued ARGUMENTS stay traced too:
                    # jax.grad(loss_fn), maybe_remat(forward),
                    # tree_map(lambda ...) — the callee applies them
                    # inside the same trace.
                    for a in (list(node.args)
                              + [k.value for k in node.keywords]):
                        if isinstance(a, ast.Lambda):
                            work.append((rel, a))
                        elif isinstance(a, ast.Name):
                            if a.id in local:
                                work.append((rel, local[a.id]))
                            else:
                                t = index.resolve_func(rel, a.id)
                                if t is not None:
                                    work.append(t)


# -- checker: lock-discipline ------------------------------------------

def _is_self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _write_targets(node: ast.AST) -> List[Tuple[str, int]]:
    """self.X = / self.X += / self.X[...] = writes in one statement."""
    out = []
    targets: List[ast.AST] = []
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
    for t in targets:
        for el in ast.walk(t):
            attr = _is_self_attr(el)
            if attr is not None:
                out.append((attr, node.lineno))
                break
            if isinstance(el, ast.Subscript):
                attr = _is_self_attr(el.value)
                if attr is not None:
                    out.append((attr, node.lineno))
                    break
    return out


def check_lock_discipline(files: Dict[str, SourceFile], report) -> None:
    scoped = {rel: sf for rel, sf in files.items()
              if rel.startswith((f"{PKG}/serve/", f"{PKG}/utils/"))}
    for rel, sf in scoped.items():
        for cls in ast.walk(sf.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            methods = {n.name: n for n in cls.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            if not methods:
                continue
            # lock attrs this class owns
            lock_attrs: Set[str] = set()
            spawns_threads = False
            for m in methods.values():
                for node in ast.walk(m):
                    if isinstance(node, ast.Assign) and \
                            isinstance(node.value, ast.Call) and \
                            _callee_tail(node.value.func) in \
                            ("Lock", "RLock"):
                        for t in node.targets:
                            attr = _is_self_attr(t)
                            if attr:
                                lock_attrs.add(attr)
                    if isinstance(node, ast.Call) and \
                            _callee_tail(node.func) in ("Thread", "Timer"):
                        spawns_threads = True
            if not lock_attrs and not spawns_threads:
                continue

            # thread entries: Thread(target=self.X)/Timer(.., self.X),
            # pool.submit(self.X, ...), local closures passed as
            # target= (their self.Y() calls and writes count as
            # thread-side, attributed to the enclosing method's
            # thread graph), plus the conventional run().
            entries: Set[str] = set()
            closure_thread_fns: List[ast.AST] = []
            for mname, m in methods.items():
                local = _local_defs(m)
                for node in ast.walk(m):
                    if not isinstance(node, ast.Call):
                        continue
                    tail = _callee_tail(node.func)
                    cands: List[ast.AST] = []
                    if tail in ("Thread", "Timer"):
                        for kw in node.keywords:
                            if kw.arg == "target":
                                cands.append(kw.value)
                        if tail == "Timer" and len(node.args) >= 2:
                            cands.append(node.args[1])
                    elif tail == "submit" and node.args:
                        cands.append(node.args[0])
                    for c in cands:
                        attr = _is_self_attr(c)
                        if attr and attr in methods:
                            entries.add(attr)
                        elif isinstance(c, ast.Name) and c.id in local:
                            closure_thread_fns.append(local[c.id])
            if "run" in methods:
                entries.add("run")
            for fn in closure_thread_fns:
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call):
                        attr = _is_self_attr(node.func)
                        if attr and attr in methods:
                            entries.add(attr)

            # close entries over the intra-class call graph
            calls: Dict[str, Set[str]] = {}
            for mname, m in methods.items():
                callees = set()
                for node in ast.walk(m):
                    if isinstance(node, ast.Call):
                        attr = _is_self_attr(node.func)
                        if attr and attr in methods:
                            callees.add(attr)
                calls[mname] = callees
            frontier = list(entries)
            while frontier:
                mname = frontier.pop()
                for c in calls.get(mname, ()):
                    if c not in entries:
                        entries.add(c)
                        frontier.append(c)

            # writes: attr → [(method, line, locked, thread_side)]
            writes: Dict[str, List[Tuple[str, int, bool, bool]]] = {}

            def scan_writes(m: ast.AST, mname: str,
                            thread_side: bool) -> None:
                nested = {id(s) for name, nd in _local_defs(m).items()
                          for s in ast.walk(nd)}
                # The ``*_locked`` naming convention: a method named
                # ``_foo_locked`` documents (and this linter trusts)
                # that every caller already holds the owning lock —
                # its writes count as locked.
                held_by_convention = mname.endswith("_locked")

                def locked_at(node):
                    if held_by_convention:
                        return True
                    n = node
                    while n is not None and n is not m:
                        if isinstance(n, ast.With):
                            for item in n.items:
                                ce = item.context_expr
                                attr = _is_self_attr(ce)
                                if attr is None and \
                                        isinstance(ce, ast.Call):
                                    attr = _is_self_attr(ce.func)
                                if attr in lock_attrs:
                                    return True
                        n = getattr(n, "_dsod_parent", None)
                    return False

                for node in ast.walk(m):
                    if id(node) in nested:
                        continue
                    for attr, line in _write_targets(node):
                        writes.setdefault(attr, []).append(
                            (mname, line, locked_at(node), thread_side))

            for mname, m in methods.items():
                scan_writes(m, mname, mname in entries)
            for fn in closure_thread_fns:
                # the closure runs ON the spawned thread
                nested_owner = sf.qualname_at(fn)
                scan_writes(fn, nested_owner.rsplit(".", 1)[-1], True)

            qual_prefix = sf.qualname_at(cls)
            for attr, sites in sorted(writes.items()):
                if attr in lock_attrs:
                    continue
                non_init = [s for s in sites if s[0] != "__init__"]
                if not non_init:
                    continue
                thread_writes = [s for s in non_init if s[3]]
                other_writes = [s for s in non_init if not s[3]]
                locked_writes = [s for s in non_init if s[2]]
                unlocked = [s for s in non_init if not s[2]]
                flag = None
                if thread_writes and other_writes and unlocked:
                    flag = ("cross-thread write of self.%s (thread "
                            "graph: %s; elsewhere: %s) outside the "
                            "owning lock" % (
                                attr,
                                ",".join(sorted({s[0]
                                                 for s in thread_writes})),
                                ",".join(sorted({s[0]
                                                 for s in other_writes}))))
                elif locked_writes and unlocked:
                    flag = ("mixed guard for self.%s: written under a "
                            "lock in %s but bare in %s" % (
                                attr,
                                ",".join(sorted({s[0]
                                                 for s in locked_writes})),
                                ",".join(sorted({s[0] for s in unlocked}))))
                if flag:
                    for mname, line, _lk, _th in unlocked:
                        report(Finding(
                            "lock-discipline", rel, line,
                            f"{qual_prefix}.{mname}", f"self.{attr}",
                            flag))


# -- checker: env-coherence --------------------------------------------

def _registry_names(files: Dict[str, SourceFile]) -> Set[str]:
    """The names utils/envvars.py registers."""
    sf = files.get(_ENVVARS_FILE)
    if sf is None:
        raise RuntimeError(f"{_ENVVARS_FILE} not found")
    out: Set[str] = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call) and \
                _callee_tail(node.func) == "EnvVar" and node.args:
            name = node.args[0]
            if isinstance(name, ast.Constant) and \
                    isinstance(name.value, str):
                out.add(name.value)
    return out


def _module_str_consts(sf: SourceFile) -> Dict[str, str]:
    out = {}
    for node in sf.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            out[node.targets[0].id] = node.value.value
    return out


def check_env_coherence(files: Dict[str, SourceFile], report) -> None:
    registry = _registry_names(files)

    for name in sorted(registry):
        if not re.fullmatch(r"DSOD_[A-Z0-9_]+", name):
            report(Finding("env-coherence", _ENVVARS_FILE, 1,
                           "REGISTRY", name,
                           f"registry entry {name!r} is not a DSOD_* "
                           "name"))
    for rel, sf in files.items():
        consts = _module_str_consts(sf)

        def lit_of(arg: ast.AST) -> Optional[str]:
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str):
                return arg.value
            if isinstance(arg, ast.Name) and arg.id in consts:
                return consts[arg.id]
            return None

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and _is_env_read(node):
                f = node.func
                via_registry = isinstance(f, ast.Attribute) and \
                    f.attr in ("read", "read_int")
                arg = node.args[0] if node.args else None
                name = lit_of(arg) if arg is not None else None
                qn = sf.qualname_at(node)
                if not via_registry and name is not None and \
                        name.startswith("DSOD_") and \
                        rel != _ENVVARS_FILE:
                    report(Finding(
                        "env-coherence", rel, node.lineno, qn,
                        f"bypass:{name}",
                        f"direct os.environ read of {name} bypasses "
                        "utils/envvars.py::read"))
                if name is not None and name.startswith("DSOD_") and \
                        name not in registry:
                    report(Finding(
                        "env-coherence", rel, node.lineno, qn,
                        f"unregistered:{name}",
                        f"{name} read but not registered in "
                        "utils/envvars.py"))
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load) and \
                    isinstance(node.value, ast.Attribute) and \
                    node.value.attr == "environ":
                name = lit_of(node.slice)
                if name is not None and name.startswith("DSOD_") and \
                        rel != _ENVVARS_FILE:
                    report(Finding(
                        "env-coherence", rel, node.lineno,
                        sf.qualname_at(node), f"bypass:{name}",
                        f"direct os.environ[{name!r}] read bypasses "
                        "utils/envvars.py::read"))


# -- checker: metrics-coherence ----------------------------------------

def _docstring_ids(sf: SourceFile) -> Set[int]:
    out = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


def check_metrics_coherence(files: Dict[str, SourceFile],
                            inventory_path: str, report) -> None:
    with open(inventory_path) as f:
        inv_doc = json.load(f)
    inventory: Set[str] = set()
    for section in inv_doc.values():
        inventory.update(section)

    # Namespaces that actually exist in the inventory (``serve`` from
    # ``dsod_serve_*`` etc.): a literal outside every known namespace
    # is a path/identifier (``dsod_xla`` cache dir, chaos run tags),
    # not a metric family — the runtime metrics_lint still catches a
    # genuinely new namespace when its surface first renders.
    namespaces = {fam.split("_", 2)[1] for fam in inventory
                  if fam.count("_") >= 2}

    def metric_shaped(m: str) -> bool:
        parts = m.split("_")
        return len(parts) >= 3 and parts[1] in namespaces

    names: Dict[str, Tuple[str, int]] = {}   # literal → first site
    prefixes: Set[str] = set()
    for rel, sf in files.items():
        if rel == "tools/dsodlint.py":
            continue  # self-referential examples
        doc_ids = _docstring_ids(sf)
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)):
                continue
            if id(node) in doc_ids:
                continue
            for m in _DSOD_METRIC_RE.findall(node.value):
                if m.endswith("_"):
                    prefixes.add(m)
                elif metric_shaped(m) and m not in names:
                    names[m] = (rel, node.lineno)

    def documented(name: str) -> bool:
        if name in inventory:
            return True
        for suf in ("_bucket", "_sum", "_count"):
            if name.endswith(suf) and name[: -len(suf)] in inventory:
                return True
        return False

    for name, (rel, line) in sorted(names.items()):
        if not documented(name):
            report(Finding(
                "metrics-coherence", rel, line, "<literal>", name,
                f"metric-family literal {name!r} is not in "
                "tools/metrics_inventory.json (run tools/metrics_lint.py "
                "--update-baseline after an INTENDED surface change)"))

    def constructible(fam: str) -> bool:
        if fam in names:
            return True
        for suf in ("_bucket", "_sum", "_count", "_total"):
            if fam.endswith(suf) and fam[: -len(suf)] in names:
                return True
        return any(fam.startswith(p) for p in prefixes)

    for fam in sorted(inventory):
        if not constructible(fam):
            report(Finding(
                "metrics-coherence", "tools/metrics_inventory.json", 1,
                "<inventory>", fam,
                f"inventory family {fam!r} has no source literal or "
                "declared prefix that could render it"))


# -- checker: accounting-seams -----------------------------------------

def check_accounting_seams(files: Dict[str, SourceFile], report) -> None:
    for rel, sf in files.items():
        if not rel.startswith(f"{PKG}/serve/"):
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            tail = _callee_tail(node.func)
            hit = None
            if tail == "inc" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    node.args[0].value in TERMINAL_COUNTERS:
                hit = f'inc("{node.args[0].value}")'
            elif tail in TERMINAL_BOOKING_CALLS and \
                    isinstance(node.func, ast.Attribute):
                # the booking METHODS' own definitions live outside
                # serve/ (utils/observability.py) or are the seam's
                # body (RouterStats.inc_* self-increments are plain
                # dict writes, not .inc calls)
                hit = f"{tail}()"
            if hit is None:
                continue
            qn = sf.qualname_at(node)
            ok = any(rel == f and (qn == q or qn.startswith(q + "."))
                     for f, q in BOOKING_SEAMS)
            if not ok:
                report(Finding(
                    "accounting-seams", rel, node.lineno, qn, hit,
                    f"terminal counter moved via {hit} outside the "
                    "declared booking seams (docs/STATIC_ANALYSIS.md: "
                    "extend BOOKING_SEAMS deliberately, with review)"))


# -- driver ------------------------------------------------------------

def run_checks(root: str, checks=CHECKS, inventory: Optional[str] = None):
    """Returns (findings, waived, crashed, parse_errors)."""
    files, parse_errors = load_files(root)
    index = ModuleIndex(files)
    findings: List[Finding] = []
    waived: List[Tuple[Finding, str, str]] = []
    crashed: Dict[str, str] = {}

    def reporter_for(check: str):
        def report(f: Finding) -> None:
            sf = files.get(f.file)
            if sf is not None:
                node_scope: List[int] = []
                # find enclosing def lines cheaply via pragma scan of
                # every def line is overkill; waiver() needs them, so
                # locate by qualname match
                for n in ast.walk(sf.tree):
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.ClassDef)) and \
                            getattr(n, "_dsod_qualname", None) and \
                            (f.symbol == n._dsod_qualname
                             or f.symbol.startswith(
                                 n._dsod_qualname + ".")):
                        node_scope.append(n.lineno)
                w = sf.waiver(f.check, f.line, node_scope)
                if w is not None:
                    reason, at = w
                    if reason == "__MISSING__":
                        findings.append(Finding(
                            "pragma", f.file, int(at), f.symbol,
                            f"missing-reason:{f.check}",
                            "dsodlint pragma without a reason string "
                            "(write: # dsodlint: disable=<check> -- "
                            "<why this is safe>)"))
                    else:
                        waived.append((f, reason, at))
                    return
            findings.append(f)
        return report

    for check in checks:
        if check == "pragma":
            continue
        try:
            if check == "traced-purity":
                check_traced_purity(files, index,
                                    reporter_for(check))
            elif check == "lock-discipline":
                check_lock_discipline(files, reporter_for(check))
            elif check == "env-coherence":
                check_env_coherence(files, reporter_for(check))
            elif check == "metrics-coherence":
                check_metrics_coherence(
                    files, inventory or _INVENTORY,
                    reporter_for(check))
            elif check == "accounting-seams":
                check_accounting_seams(files, reporter_for(check))
        except Exception as e:  # noqa: BLE001 — crash isolation per pass
            crashed[check] = f"{type(e).__name__}: {e}"
    return findings, waived, crashed, parse_errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--root", default=REPO)
    p.add_argument("--baseline", default=None,
                   help="findings baseline (default: "
                        "tools/dsodlint_baseline.json under --root — "
                        "NOT this repo's, so a --root run on another "
                        "tree can never clobber the checked-in file)")
    p.add_argument("--inventory", default=None,
                   help="metrics inventory path (default: "
                        "tools/metrics_inventory.json next to --root's "
                        "tools, falling back to this repo's)")
    p.add_argument("--update-baseline", action="store_true")
    p.add_argument("--fail-on-new", action="store_true",
                   help="exit 2 when findings appear that are not in "
                        "the baseline")
    p.add_argument("--check", action="append", default=[],
                   choices=[c for c in CHECKS if c != "pragma"],
                   help="run only these checkers (repeatable)")
    p.add_argument("--human", action="store_true",
                   help="readable findings instead of the one-line "
                        "JSON summary")
    args = p.parse_args(argv)

    root = os.path.abspath(args.root)
    if args.baseline is None:
        args.baseline = os.path.join(root, "tools",
                                     "dsodlint_baseline.json")
    inventory = args.inventory
    if inventory is None:
        cand = os.path.join(root, "tools", "metrics_inventory.json")
        inventory = cand if os.path.exists(cand) else _INVENTORY
    checks = tuple(args.check) or CHECKS

    findings, waived, crashed, parse_errors = run_checks(
        root, checks=checks, inventory=inventory)

    current = sorted({f.key() for f in findings})
    baseline = None
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)

    if crashed or parse_errors:
        # NEVER seed or refresh a baseline from a crashed run: a
        # crashed checker reports zero findings, and recording that as
        # the baseline would green-light every future violation.
        payload = {"metric": "dsodlint", "error": "checker crashed",
                   "crashed": crashed, "parse_errors": parse_errors}
        print(json.dumps(payload), flush=True)
        return 1

    # --fail-on-new never auto-seeds: a gate run on a baseline-less
    # tree must treat every finding as new, not silently bless it.
    if args.update_baseline or (baseline is None
                                and not args.fail_on_new):
        if args.human:
            for f in sorted(findings, key=lambda f: (f.file, f.line)):
                print(f.human())
        with open(args.baseline, "w") as f:
            json.dump({"version": 1, "findings": current}, f, indent=2)
            f.write("\n")
        print(json.dumps({
            "metric": "dsodlint", "findings": len(current),
            "waived": len(waived), "recorded": True}), flush=True)
        return 0

    base = set(baseline.get("findings", [])) if baseline else set()
    new = sorted(set(current) - base)
    fixed = sorted(base - set(current))

    if args.human:
        for f in sorted(findings, key=lambda f: (f.file, f.line)):
            marker = "NEW " if f.key() in set(new) else ""
            print(f"{marker}{f.human()}")
        for f, reason, at in sorted(waived,
                                    key=lambda w: (w[0].file, w[0].line)):
            print(f"waived {f.human()}  [pragma@{at}: {reason}]")
        if fixed:
            print("fixed since baseline:")
            for k in fixed:
                print(f"  {k}")
    summary = {
        "metric": "dsodlint",
        "checks": list(checks),
        "findings": len(current),
        "waived": len(waived),
        "new": new,
        "fixed": fixed,
        "delta": len(new),
    }
    print(json.dumps(summary), flush=True)
    if args.fail_on_new and new:
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
