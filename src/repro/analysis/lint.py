"""Pass 2 — the repo-invariant lint engine.

``ast``-based rules enforcing invariants ruff cannot express:

``raw-collective``
    No raw ``jax.lax`` collective calls outside ``core/nap_collectives.py``.
    Every collective must go through the NAP wrappers so the comm auditor's
    per-strategy signatures stay exhaustive.  Documented exceptions carry an
    inline ``# comm-audit: allow <tag>`` marker (e.g. the flat psum of
    ``models/moe.py``) or a module-level
    ``# comm-audit: allow-file raw-collective`` marker (e.g.
    ``train/grad_sync.py``, itself a hierarchical-collective implementation).

``async-blocking``
    No blocking ``AMGService`` / ``Ticket.result`` calls inside ``async def``
    bodies — the deadlock class the serving front-end routes around via
    ``ticket_future`` / ``asyncio.to_thread``.  A nested *sync* ``def``
    (e.g. a done-callback) resets the scope.

``traced-host-call``
    No wall-clock reads, host spans (``span`` of ``repro.amg.spans``) or
    host callbacks inside functions handed to ``jax.jit`` / ``shard_map`` /
    ``vmap`` — they would be baked in at trace time, timing the trace and
    not the run (or stall the device stream), silently corrupting
    measurements.

``frozen-mutation``
    No attribute assignment on frozen-dataclass instances and no
    ``object.__setattr__`` escape hatch outside ``__post_init__`` — state
    evolution must go through ``dataclasses.replace`` so config/plan
    identity stays hashable and cache-safe.

Suppression markers:

* ``# comm-audit: allow <tag>`` on the violating line — documented,
  per-site exception; the tag is the rationale label.
* ``# comm-audit: allow-file <rule>`` anywhere in the module — exempts the
  whole file from that rule.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path

from .records import LintViolation

COLLECTIVE_FNS = frozenset({
    "psum", "psum_scatter", "all_gather", "all_to_all", "ppermute",
    "pshuffle", "pmax", "pmin", "pmean",
})
BLOCKING_METHODS = frozenset({"result", "update_wire", "drain"})
TRACE_WRAPPERS = frozenset({"jit", "shard_map", "smap", "vmap", "pmap"})
HOST_CALLS = frozenset({
    "time.time", "time.perf_counter", "time.monotonic",
    "time.time_ns", "time.perf_counter_ns",
    "datetime.now", "datetime.datetime.now", "datetime.utcnow",
    "jax.pure_callback", "jax.experimental.io_callback", "io_callback",
    "jax.debug.callback",
})

_ALLOW_LINE = re.compile(r"#\s*comm-audit:\s*allow\s+(\S+)")
_ALLOW_FILE = re.compile(r"#\s*comm-audit:\s*allow-file\s+(\S+)")


def _dotted(node: ast.AST) -> str | None:
    """``jax.lax.psum`` -> "jax.lax.psum"; None for non-name chains."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _decorator_names(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> set[str]:
    out: set[str] = set()
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _dotted(target)
        if name:
            out.add(name.rsplit(".", 1)[-1])
        if isinstance(dec, ast.Call):        # functools.partial(jax.jit, ...)
            for arg in dec.args:
                inner = _dotted(arg)
                if inner:
                    out.add(inner.rsplit(".", 1)[-1])
    return out


def collect_frozen_classes(trees: dict[str, ast.Module]) -> set[str]:
    """Names of every ``@dataclass(frozen=True)`` class across the repo."""
    frozen: set[str] = set()
    for tree in trees.values():
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                name = _dotted(dec.func)
                if not name or name.rsplit(".", 1)[-1] != "dataclass":
                    continue
                for kw in dec.keywords:
                    if (kw.arg == "frozen"
                            and isinstance(kw.value, ast.Constant)
                            and kw.value.value is True):
                        frozen.add(node.name)
    return frozen


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, lines: list[str], frozen: set[str],
                 file_allows: set[str]):
        self.path = path
        self.lines = lines
        self.frozen = frozen
        self.file_allows = file_allows
        self.violations: list[LintViolation] = []
        self._fn_stack: list[str] = []      # "async" | "sync"
        self._traced_names: set[str] = set()
        self._span_calls: set[str] = set()  # how this module names span()
        self._traced_depth = 0
        self._frozen_vars: list[set[str]] = [set()]
        self._in_post_init = False
        self._is_nap_core = path.replace("\\", "/").endswith(
            "core/nap_collectives.py")

    # -- bookkeeping -------------------------------------------------------
    def _allowed(self, rule: str, line: int) -> bool:
        if rule in self.file_allows:
            return True
        text = self.lines[line - 1] if 0 < line <= len(self.lines) else ""
        return bool(_ALLOW_LINE.search(text))

    def _flag(self, rule: str, node: ast.AST, message: str) -> None:
        if not self._allowed(rule, node.lineno):
            self.violations.append(
                LintViolation(rule, self.path, node.lineno, message))

    # -- scopes ------------------------------------------------------------
    def _visit_fn(self, node, kind: str) -> None:
        decos = _decorator_names(node)
        traced = (bool(decos & TRACE_WRAPPERS)
                  or node.name in self._traced_names)
        self._fn_stack.append(kind)
        self._traced_depth += 1 if traced else 0
        frozen_here = set()
        for arg in (node.args.args + node.args.posonlyargs
                    + node.args.kwonlyargs):
            ann = arg.annotation
            name = ann and _dotted(ann)
            if (name and name.rsplit(".", 1)[-1] in self.frozen
                    and arg.arg != "self"):
                frozen_here.add(arg.arg)
        self._frozen_vars.append(frozen_here)
        was_post_init = self._in_post_init
        if node.name == "__post_init__":
            self._in_post_init = True
        self.generic_visit(node)
        self._in_post_init = was_post_init
        self._frozen_vars.pop()
        self._traced_depth -= 1 if traced else 0
        self._fn_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_fn(node, "sync")

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_fn(node, "async")

    def visit_Module(self, node: ast.Module) -> None:
        # pre-scan: local functions handed to jit/shard_map/vmap are traced;
        # imports of the host span (`from .spans import span`, `from
        # repro.amg import spans`, `import repro.amg.spans as s`)
        for sub in ast.walk(node):
            if isinstance(sub, ast.ImportFrom):
                from_spans = (sub.module or "").rsplit(".", 1)[-1] == "spans"
                for alias in sub.names:
                    if from_spans and alias.name == "span":
                        self._span_calls.add(alias.asname or "span")
                    elif alias.name == "spans":
                        self._span_calls.add(f"{alias.asname or 'spans'}.span")
            elif isinstance(sub, ast.Import):
                for alias in sub.names:
                    if alias.name.rsplit(".", 1)[-1] == "spans":
                        self._span_calls.add(
                            f"{alias.asname or alias.name}.span")
            elif isinstance(sub, ast.Call):
                name = _dotted(sub.func)
                if name and name.rsplit(".", 1)[-1] in TRACE_WRAPPERS:
                    for arg in sub.args:
                        if isinstance(arg, ast.Name):
                            self._traced_names.add(arg.id)
        self.generic_visit(node)

    # -- rules -------------------------------------------------------------
    def visit_Await(self, node: ast.Await) -> None:
        # an awaited call yields to the event loop — by definition not a
        # blocking call (e.g. `await writer.drain()` on an asyncio stream)
        setattr(node.value, "_awaited", True)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func) or ""
        leaf = name.rsplit(".", 1)[-1]

        if (not self._is_nap_core and leaf in COLLECTIVE_FNS
                and (name.startswith("jax.lax.") or name.startswith("lax."))):
            self._flag("raw-collective", node,
                       f"raw `{name}` call — route through "
                       f"repro.core.nap_collectives so the comm auditor's "
                       f"strategy signatures stay exhaustive")

        if (self._fn_stack and self._fn_stack[-1] == "async"
                and not getattr(node, "_awaited", False)):
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in BLOCKING_METHODS):
                self._flag("async-blocking", node,
                           f"blocking `.{node.func.attr}()` call inside an "
                           f"`async def` body — route through ticket_future "
                           f"/ asyncio.to_thread")
            elif name == "time.sleep":
                self._flag("async-blocking", node,
                           "`time.sleep` inside an `async def` body — use "
                           "`await asyncio.sleep`")

        if self._traced_depth > 0 and (
                name in HOST_CALLS or name in self._span_calls
                or leaf in {"pure_callback", "io_callback"}
                or name.endswith("debug.callback")):
            self._flag("traced-host-call", node,
                       f"`{name}` inside a traced function — host reads are "
                       f"baked in at trace time")

        if (name == "object.__setattr__" and not self._in_post_init):
            self._flag("frozen-mutation", node,
                       "`object.__setattr__` outside `__post_init__` — use "
                       "`dataclasses.replace`")
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        # x = FrozenClass(...) makes x a frozen instance in this scope
        is_frozen_ctor = False
        if isinstance(node.value, ast.Call):
            vname = _dotted(node.value.func) or ""
            if vname.rsplit(".", 1)[-1] in self.frozen:
                is_frozen_ctor = True
        for tgt in node.targets:
            if is_frozen_ctor and isinstance(tgt, ast.Name):
                self._frozen_vars[-1].add(tgt.id)
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id in self._frozen_vars[-1]
                    and not self._in_post_init):
                self._flag("frozen-mutation", node,
                           f"assignment to `{tgt.value.id}.{tgt.attr}` on a "
                           f"frozen dataclass — use `dataclasses.replace`")
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        ann = _dotted(node.annotation) or ""
        if (ann.rsplit(".", 1)[-1] in self.frozen
                and isinstance(node.target, ast.Name)):
            self._frozen_vars[-1].add(node.target.id)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        tgt = node.target
        if (isinstance(tgt, ast.Attribute) and isinstance(tgt.value, ast.Name)
                and tgt.value.id in self._frozen_vars[-1]
                and not self._in_post_init):
            self._flag("frozen-mutation", node,
                       f"augmented assignment to `{tgt.value.id}.{tgt.attr}`"
                       f" on a frozen dataclass — use `dataclasses.replace`")
        self.generic_visit(node)


def lint_source(src: str, path: str = "<string>",
                frozen: set[str] | None = None) -> list[LintViolation]:
    """Lint one module's source.  ``frozen`` injects repo-wide frozen-class
    names; when omitted, only classes defined in ``src`` are known."""
    tree = ast.parse(src, filename=path)
    if frozen is None:
        frozen = collect_frozen_classes({path: tree})
    file_allows = set(_ALLOW_FILE.findall(src))
    lines = src.splitlines()
    linter = _Linter(path, lines, frozen, file_allows)
    linter.visit(tree)
    return sorted(linter.violations, key=lambda v: (v.path, v.line, v.rule))


def lint_paths(root: str | Path) -> list[LintViolation]:
    """Lint every ``.py`` module under ``root`` (normally ``src/``), with
    frozen-dataclass names collected repo-wide first so cross-module
    instances are tracked."""
    root = Path(root)
    sources: dict[str, str] = {}
    trees: dict[str, ast.Module] = {}
    for p in sorted(root.rglob("*.py")):
        rel = str(p)
        src = p.read_text()
        sources[rel] = src
        trees[rel] = ast.parse(src, filename=rel)
    frozen = collect_frozen_classes(trees)
    out: list[LintViolation] = []
    for rel, src in sources.items():
        out.extend(lint_source(src, rel, frozen=frozen))
    return out
