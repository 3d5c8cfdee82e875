"""Shared plumbing for the graft-lint passes.

Every pass is a module with ``run(sources) -> List[Finding]`` where
``sources`` maps repo-relative paths (forward slashes) to file text.
Passes locate the files they care about by CONTENT (e.g. "the module
defining ``class ServeConfig``"), not by hardcoded paths, so the test
fixtures can feed small synthetic trees through the exact production
code path.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Finding:
    """One ``file:line: PASS-ID message`` diagnostic."""
    file: str
    line: int
    pass_id: str
    message: str

    def format(self) -> str:
        return f"{self.file}:{self.line}: {self.pass_id} {self.message}"

    @property
    def baseline_key(self) -> str:
        """Suppressions are counted per (pass, file) — coarse enough to
        survive line churn, fine enough that a NEW violation in a file
        with no budget fails immediately."""
        return f"{self.pass_id}:{self.file}"


def repo_root() -> str:
    """The repository root (parent of the package directory)."""
    here = os.path.dirname(os.path.abspath(__file__))     # .../analysis
    return os.path.dirname(os.path.dirname(here))         # repo


def load_sources(root: Optional[str] = None) -> Dict[str, str]:
    """The package's sources.  Keys are repo-relative with forward
    slashes."""
    root = root or repo_root()
    pkg = os.path.join(root, "mpi_tensorflow_tpu")
    out: Dict[str, str] = {}
    for base, _dirs, files in os.walk(pkg):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, encoding="utf-8") as fh:
                out[rel] = fh.read()
    return out


def parse_sources(sources: Dict[str, str]) -> Dict[str, ast.Module]:
    """Parse every source, skipping files that do not parse (the names
    pass would drown in noise on a syntax error the interpreter will
    report anyway)."""
    out: Dict[str, ast.Module] = {}
    for rel, text in sources.items():
        try:
            out[rel] = ast.parse(text, filename=rel)
        except SyntaxError:
            continue
    return out


_ALLOW_RE = re.compile(r"#\s*graft-lint:\s*([a-z-]+)-ok\(([^)]*)\)")


def allowlist_reason(source: str, lineno: int, tag: str) -> Optional[str]:
    """Return the ``# graft-lint: <tag>-ok(<reason>)`` reason covering
    ``lineno``, or None.  The marker may sit on the flagged line itself
    or on the line directly above it (long lines push it up)."""
    lines = source.splitlines()
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = _ALLOW_RE.search(lines[ln - 1])
            if m and m.group(1) == tag:
                return m.group(2) or "unspecified"
    return None


def iter_functions(tree: ast.AST) -> Iterator[ast.AST]:
    """Every function/async-function definition in ``tree``."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def find_class(trees: Dict[str, ast.Module],
               name: str) -> Optional[Tuple[str, ast.ClassDef]]:
    """Locate ``class <name>`` anywhere in the parsed sources."""
    for rel, tree in trees.items():
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name == name:
                return rel, node
    return None


def find_function(tree: ast.AST, name: str) -> Optional[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def dotted_name(node: ast.AST) -> Optional[str]:
    """``jax.jit`` -> "jax.jit", ``jit`` -> "jit", else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted_name(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def arg_names(fn: ast.AST) -> List[str]:
    """Positional + keyword parameter names of a def/lambda, minus
    ``self``/``cls``."""
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return [n for n in names if n not in ("self", "cls")]
