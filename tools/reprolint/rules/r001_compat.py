"""R001 compat-only-imports.

Contract: the jax APIs that ``src/repro/compat.py`` wraps
(``jax.experimental.shard_map``, top-level ``jax.shard_map``,
``jax.set_mesh``, ``jax.make_array_from_single_device_arrays``,
``jax.sharding.AxisType``, ``jax.experimental.multihost_utils``,
``jax.distributed`` initialize/shutdown) are used *only* inside it —
every other module goes through the wrappers, so the behavior they add
(``check_replication``, auto-axis meshes, per-shard assembly, the
single-process fast paths) lives in one place and a future jax line is
absorbed there.

Pinned by: ARCHITECTURE.md "Version portability" and
``tests/test_compat_fallbacks.py`` (the wrappers on the installed line);
the whitelist is ``config.WHITELIST["R001"]``.
"""
from __future__ import annotations

import ast
from typing import Iterator, List

from .. import config
from ..core import Diagnostic, Rule, register

_FORBIDDEN_MODULES = (
    "jax.experimental.shard_map",
    "jax.experimental.multihost_utils",
)

_FORBIDDEN_FROM = {
    ("jax", "shard_map"),
    ("jax", "set_mesh"),
    ("jax", "make_array_from_single_device_arrays"),
    ("jax.sharding", "AxisType"),
    ("jax.experimental", "shard_map"),
    ("jax.experimental", "multihost_utils"),
    ("jax.distributed", "initialize"),
    ("jax.distributed", "shutdown"),
}

_FORBIDDEN_ATTRS = {
    "jax.shard_map",
    "jax.set_mesh",
    "jax.make_array_from_single_device_arrays",
    "jax.sharding.AxisType",
    "jax.experimental.shard_map",
    "jax.experimental.multihost_utils",
    "jax.distributed.initialize",
    "jax.distributed.shutdown",
}

_HINT = "route it through repro.compat (extend the shim if missing)"


@register
class CompatOnlyImports(Rule):
    __doc__ = __doc__

    id = "R001"
    name = "compat-only-imports"

    def check(self, tree: ast.AST, text: str, relpath: str) -> Iterator[Diagnostic]:
        if config.rule_whitelisted(self.id, relpath):
            return
        diags: List[Diagnostic] = []

        class V(ast.NodeVisitor):
            def visit_Import(self, node: ast.Import) -> None:
                for alias in node.names:
                    for mod in _FORBIDDEN_MODULES:
                        if alias.name == mod or alias.name.startswith(mod + "."):
                            diags.append(Diagnostic(
                                relpath, node.lineno, "R001",
                                f"direct import of {alias.name!r}; {_HINT}"))

            def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
                mod = node.module or ""
                if mod in _FORBIDDEN_MODULES:
                    diags.append(Diagnostic(
                        relpath, node.lineno, "R001",
                        f"direct import from {mod!r}; {_HINT}"))
                    return
                for alias in node.names:
                    if (mod, alias.name) in _FORBIDDEN_FROM:
                        diags.append(Diagnostic(
                            relpath, node.lineno, "R001",
                            f"direct import of {mod}.{alias.name}; {_HINT}"))

            def visit_Attribute(self, node: ast.Attribute) -> None:
                dn = Rule.dotted(node)
                if dn is not None and (
                    dn in _FORBIDDEN_ATTRS
                    or any(dn.startswith(a + ".") for a in _FORBIDDEN_ATTRS)
                ):
                    diags.append(Diagnostic(
                        relpath, node.lineno, "R001",
                        f"direct use of drifting jax API {dn!r}; {_HINT}"))
                    return  # don't recurse: avoid re-flagging the prefix
                self.generic_visit(node)

        V().visit(tree)
        yield from diags
