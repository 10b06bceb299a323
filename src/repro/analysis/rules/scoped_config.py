"""scoped-config: JAX global config flips must be scoped, never mutated.

``core.jax_model``/``core.jax_evolve`` need 64-bit JAX (int64 genomes,
float64 latencies).  The wrong way to get it is
``jax.config.update("jax_enable_x64", True)`` — a process-global flip
that silently changes dtypes for *every other* jax user in the process:
the Pallas kernels, the serving engine, the train step.  PR 6 scoped the
requirement with ``with jax.enable_x64(True):`` around each
entry point so the flag is restored on exit; this rule keeps it that way.

Flags:
  * any call to ``jax.config.update(...)`` / ``config.update("jax_*")``,
  * assignments to ``jax.config.<flag>``,
  * ``enable_x64(...)`` / ``jax.enable_x64(...)`` called as a plain
    expression instead of as a ``with`` context manager (entering
    without the ``with`` leaks the flipped state).
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator, Set

from ..core import Finding, Rule
from ..project import ModuleInfo, Project


def _attr_chain(node: ast.AST) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _enable_x64_names(tree: ast.Module) -> Set[str]:
    """Local names bound to jax.enable_x64."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for alias in node.names:
                if alias.name == "enable_x64":
                    out.add(alias.asname or "enable_x64")
    return out


class ScopedConfigRule(Rule):
    name = "scoped-config"
    description = ("jax.config mutations are forbidden; 64-bit mode must "
                   "be entered via `with jax.enable_x64(True):`")

    def check(self, project: Project) -> Iterable[Finding]:
        for mod in project.iter_modules():
            yield from self._check_module(mod)

    def _check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        x64_names = _enable_x64_names(mod.tree)
        # collect every Call that appears as a with-statement context
        # expression: those are the scoped (legal) enable_x64 uses
        with_calls = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    with_calls.add(id(item.context_expr))

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if chain.endswith("config.update") and self._is_jax_update(
                        chain, node):
                    yield self.finding(
                        mod, node.lineno, col=node.col_offset,
                        message=(
                            "process-global jax.config.update() mutation; "
                            "scope the requirement with `with "
                            "jax.enable_x64(True):` (or the "
                            "matching context manager) so the flag is "
                            "restored on exit"))
                elif (chain == "jax.enable_x64" or chain in x64_names) \
                        and id(node) not in with_calls:
                    yield self.finding(
                        mod, node.lineno, col=node.col_offset,
                        message=(
                            "enable_x64() called outside a `with` "
                            "statement; entering the context manually "
                            "leaks 64-bit mode to every jax user in the "
                            "process"))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    chain = _attr_chain(t)
                    if ".config." in chain and \
                            chain.split(".config.")[0].endswith("jax"):
                        yield self.finding(
                            mod, node.lineno, col=node.col_offset,
                            message=(
                                f"assignment to '{chain}' mutates "
                                "process-global JAX config; use a scoped "
                                "context manager instead"))

    @staticmethod
    def _is_jax_update(chain: str, node: ast.Call) -> bool:
        """True when the config.update call targets JAX config: either the
        receiver chain mentions jax, or the flag literal starts 'jax_'."""
        root = chain.split(".")[0]
        if root == "jax":
            return True
        if node.args and isinstance(node.args[0], ast.Constant) and \
                isinstance(node.args[0].value, str):
            return node.args[0].value.startswith("jax_")
        return False
