"""meshshim: every shard_map call site goes through parallel/mesh.

``parallel/mesh.py::shard_map`` is the tree's one spelling of
``jax.shard_map``; every sharded program in the tree — the 1-D ladder
programs, the per-column programs of the 2-D (data × rung) grid, the
dryrun harness — imports it from there. One import site means the next
jax that moves or renames the function (as 0.4.35 did: out of
``jax.experimental``, ``check_rep`` to ``check_vma``) is absorbed in
one line instead of at every program builder.

Rule: outside ``parallel/mesh.py``, no module may

- ``from jax.experimental.shard_map import ...``
- ``from jax.experimental import shard_map``
- ``import jax.experimental.shard_map``
- ``from jax import shard_map``
- reference the ``jax.shard_map`` / ``jax.experimental.shard_map``
  attribute path in code.

Importing the shim (``from vlog_tpu.parallel.mesh import shard_map``)
is of course the sanctioned spelling and is not matched — the pass
only looks at jax-rooted paths.
"""

from __future__ import annotations

import ast

from vlog_tpu.analysis.core import Finding, Module, dotted_name

RULE = "meshshim"

_SHIM = "parallel/mesh.py (the one import site)"
_RAW_MODULES = frozenset({
    "jax.experimental.shard_map",
})


def _exempt(mod: Module) -> bool:
    # The shim itself, and the analysis package (this file quotes the
    # banned spellings in docstrings/tests).
    return (mod.pkg_parts == ("parallel", "mesh.py")
            or mod.pkg_parts[0] == "analysis")


def _import_findings(mod: Module) -> list[Finding]:
    findings = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name in _RAW_MODULES:
                    findings.append(Finding(
                        RULE, mod.rel, node.lineno,
                        f"raw import {alias.name} — route shard_map "
                        f"through {_SHIM}"))
        elif isinstance(node, ast.ImportFrom):
            if node.module in _RAW_MODULES:
                findings.append(Finding(
                    RULE, mod.rel, node.lineno,
                    f"raw from {node.module} import — route shard_map "
                    f"through {_SHIM}"))
            elif node.module in ("jax", "jax.experimental") and any(
                    alias.name == "shard_map" for alias in node.names):
                findings.append(Finding(
                    RULE, mod.rel, node.lineno,
                    f"raw from {node.module} import shard_map — route "
                    f"shard_map through {_SHIM}"))
    return findings


def _attr_findings(mod: Module) -> list[Finding]:
    findings = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Attribute) or node.attr != "shard_map":
            continue
        dotted = dotted_name(node)
        if dotted in ("jax.shard_map", "jax.experimental.shard_map"):
            findings.append(Finding(
                RULE, mod.rel, node.lineno,
                f"raw {dotted} attribute use — route shard_map "
                f"through {_SHIM}"))
    return findings


def run(modules: list[Module], pkg_dir) -> list[Finding]:
    findings: list[Finding] = []
    for mod in modules:
        if _exempt(mod):
            continue
        findings.extend(_import_findings(mod))
        findings.extend(_attr_findings(mod))
    return findings
