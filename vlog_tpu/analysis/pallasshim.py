"""pallasshim: Pallas kernel code stays inside the kernel modules.

``ops/pallas_ladder.py`` is the ladder's Pallas surface: it owns the
``jax.experimental.pallas`` import, the interpret-mode switch for
non-TPU backends, and the byte-identity contract with the XLA resize
path. Program builders select a *plane* via
:func:`~vlog_tpu.ops.pallas_ladder.ladder_resize` — they never see
``pallas_call``. A raw pallas import anywhere else leaks kernel code
past that module: the call site explodes under ``JAX_PLATFORMS=cpu`` (no
interpret switch), ignores ``VLOG_PALLAS``, and silently forks the
byte-identity contract the tier-1 matrix asserts.

``lm/attention_kernel.py`` (PR 34) is the second kernel module, under
the same terms: the transcript model's chunk attention, which
``lm/model.py::attention_form`` selects from the call's shapes and the
backend (the CPU keeps the XLA loop; tests run the kernel interpreted),
so ``model.py`` never sees ``pallas_call`` either.

``lm/moe_kernel.py`` is the third, under the same terms: the
experts' combine, which ``lm/moe.py::combine_form`` selects from the
call's shapes and the backend (the CPU keeps the XLA combine; tests run
the kernel interpreted), so ``moe.py`` never sees ``pallas_call``.

Rule: outside those modules, no module may

- ``from jax.experimental import pallas`` (or ``pallas as pl``)
- ``import jax.experimental.pallas`` / any ``jax.experimental.pallas.*``
  submodule (``...pallas.tpu`` included)
- ``from jax.experimental.pallas import ...``
- reference the ``jax.experimental.pallas`` attribute path or call a
  ``pallas_call`` attribute (``pl.pallas_call`` spelled any way) in code.

Importing the sanctioned surface
(``from vlog_tpu.ops.pallas_ladder import ladder_resize``) is of course
not matched — the pass only looks at jax-rooted paths and the
``pallas_call`` attribute name.
"""

from __future__ import annotations

import ast

from vlog_tpu.analysis.core import Finding, Module, dotted_name

RULE = "pallasshim"

_SHIM = ("ops/pallas_ladder.py, lm/attention_kernel.py or lm/moe_kernel.py "
         "(the sanctioned Pallas surfaces)")
_PALLAS_ROOT = "jax.experimental.pallas"


_KERNEL_MODULES = (("ops", "pallas_ladder.py"), ("lm", "attention_kernel.py"),
                   ("lm", "moe_kernel.py"))


def _exempt(mod: Module) -> bool:
    # The kernel modules themselves, and the analysis package (this file
    # quotes the banned spellings in docstrings/tests).
    return mod.pkg_parts in _KERNEL_MODULES or mod.pkg_parts[0] == "analysis"


def _is_pallas_module(name: str | None) -> bool:
    return bool(name) and (name == _PALLAS_ROOT
                           or name.startswith(_PALLAS_ROOT + "."))


def _import_findings(mod: Module) -> list[Finding]:
    findings = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if _is_pallas_module(alias.name):
                    findings.append(Finding(
                        RULE, mod.rel, node.lineno,
                        f"raw import {alias.name} — kernel code belongs "
                        f"in {_SHIM}"))
        elif isinstance(node, ast.ImportFrom):
            if _is_pallas_module(node.module):
                findings.append(Finding(
                    RULE, mod.rel, node.lineno,
                    f"raw from {node.module} import — kernel code "
                    f"belongs in {_SHIM}"))
            elif node.module == "jax.experimental" and any(
                    alias.name == "pallas" for alias in node.names):
                findings.append(Finding(
                    RULE, mod.rel, node.lineno,
                    f"raw from jax.experimental import pallas — kernel "
                    f"code belongs in {_SHIM}"))
    return findings


def _attr_findings(mod: Module) -> list[Finding]:
    findings = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Attribute):
            continue
        if node.attr == "pallas_call":
            # any X.pallas_call spelling — the alias (pl, pallas, ...)
            # doesn't matter; only the shim may build kernels
            findings.append(Finding(
                RULE, mod.rel, node.lineno,
                f"pallas_call attribute use — kernel code belongs "
                f"in {_SHIM}"))
        elif node.attr == "pallas" and dotted_name(node) == _PALLAS_ROOT:
            findings.append(Finding(
                RULE, mod.rel, node.lineno,
                f"raw {_PALLAS_ROOT} attribute use — kernel code "
                f"belongs in {_SHIM}"))
    return findings


def run(modules: list[Module], pkg_dir) -> list[Finding]:
    findings: list[Finding] = []
    for mod in modules:
        if _exempt(mod):
            continue
        findings.extend(_import_findings(mod))
        findings.extend(_attr_findings(mod))
    return findings
