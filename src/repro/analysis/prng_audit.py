"""Rule ``prng-audit``: the derive_key schedule is collision-free.

Reproducibility across the fused/vmapped/sharded execution paths rests
on every consumer deriving its keys through the same
``derive_key(base, purpose, iteration, index)`` tree. Two failure
classes are audited:

1. **Structural** (AST, over ``core/bo.py`` + the service): a
   ``fold_in`` tag built from ARITHMETIC (``purpose * K + it``) can
   collide for in-range values — every fold tag must be a plain
   name/constant, every ``derive_key`` (and batched ``derive_keys``)
   call site must pass a ``KEY_PURPOSE_*`` constant, and the declared
   purpose registry (``bo.KEY_PURPOSES``, mirrored by the service's
   ``KEY_SCHEDULE``) must be distinct and complete.

2. **Behavioural** (concrete enumeration): ``derive_key`` evaluated
   over the full purpose set x iterations x indices must produce
   pairwise-distinct key data. The ranges cover the collision windows
   arithmetic encodings actually alias in (index spans crossing an
   iteration step), so the seeded-bug corpus's flattened-tag mutant is
   caught by construction.
"""
from __future__ import annotations

import ast
import inspect
from typing import Callable, List, Optional, Sequence

from .findings import Finding

AUDIT_ITERS = range(8)
AUDIT_INDICES = range(12)


def _prng_sources():
    import repro.core.bo
    import repro.serve.search_service
    return [(m.__name__, inspect.getsource(m))
            for m in (repro.core.bo, repro.serve.search_service)]


def check_fold_in_tags(source: Optional[str] = None,
                       label: str = "") -> List[Finding]:
    """Flag arithmetic fold_in tags and non-constant derive_key
    purposes."""
    sources = ([(label, source)] if source is not None
               else _prng_sources())
    out: List[Finding] = []
    for mod_label, src in sources:
        for node in ast.walk(ast.parse(src)):
            if not isinstance(node, ast.Call):
                continue
            fname = None
            if isinstance(node.func, ast.Attribute):
                fname = node.func.attr
            elif isinstance(node.func, ast.Name):
                fname = node.func.id
            if fname == "fold_in":
                for arg in node.args[1:]:
                    if isinstance(arg, ast.BinOp):
                        out.append(Finding(
                            "prng-audit", "error", mod_label,
                            f"{mod_label}:{node.lineno}",
                            "fold_in tag is an arithmetic expression "
                            "— flattened encodings alias distinct "
                            "(purpose, iteration, index) paths; fold "
                            "each component separately"))
            if (fname in ("derive_key", "derive_keys")
                    and len(node.args) >= 2):
                purpose = node.args[1]
                named = (isinstance(purpose, ast.Name)
                         and purpose.id.startswith("KEY_PURPOSE_"))
                const = isinstance(purpose, ast.Constant)
                is_def_param = isinstance(purpose, ast.Name)
                if not (named or const or is_def_param):
                    out.append(Finding(
                        "prng-audit", "warning", mod_label,
                        f"{mod_label}:{node.lineno}",
                        "derive_key purpose is not a KEY_PURPOSE_* "
                        "constant"))
    return out


def check_purpose_registry() -> List[Finding]:
    """Purposes distinct; every KEY_PURPOSE_* constant registered; the
    service's declared schedule covers the same set."""
    from repro.core import bo
    out: List[Finding] = []
    values = list(bo.KEY_PURPOSES.values())
    if len(set(values)) != len(values):
        out.append(Finding(
            "prng-audit", "error", "core.bo", "KEY_PURPOSES",
            f"purpose tags collide: {bo.KEY_PURPOSES}"))
    declared = {name: getattr(bo, name) for name in dir(bo)
                if name.startswith("KEY_PURPOSE_")}
    missing = {n: v for n, v in declared.items() if v not in values}
    if missing:
        out.append(Finding(
            "prng-audit", "error", "core.bo", "KEY_PURPOSES",
            f"purpose constants not in the registry: {missing}"))
    try:
        from repro.serve import search_service
        schedule = {p for p, _desc in search_service.KEY_SCHEDULE}
        if schedule != set(values):
            out.append(Finding(
                "prng-audit", "error", "serve.search_service",
                "KEY_SCHEDULE",
                f"service schedule purposes {schedule} != registry "
                f"{set(values)}"))
    except Exception as exc:
        out.append(Finding(
            "prng-audit", "warning", "serve.search_service",
            "KEY_SCHEDULE", f"schedule not inspectable: {exc}"))
    return out


def check_schedule_collisions(
    derive: Optional[Callable] = None,
    purposes: Optional[Sequence[int]] = None,
    iters: Sequence[int] = AUDIT_ITERS,
    indices: Sequence[int] = AUDIT_INDICES,
) -> List[Finding]:
    """Concretely enumerate the schedule and demand distinct key
    data."""
    import jax
    import numpy as np

    from repro.core import bo
    derive = bo.derive_key if derive is None else derive
    purposes = (sorted(bo.KEY_PURPOSES.values()) if purposes is None
                else purposes)
    base = jax.random.PRNGKey(0)
    seen = {}
    out: List[Finding] = []
    for p in purposes:
        for it in iters:
            for idx in indices:
                data = np.asarray(derive(base, p, it, idx)).tobytes()
                if data in seen:
                    out.append(Finding(
                        "prng-audit", "error", "derive_key",
                        f"{(p, it, idx)} == {seen[data]}",
                        "two (purpose, iteration, index) paths derive "
                        "the same key: streams would be correlated"))
                    return out
                seen[data] = (p, it, idx)
    return out


def check_prng_audit() -> List[Finding]:
    return (check_fold_in_tags() + check_purpose_registry()
            + check_schedule_collisions())
