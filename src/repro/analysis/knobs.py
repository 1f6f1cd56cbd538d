"""Knob-inertness: the "default-off, baseline hash-gated" convention.

The public constructors (``VDMSAsyncEngine``, ``ShardedEngine``,
``WireFrontend``) grow a knob per feature; the repo convention is that
every knob (1) is a keyword argument with a default, (2) defaults to
the *inert* value — the paper-faithful path must be byte-identical
with all knobs at their defaults — and (3) is exercised by name in at
least one test or benchmark cell, so the default-off path stays pinned
by the hash-gated tests (``tests/test_response_identity.py``).

Statically checkable slice:

* a keyword-only parameter with no default — a knob that callers are
  forced to think about — violates (1);
* a boolean knob defaulting to ``True`` is an *enabling* default and
  violates (2) (deliberate exceptions carry a waiver);
* a knob whose name appears nowhere under ``tests/`` or ``bench/``
  violates (3) — nothing pins its default-off path.

Positional parameters without defaults (``engine``, required wiring)
are dependencies, not knobs, and are skipped.
"""
from __future__ import annotations

import re

from repro.analysis.harvest import ModuleFacts
from repro.analysis.model import Finding

#: Constructors held to the knob convention.
KNOB_CLASSES = ("VDMSAsyncEngine", "ShardedEngine", "WireFrontend")


def check_knobs(modules: list[ModuleFacts], ref_corpus: str,
                knob_classes=KNOB_CLASSES) -> list[Finding]:
    out: list[Finding] = []
    for mf in modules:
        for cls_name in knob_classes:
            cf = mf.classes.get(cls_name)
            if cf is None:
                continue
            for p in cf.init_params:
                scope = f"{cls_name}.__init__"
                if p.kwonly and not p.has_default:
                    out.append(Finding(
                        rule="knob-inert", severity="error",
                        path=mf.path, line=p.line, scope=scope,
                        subject=f"{cls_name}.{p.name}:no-default",
                        message=(f"knob {p.name!r} has no default — every "
                                 f"engine knob must be optional with an "
                                 f"inert default")))
                    continue
                if not p.has_default:
                    continue          # required dependency, not a knob
                if p.default_is_true:
                    out.append(Finding(
                        rule="knob-inert", severity="error",
                        path=mf.path, line=p.line, scope=scope,
                        subject=f"{cls_name}.{p.name}:enabling-default",
                        message=(f"knob {p.name!r} defaults to True — an "
                                 f"enabling default breaks the default-off "
                                 f"convention (waive if deliberate)")))
                if not re.search(rf"\b{re.escape(p.name)}\b", ref_corpus):
                    out.append(Finding(
                        rule="knob-inert", severity="error",
                        path=mf.path, line=p.line, scope=scope,
                        subject=f"{cls_name}.{p.name}:unreferenced",
                        message=(f"knob {p.name!r} is referenced by no test "
                                 f"or benchmark — nothing pins its "
                                 f"default-off path")))
    return out
