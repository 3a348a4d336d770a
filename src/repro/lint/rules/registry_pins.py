"""Rule ``registry-kind-unpinned`` — cross-file registry/test consistency.

The ``repro.opt`` registries are open: registering a new censor, transport,
or server kind instantly makes it reachable from every builder, sweep, and
JSON spec. The test suite pins behavior per *kind* — the transport
conformance suite parametrizes over ``TRANSPORT_KINDS`` at collection time
and the pinned-run tests name each kind by string — so a kind that exists
in the registry but never appears in those files ships unpinned: nothing
fails when its numerics drift.

This project rule parses ``src/repro/opt/registry.py`` for the three
``*_KINDS`` dict literals and requires every key to appear as a string
literal in its pin files:

  * transport kinds -> ``tests/transport_conformance.py`` (the contract
    suite's kind vocabulary) AND ``tests/test_backend.py`` (the pinned
    runs of every transport);
  * censor + server kinds -> ``tests/test_opt.py`` (spec round-trip and
    pinned runs).

It is a tripwire, not a coverage proof: the literal's presence is checked
textually (AST string constants), which is exactly the level at which the
"I registered a kind and forgot the pins" mistake happens.  Outside a
repo with that layout the rule is silent.
"""
from __future__ import annotations

import ast

from ..asthelpers import dict_str_keys, str_constants
from ..findings import Finding
from ..registry import project_rule

_REGISTRY = "src/repro/opt/registry.py"
_PINS = {
    "TRANSPORT_KINDS": ("transport",
                        ("tests/transport_conformance.py",
                         "tests/test_backend.py")),
    "CENSOR_KINDS": ("censor", ("tests/test_opt.py",)),
    "SERVER_KINDS": ("server", ("tests/test_opt.py",)),
}


def _kind_tables(tree: ast.Module) -> dict[str, tuple[int, set[str]]]:
    """{table_name: (lineno, kind keys)} from the registry module."""
    out = {}
    for node in tree.body:
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        else:
            continue
        for t in targets:
            if isinstance(t, ast.Name) and t.id in _PINS:
                keys = dict_str_keys(node.value)
                if keys:
                    out[t.id] = (node.lineno, keys)
    return out


@project_rule("registry-kind-unpinned",
              "every kind in the censor/transport/server registries must "
              "appear in the conformance-suite parametrization and the "
              "pinned-run tests — an unpinned kind ships with no drift "
              "tripwire")
def check(ctx):
    registry_tree = ctx.read_project_file(_REGISTRY)
    if registry_tree is None:
        return
    tables = _kind_tables(registry_tree)
    pin_literals: dict[str, set[str] | None] = {}
    for _, (_, pin_files) in _PINS.items():
        for pf in pin_files:
            if pf not in pin_literals:
                tree = ctx.read_project_file(pf)
                pin_literals[pf] = None if tree is None \
                    else str_constants(tree)

    for table, (lineno, kinds) in tables.items():
        what, pin_files = _PINS[table]
        for kind in sorted(kinds):
            missing = [pf for pf in pin_files
                       if pin_literals.get(pf) is not None
                       and kind not in pin_literals[pf]]
            if missing:
                yield Finding(
                    rule="registry-kind-unpinned", path=_REGISTRY,
                    line=lineno, col=0,
                    message=f"{what} kind {kind!r} ({table}) is not "
                            f"pinned in {missing}: add it to the "
                            "conformance parametrization / golden tables "
                            "so numeric drift in it fails a test")
