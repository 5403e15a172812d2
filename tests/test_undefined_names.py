"""No module of the tree reads a global name that nothing binds.

A helper deleted with one of its calls left behind raises NameError only
when that line runs, and the lines of a tool that times arms on a mesh
(`tools/_mc_ab.campaign`) run in no tier-1 test: PR 42's first diff left
such a call and counted 1,416 passes. This reads every file's symbol table
(nothing is imported or run): a name a scope looks up as a global has to be
bound at the module's top level, by a `global` statement, or be a builtin.
A module with a star import is passed over (`paddle_tpu/layers/__init__.py`
alone: it re-exports and reads nothing).
"""
import ast
import builtins
import glob
import os
import symtable

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = {
    "paddle_tpu": "paddle_tpu/**/*.py",
    "tools": "tools/**/*.py",
    "benchmark": "benchmark/**/*.py",
    "tests": "tests/**/*.py",
    "root scripts": "*.py",
}
_MODULE_NAMES = set(dir(builtins)) | {
    "__file__", "__name__", "__doc__", "__path__", "__spec__", "__package__",
    "__builtins__", "__debug__"}


def _scopes(table):
    yield table
    for child in table.get_children():
        yield from _scopes(child)


def _binds(symbol) -> bool:
    return symbol.is_assigned() or symbol.is_imported() \
        or symbol.is_namespace()


def undefined_names(source: str, filename: str) -> list:
    """[(scope, first line of the scope, name)] of the global names `source`
    reads and never binds."""
    top = symtable.symtable(source, filename, "exec")
    bound = {s.get_name() for s in top.get_symbols() if _binds(s)}
    bound.update(s.get_name() for scope in _scopes(top) if scope is not top
                 for s in scope.get_symbols()
                 if s.is_declared_global() and _binds(s))
    bound |= _MODULE_NAMES
    return [(scope.get_name(), scope.get_lineno(), s.get_name())
            for scope in _scopes(top) for s in scope.get_symbols()
            if s.is_referenced() and s.get_name() not in bound
            and (s.is_global() if scope is not top else not _binds(s))]


def test_the_check_sees_a_call_left_behind():
    source = ("def campaign(n):\n"
              "    stats = [n]\n"
              "    _rec('dp_zero1', stats, n)\n"
              "    return len(stats)\n")
    assert undefined_names(source, "<left behind>") == [
        ("campaign", 1, "_rec")]
    assert undefined_names("def _rec(*a): pass\n" + source, "<bound>") == []


@pytest.mark.parametrize("root", sorted(ROOTS))
def test_no_global_name_is_undefined(root):
    found = []
    for path in sorted(glob.glob(os.path.join(REPO, ROOTS[root]),
                                 recursive=True)):
        with open(path) as f:
            source = f.read()
        if any(isinstance(node, ast.ImportFrom) and node.names[0].name == "*"
               for node in ast.walk(ast.parse(source))):
            continue        # a star import may bind anything
        found += [f"{os.path.relpath(path, REPO)}:{line} in {scope}: {name}"
                  for scope, line, name in undefined_names(source, path)]
    assert not found, "\n".join(found)
