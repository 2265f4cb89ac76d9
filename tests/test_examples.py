"""The examples against the package they import.

No test runs an example, so a name deleted from the package can break
one unseen.  For each file under ``examples/``: it compiles, and every
name it imports from ``covalent_tpu_plugin`` resolves.  Nothing of the
example is executed and no jax program is built.
"""

import ast
import importlib
import pathlib

import pytest

EXAMPLES = sorted(
    (pathlib.Path(__file__).resolve().parents[1] / "examples").glob("*.py")
)
PACKAGE = "covalent_tpu_plugin"


def package_imports(tree: ast.AST):
    """``(module, name or None, line)`` for each import of the package."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == PACKAGE:
                    yield alias.name, None, node.lineno
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            if (node.module or "").split(".")[0] == PACKAGE:
                for alias in node.names:
                    yield node.module, alias.name, node.lineno


@pytest.mark.parametrize("path", EXAMPLES, ids=lambda p: p.name)
def test_example_compiles_and_its_imports_resolve(path):
    source = path.read_text()
    compile(source, str(path), "exec")
    imports = list(package_imports(ast.parse(source)))
    assert imports, f"{path.name} imports nothing from {PACKAGE}"
    for module, name, line in imports:
        imported = importlib.import_module(module)
        if name is None or name == "*" or hasattr(imported, name):
            continue
        # ``from package import submodule`` of one not imported yet.
        try:
            importlib.import_module(f"{module}.{name}")
        except ImportError:
            pytest.fail(f"{path.name}:{line}: {module} has no {name!r}")


def test_the_examples_were_found():
    assert EXAMPLES, "no examples/*.py beside tests/: nothing was checked"
