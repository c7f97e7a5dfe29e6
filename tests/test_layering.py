"""The package's imports point one way: launch -> serve -> core ->
graph/sparse/kernels.  No module below ``launch`` imports it, at module
level or inside a function."""
import ast
from pathlib import Path

import pytest

PKG = Path(__file__).resolve().parent.parent / "src" / "repro"


def _imported(path: Path) -> list[tuple[int, str]]:
    """Every absolute module name that ``path`` imports, with its line."""
    package = ["repro", *path.relative_to(PKG).parent.parts]
    out = []
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out += [(node.lineno, a.name) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            # `from .. import launch` names the module in the import list
            out += [(node.lineno, f"{mod}.{a.name}") for a in node.names]
    return out


@pytest.mark.parametrize("layer", ["core", "serve", "graph", "sparse", "kernels"])
def test_layer_does_not_import_launch(layer):
    files = sorted((PKG / layer).rglob("*.py"))
    assert files, layer
    bad = [f"{p.relative_to(PKG)}:{line} imports {name}"
           for p in files for line, name in _imported(p)
           if name == "repro.launch" or name.startswith("repro.launch.")]
    assert not bad, bad
