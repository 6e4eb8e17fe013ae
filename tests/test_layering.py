"""The import graph: the system is the clustering and serving path, and its
lower layers do not reach up into the launchers.  Read from the sources
with ``ast``, so nothing is imported."""
import ast
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = ROOT / "src" / "repro"

# modules removed with the language-model and GNN code; none may come back
# through an import
REMOVED = ("repro.models", "repro.train", "repro.optim", "repro.configs",
           "repro.launch.dryrun", "repro.launch.roofline",
           "repro.launch.report", "repro.launch.mesh",
           "repro.launch.sharding", "repro.launch.train",
           "repro.ckpt.elastic", "repro.data.tokens", "repro.data.sampler")


def _sources():
    for base in (SRC, ROOT / "bench", ROOT / "examples"):
        yield from sorted(base.rglob("*.py"))
    yield ROOT / "chip_smoke.py"


def _module_of(path: pathlib.Path) -> str:
    base = SRC.parent if SRC in path.parents else ROOT
    parts = path.relative_to(base).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


def _imports(path: pathlib.Path):
    """Every module an import statement in ``path`` names, with ``from X
    import Y`` also giving ``X.Y`` (Y may be a submodule)."""
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:  # relative: resolve against the package
                pkg = _module_of(path).split(".")
                if path.name != "__init__.py":
                    pkg = pkg[:-1]
                pkg = pkg[:len(pkg) - node.level + 1]
                base = ".".join(pkg + ([base] if base else []))
            yield base
            yield from (f"{base}.{a.name}" for a in node.names)


def _under(name: str, prefix: str) -> bool:
    return name == prefix or name.startswith(prefix + ".")


def test_no_module_imports_a_removed_module():
    bad = [(str(p.relative_to(ROOT)), name) for p in _sources()
           for name in _imports(p)
           if any(_under(name, r) for r in REMOVED)]
    assert not bad, bad
    for r in REMOVED:
        rel = pathlib.Path(*r.split(".")[1:])
        assert not (SRC / rel).exists() and not (SRC / rel).with_suffix(
            ".py").exists(), r


def test_library_layers_do_not_import_the_launchers():
    """``core``, ``kernels``, ``sparse``, ``serve``, ``data``, ``ckpt`` and
    ``testing`` import nothing from ``repro.launch``: checkpointing and
    state I/O sit below the launchers that drive them."""
    bad = [(str(p.relative_to(ROOT)), name)
           for p in sorted(SRC.rglob("*.py"))
           if _module_of(p).split(".")[1:2] != ["launch"]
           for name in _imports(p) if _under(name, "repro.launch")]
    assert not bad, bad
