"""serving -> models -> kernels, arrows one way. ``serving`` calls the
model's protocol and imports ``kernels``; ``models`` import ``kernels``;
``kernels`` import neither. Read from the source with ``ast``, function
bodies included, so a function-local import counts."""

import ast
import pathlib

PKG = pathlib.Path(__file__).resolve().parent.parent / "paddle_tpu"
#: the one import of ``serving`` from below it that is named and stays
#: (ROADMAP Design 3): ``GPTForCausalLM.generate`` -> ``cached_generate``
ALLOWED = {("models/gpt.py", "paddle_tpu.serving.engine", "cached_generate")}


def imports_of(path: pathlib.Path):
    """``(absolute module, imported name)`` of every import in a file,
    relative ones resolved against the file's own package."""
    package = ("paddle_tpu",) + path.relative_to(PKG).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else ()
            module = ".".join(base + ((node.module,) if node.module else ()))
            for a in node.names:
                yield module, a.name


def reaches(module, name, package):
    """Does importing ``name`` from ``module`` reach ``paddle_tpu.<package>``?
    (``from .. import serving`` does, by the name.)"""
    target = f"paddle_tpu.{package}"
    full = module if name is None else f"{module}.{name}"
    return any(m == target or m.startswith(target + ".")
               for m in (module, full))


def offenders(directory, packages):
    return sorted(
        (str(path.relative_to(PKG)), module, name)
        for path in (PKG / directory).rglob("*.py")
        for module, name in imports_of(path)
        if any(reaches(module, name, p) for p in packages))


def test_kernels_import_neither_models_nor_serving():
    assert offenders("kernels", ("models", "serving")) == []


def test_models_and_incubate_reach_serving_through_generate_alone():
    found = offenders("models", ("serving",)) \
        + offenders("incubate", ("serving",))
    assert set(found) == ALLOWED and len(found) == 1, found


def test_kv_cache_is_the_page_manager_and_the_tier_is_defined_once():
    tree = ast.parse((PKG / "serving" / "kv_cache.py").read_text())
    defined = {n.name for n in tree.body
               if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    assert defined == {"PagedKVCache", "_layer_buffers", "_tuple_nbytes"}
    tier = [str(path.relative_to(PKG)) for path in PKG.rglob("*.py")
            for n in ast.walk(ast.parse(path.read_text()))
            if isinstance(n, ast.FunctionDef)
            and n.name == "default_paged_impl"]
    assert tier == ["kernels/tier.py"]
