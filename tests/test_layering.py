"""The package's layers, held by reading the sources (``ast``; nothing is
imported): the hot path takes from above only the program counters,
scopes, spans and flight events the benchmark reads; nothing under
``apex_tpu/`` reaches up to the benchmark, the tests or the smoke; and
everything ``benchmark/`` imports from the package still exists."""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# examples/*/main_amp.py:build() -> amp + parallel -> optimizers / nn /
# transformer / models -> ops/pallas_*
HOT_PATH = ("ops", "multi_tensor_apply", "optimizers", "normalization",
            "amp", "nn", "transformer", "models", "parallel",
            "fp16_utils")
# the half that measures and orchestrates: never imported by the hot path
ABOVE = ("apex_tpu.fleet", "apex_tpu.analysis", "apex_tpu.serving")
# what the hot path may take from observability: submodules whole, and
# these names of the package itself
OBS_MODULES = ("metrics", "phases", "tracing", "flightrec")
OBS_NAMES = {"get_registry", "get_recorder", "span", "event",
             *OBS_MODULES}


def _py_files(*parts):
    top = os.path.join(ROOT, *parts)
    if os.path.isfile(top):
        yield top
        return
    for d, _dirs, files in os.walk(top):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(d, f)


def _module_of(path):
    rel = os.path.relpath(path, ROOT)[:-3].split(os.sep)
    if rel[-1] == "__init__":
        rel.pop()
    return ".".join(rel), path.endswith("__init__.py")


def imports(path):
    """Every import in a file, nested ones too, as ``(module, name)``
    with relative imports resolved: ``import a.b`` gives ``("a.b",
    None)``, ``from .x import y`` in ``p/q.py`` gives ``("p.x", "y")``."""
    mod, is_pkg = _module_of(path)
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                pkg = mod.split(".") if is_pkg else mod.split(".")[:-1]
                pkg = pkg[:len(pkg) - (node.level - 1)]
                base = ".".join(pkg + ([base] if base else []))
            for a in node.names:
                yield base, a.name


def _under(module, name, prefix):
    full = module if name is None else f"{module}.{name}"
    return (module == prefix or module.startswith(prefix + ".")
            or full == prefix)


@pytest.mark.parametrize("package", HOT_PATH)
def test_hot_path_takes_only_counters_and_scopes_from_above(package):
    bad = []
    for path in _py_files("apex_tpu", package):
        where = os.path.relpath(path, ROOT)
        for module, name in imports(path):
            if any(_under(module, name, up) for up in ABOVE):
                bad.append(f"{where}: {module} . {name}")
            elif module == "apex_tpu.observability":
                if name not in OBS_NAMES:
                    bad.append(f"{where}: observability . {name}")
            elif module.startswith("apex_tpu.observability."):
                if module.split(".")[2] not in OBS_MODULES:
                    bad.append(f"{where}: {module} . {name}")
    assert bad == []


def test_package_imports_nothing_that_measures_it():
    """No module under ``apex_tpu/`` imports the benchmark, the tests or
    the smoke: they stand on the package, not beside it."""
    outside = ("benchmark", "bench", "tests", "chip_smoke", "conftest")
    bad = [f"{os.path.relpath(p, ROOT)}: {m}"
           for p in _py_files("apex_tpu") for m, _n in imports(p)
           if m.split(".")[0] in outside]
    assert bad == []


def test_observability_imports_no_orchestration():
    bad = [f"{os.path.relpath(p, ROOT)}: {m} . {n}"
           for p in _py_files("apex_tpu", "observability")
           for m, n in imports(p)
           if any(_under(m, n, up) for up in ABOVE)]
    assert bad == []


def _exists(module, name):
    """``module`` is a file or package of the repo and, if ``name`` is
    given, ``name`` is a submodule of it or bound at its top level."""
    base = os.path.join(ROOT, *module.split("."))
    path = base + ".py" if os.path.isfile(base + ".py") else \
        os.path.join(base, "__init__.py")
    if not os.path.isfile(path):
        return False
    if name is None or name == "*":
        return True
    if os.path.isfile(os.path.join(base, name + ".py")) or \
            os.path.isfile(os.path.join(base, name, "__init__.py")):
        return True
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    bound = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            bound.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            bound.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound.add(node.id)
    return name in bound


def test_everything_the_benchmark_imports_from_the_package_exists():
    """``benchmark/`` may not be edited by a PR that changes the
    package, so a name it imports must not move or go."""
    seen, missing = set(), []
    for path in _py_files("benchmark"):
        for module, name in imports(path):
            if module.split(".")[0] != "apex_tpu":
                continue
            seen.add((module, name))
            if not _exists(module, name):
                missing.append(f"{os.path.relpath(path, ROOT)}: "
                               f"{module} . {name}")
    assert missing == []
    # the surface ISSUE 28 counted, so that a reader sees it grow
    assert seen >= {
        ("apex_tpu.observability", "compilation"),
        ("apex_tpu.observability", "phases"),
        ("apex_tpu.observability", "get_recorder"),
        ("apex_tpu.utils", "configure_compile_cache"),
        ("apex_tpu.ops", "dispatch"),
        ("apex_tpu.transformer", "attention")}
