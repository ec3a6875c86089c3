"""The package's layers, held by reading the sources (``ast``; nothing is
imported): the hot path takes from above only the program counters,
scopes, spans and flight events the benchmark reads; nothing under
``apex_tpu/`` reaches up to the benchmark, the tests or the smoke;
everything ``benchmark/`` imports from the package still exists; every
kernel module is dispatched by the code a chip runs; and every module is
used by the package or an entry point, not by its tests alone."""

import ast
import functools
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


@functools.lru_cache(maxsize=None)
def _tree(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def _from_base(node, path):
    """The module an ``ImportFrom`` of the file at ``path`` names, its
    leading dots resolved."""
    mod, is_pkg = _module_of(path)
    base = node.module or ""
    if node.level:
        pkg = mod.split(".") if is_pkg else mod.split(".")[:-1]
        pkg = pkg[:len(pkg) - (node.level - 1)]
        base = ".".join(pkg + ([base] if base else []))
    return base


def imports(path, within=None):
    """Every import in a file (or in the node ``within`` of it), nested
    ones too, as ``(module, name)`` with relative imports resolved:
    ``import a.b`` gives ``("a.b", None)``, ``from .x import y`` in
    ``p/q.py`` gives ``("p.x", "y")``."""
    for node in ast.walk(_tree(path) if within is None else within):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None
        elif isinstance(node, ast.ImportFrom):
            base = _from_base(node, path)
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


# -- a kernel is dispatched on the chip or it is not in the package --------

def _ops_modules():
    for path in _py_files("apex_tpu", "ops"):
        name = os.path.basename(path)[:-3]
        if name.startswith("pallas_") or name == "row_moves":
            yield name


KERNEL_MODULES = sorted(_ops_modules())
# not behind the dispatch switch, and why: what their callers need is a
# production import, no more
UNGATED = {
    "pallas_common": "the kernels' shared block arithmetic, no kernel",
    "row_moves": "gathers on every backend, chosen from shapes "
                 "(home_by_gathers)",
}
_GATES = {"pallas_enabled", "use_pallas_for"}


def _imports_of(node, path, target):
    """Whether ``node`` (a file's tree or a function's) imports the
    ``apex_tpu.ops`` module ``target``."""
    return any(_under(module, name, f"apex_tpu.ops.{target}")
               for module, name in imports(path, within=node))


def _calls_a_gate(func):
    return any(isinstance(n, ast.Call)
               and isinstance(n.func, ast.Attribute)
               and n.func.attr in _GATES
               and isinstance(n.func.value, ast.Name)
               and n.func.value.id == "dispatch"
               for n in ast.walk(func))


@pytest.mark.parametrize("module", KERNEL_MODULES)
def test_every_kernel_module_has_a_production_dispatch_site(module):
    """Some function outside ``ops/`` and ``analysis/`` (and the tests)
    imports the module and asks ``dispatch.pallas_enabled()`` /
    ``use_pallas_for()`` on the way to it: the switch a chip turns on.
    A kernel reached only by another switch, or only by its own parity
    tests, is not in the package."""
    sites = []
    for path in _py_files("apex_tpu"):
        rel = os.path.relpath(path, ROOT).split(os.sep)
        if rel[1] in ("ops", "analysis"):
            continue
        tree = _tree(path)
        if module in UNGATED:
            if _imports_of(tree, path, module):
                sites.append(os.sep.join(rel))
            continue
        sites += [f"{os.sep.join(rel)}:{n.name}" for n in ast.walk(tree)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and _imports_of(n, path, module) and _calls_a_gate(n)]
    assert sites, f"nothing a chip runs dispatches ops/{module}.py"


def test_the_kernel_inventory_is_the_one_the_docstring_gives():
    """``ops/__init__.py`` lists the kernel modules; the list and the
    directory agree, so a module cannot arrive or go unannounced."""
    with open(os.path.join(ROOT, "apex_tpu", "ops", "__init__.py")) as f:
        doc = ast.get_docstring(ast.parse(f.read()))
    listed = {w for w in doc.split()
              if w.startswith("pallas_") or w == "row_moves"}
    assert listed == set(KERNEL_MODULES)


# -- every module has a user that is not its test ---------------------------

def _file_of(module):
    base = os.path.join(ROOT, *module.split("."))
    if os.path.isfile(base + ".py"):
        return base + ".py"
    init = os.path.join(base, "__init__.py")
    return init if os.path.isfile(init) else None


def _uses(path, bare_counts=False):
    """The package's modules that a file uses: what it imports, and what
    it reaches by attribute through a package it imported
    (``obs.exporters.x``).  A bare ``from . import x`` in an
    ``__init__`` binds ``x`` for others to reach and is not itself a
    use, except in ``apex_tpu/__init__.py`` (``bare_counts``), whose
    lines are the package's declared surface."""
    is_pkg = path.endswith("__init__.py")
    tree = _tree(path)
    used, bound = set(), {}

    def use(module):
        parts = module.split(".")
        used.update(".".join(parts[:i]) for i in range(1, len(parts) + 1))

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "apex_tpu":
                    use(a.name)
                    bound[a.asname or "apex_tpu"] = \
                        a.name if a.asname else "apex_tpu"
        elif isinstance(node, ast.ImportFrom):
            base = _from_base(node, path)
            if base.split(".")[0] != "apex_tpu":
                continue
            bare = is_pkg and node.level and not node.module \
                and not bare_counts
            if not bare:
                use(base)
            for a in node.names:
                sub = f"{base}.{a.name}"
                if _file_of(sub):
                    bound[a.asname or a.name] = sub
                    if not bare:
                        use(sub)
    for node in ast.walk(tree):
        chain = []
        while isinstance(node, ast.Attribute):
            chain.append(node.attr)
            node = node.value
        if chain and isinstance(node, ast.Name) and node.id in bound:
            cur = bound[node.id]
            for attr in reversed(chain):
                if not _file_of(f"{cur}.{attr}"):
                    break
                cur = f"{cur}.{attr}"
                use(cur)
    return {m for m in used if _file_of(m)}


def _entry_points():
    yield os.path.join(ROOT, "chip_smoke.py")
    yield os.path.join(ROOT, "__graft_entry__.py")
    yield from _py_files("examples")
    for path in _py_files("benchmark"):
        if f"{os.sep}tests{os.sep}" not in path:
            yield path
    for path in _py_files("apex_tpu"):      # ``python -m apex_tpu.x``
        if path.endswith("__main__.py"):
            yield path


@functools.lru_cache(maxsize=None)
def _reachable():
    surface = os.path.join(ROOT, "apex_tpu", "__init__.py")
    seen = {_module_of(p)[0] for p in _entry_points()}
    todo = list(_uses(surface, bare_counts=True))
    for path in _entry_points():
        todo += _uses(path)
    while todo:
        m = todo.pop()
        if m not in seen:
            seen.add(m)
            todo += _uses(_file_of(m))
    return seen


# imported by their package for what importing them does
IMPORTED_FOR_EFFECT = {"apex_tpu.analysis.rules": "registers the rule set"}
# ROADMAP.md Design [unused-modules], the part of it that only tests
# reach: this list equals that debt and can only shrink
ONLY_TESTS_USE = {"apex_tpu.utils.hf_interop", "apex_tpu.utils.ema",
                  "apex_tpu.utils.checkpoint_orbax"}

SUBPACKAGES = sorted(
    d for d in os.listdir(os.path.join(ROOT, "apex_tpu"))
    if os.path.isfile(os.path.join(ROOT, "apex_tpu", d, "__init__.py")))


@pytest.mark.parametrize("subpackage", ["."] + SUBPACKAGES)
def test_every_module_is_imported_by_the_package_or_an_entry_point(
        subpackage):
    """Each module is reached from ``apex_tpu/__init__.py``, an example,
    the smoke, the benchmark, the dry run or a ``__main__`` without
    passing through ``tests/``; ``"."`` is the package's own files."""
    if subpackage == ".":
        paths = [os.path.join(ROOT, "apex_tpu", f)
                 for f in sorted(os.listdir(os.path.join(ROOT, "apex_tpu")))
                 if f.endswith(".py")]
    else:
        paths = list(_py_files("apex_tpu", subpackage))
    mine = {_module_of(p)[0] for p in paths}
    unused = mine - _reachable() - set(IMPORTED_FOR_EFFECT)
    assert unused == ONLY_TESTS_USE & mine
