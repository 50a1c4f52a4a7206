"""Static-analysis guards for repo-wide conventions.

The repo pins every moved/renamed jax API behind one shim so a jax upgrade
is a one-file change (ROADMAP housekeeping):

* ``shard_map`` and ``axis_size`` — :mod:`repro.compat`;
* ``Compiled.cost_analysis()`` — :func:`repro.compat.compiled_cost_analysis`
  (its return shape has changed between jax releases);
* ``AxisType`` — the :mod:`repro.launch.mesh` ``_make_mesh`` shim.

This test walks the ASTs of every module under ``src/repro/`` and fails on
a direct use outside the owning shim, with the offending file:line, so a
new call site cannot silently reintroduce a version-specific spelling.
"""
import ast
import pathlib

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"

# banned name -> the module(s) allowed to spell it directly
ALLOWED = {
    "shard_map": {"compat.py"},
    "axis_size": {"compat.py"},
    "AxisType": {"launch/mesh.py"},
    "cost_analysis": {"compat.py"},
}


def _jax_rooted(node: ast.Attribute) -> bool:
    """Whether an attribute chain bottoms out at the name ``jax``."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return isinstance(node, ast.Name) and node.id == "jax"


def _violations(path: pathlib.Path, rel: str):
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and \
                node.module.split(".")[0] == "jax":
            for alias in node.names:
                name = alias.name
                if node.module.endswith(".shard_map"):
                    name = "shard_map"
                if name in ALLOWED and rel not in ALLOWED[name]:
                    yield (node.lineno, f"from {node.module} import "
                           f"{alias.name}")
        elif isinstance(node, ast.Import):
            for alias in node.names:
                last = alias.name.split(".")[-1]
                if alias.name.split(".")[0] == "jax" and \
                        last in ALLOWED and rel not in ALLOWED[last]:
                    yield (node.lineno, f"import {alias.name}")
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr == "cost_analysis" and rel not in ALLOWED[attr]:
                yield (node.lineno, f"<compiled>.{attr}() — use "
                       "repro.compat.compiled_cost_analysis")
            elif attr in ("shard_map", "axis_size") and \
                    _jax_rooted(node.func) and rel not in ALLOWED[attr]:
                yield (node.lineno, f"jax…{attr}() — use repro.compat")
        elif isinstance(node, ast.Attribute) and \
                node.attr in ("shard_map", "AxisType") and \
                _jax_rooted(node):
            if node.attr in ALLOWED and rel not in ALLOWED[node.attr]:
                yield (node.lineno, f"jax…{node.attr}")


def test_moved_jax_apis_only_via_compat_shims():
    assert SRC.is_dir(), SRC
    offenders = []
    for path in sorted(SRC.rglob("*.py")):
        rel = path.relative_to(SRC).as_posix()
        offenders.extend(f"src/repro/{rel}:{line}: {what}"
                         for line, what in _violations(path, rel))
    assert not offenders, (
        "moved jax APIs must go through repro.compat / repro.launch.mesh "
        "(one-file jax upgrades):\n  " + "\n  ".join(offenders))


def test_guard_catches_a_planted_violation(tmp_path):
    """The guard itself must flag each banned spelling (meta-test: an AST
    walker that silently matches nothing would pass the test above)."""
    planted = tmp_path / "planted.py"
    planted.write_text(
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "from jax.sharding import AxisType\n"
        "def f(compiled):\n"
        "    ca = compiled.cost_analysis()\n"
        "    n = jax.lax.axis_size('data')\n"
        "    return jax.shard_map, ca, n\n")
    found = {what for _, what in _violations(planted, "planted.py")}
    assert len(found) == 5, found


# asking for devices initialises the backend: on a TPU host that claims the
# chip for the importing process, so no module may do it while imported
_DEVICE_PROBES = {"devices", "local_devices", "device_count",
                  "default_backend"}


def _import_time_probes(tree):
    """Calls of a device probe evaluated at import: in module-level
    statements or in a function's default arguments."""
    roots = [n for n in tree.body
             if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef))]
    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            roots += [d for d in fn.args.defaults + fn.args.kw_defaults
                      if d is not None]
    for root in roots:
        for node in ast.walk(root):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _DEVICE_PROBES:
                yield node.lineno, ast.unparse(node)


def test_no_device_probe_at_import():
    offenders = []
    for path in sorted(SRC.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        offenders.extend(f"{path.relative_to(SRC)}:{line}: {call}"
                         for line, call in _import_time_probes(tree))
    assert not offenders, "\n".join(offenders)
    planted = ast.parse("import jax\nON = jax.devices()\n"
                        "def f(i=not jax.default_backend()):\n    pass\n")
    assert len(list(_import_time_probes(planted))) == 2
