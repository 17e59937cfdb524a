"""sharding-spec — mesh-axis hygiene for pjit/shard_map machinery.

GSPMD fails late and cryptically: a ``PartitionSpec`` naming an axis
absent from the mesh raises deep inside lowering (or worse, silently
replicates), ``in_specs`` whose arity disagrees with the mapped
function's signature is a pytree-mismatch stack trace with no source
line, and a bare ``jax.device_put(x)`` inside mesh-aware code pins the
array to the default device and inserts a cross-device copy on first
collective use. All three are visible statically:

* a **project-wide axis registry** is built from every ``Mesh(...)`` /
  ``jax.make_mesh(...)`` construction (tuples of string constants,
  resolved through module-level constants like ``DATA_AXIS = "data"``,
  parameter defaults, and ``*_AXIS``-named string constants);
* every ``PartitionSpec(...)`` / ``P(...)`` site (including inside
  ``with_sharding_constraint``, ``NamedSharding``, ``in_specs``/
  ``out_specs``) is checked against it — axis names that resolve to a
  string not on any mesh are flagged; unresolvable names are skipped
  (silence over guessing);
* ``shard_map`` calls get an arity check: an ``in_specs`` tuple must
  match the mapped function's positional signature, an ``out_specs``
  tuple must match the returned tuple's length;
* ``jax.device_put`` with no explicit sharding inside a function that
  also touches mesh machinery is flagged.
"""

from __future__ import annotations

import ast

from predictionio_tpu.analysis import astutil, jaxast
from predictionio_tpu.analysis.model import Finding
from predictionio_tpu.analysis.source import SourceModule

_MESH_CTORS = {"Mesh", "jax.sharding.Mesh", "sharding.Mesh"}
_MAKE_MESH = {"jax.make_mesh", "make_mesh"}
_PSPEC_DOTTED = {"PartitionSpec", "jax.sharding.PartitionSpec"}
_WSC = "with_sharding_constraint"

#: call targets that mark the enclosing function as mesh-aware
_MESH_MARKERS = _MESH_CTORS | _MAKE_MESH | {
    "NamedSharding",
    "jax.sharding.NamedSharding",
    "shard_map",
    "jax.shard_map",
}


class _Registry:
    """Project-wide mesh axis names + per-module string constants."""

    def __init__(self, modules: list[SourceModule]):
        self.axes: set[str] = set()
        #: rel_path -> {name: str value} for module-level constants
        self.module_consts: dict[str, dict[str, str]] = {}
        #: bare name -> set of values across the project
        self.global_consts: dict[str, set[str]] = {}
        #: rel_path -> every name the module assigns anywhere; a name
        #: bound locally must never resolve through another module's
        #: same-named constant (silence over guessing)
        self.assigned_names: dict[str, set[str]] = {}
        for mod in modules:
            self._collect_consts(mod)
        for mod in modules:
            self._collect_meshes(mod)

    def _collect_consts(self, mod: SourceModule) -> None:
        index = mod.index()
        consts: dict[str, str] = {}
        assigned: set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Name) and isinstance(
                node.ctx, ast.Store
            ):
                assigned.add(node.id)
            if not isinstance(node, ast.Assign):
                continue
            if index.context_of(node) != "":
                continue
            if not (
                isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    consts[t.id] = node.value.value
                    self.global_consts.setdefault(t.id, set()).add(
                        node.value.value
                    )
                    if t.id.endswith("_AXIS") or t.id.startswith("AXIS_"):
                        self.axes.add(node.value.value)
        self.module_consts[mod.rel_path] = consts
        self.assigned_names[mod.rel_path] = assigned

    def _collect_meshes(self, mod: SourceModule) -> None:
        index = mod.index()
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = astutil.dotted_name(node.func)
            if name not in _MESH_CTORS and name not in _MAKE_MESH:
                continue
            axis_arg = None
            if len(node.args) >= 2:
                axis_arg = node.args[1]
            for kw in node.keywords:
                if kw.arg == "axis_names":
                    axis_arg = kw.value
            if axis_arg is not None:
                self._add_axes(mod, index, axis_arg, node)

    def _add_axes(self, mod, index, expr, site) -> None:
        for value in _iter_axis_exprs(expr):
            resolved = self.resolve(mod, index, value, site)
            if resolved is not None:
                self.axes.add(resolved)

    def resolve(self, mod, index, expr, site) -> str | None:
        """String value of an axis expression, or None if unknowable."""
        if isinstance(expr, ast.Constant):
            return expr.value if isinstance(expr.value, str) else None
        if not isinstance(expr, ast.Name):
            return None
        consts = self.module_consts.get(mod.rel_path, {})
        if expr.id in consts:
            return consts[expr.id]
        default = _param_default(index, site, expr.id)
        if isinstance(default, ast.Constant) and isinstance(
            default.value, str
        ):
            return default.value
        # cross-module constant (`from mesh import MODEL_AXIS`): only
        # when this module never assigns the name itself — a local
        # `axis = pick_axis()` must stay unresolvable, not borrow an
        # unrelated module's same-named constant
        if expr.id not in self.assigned_names.get(mod.rel_path, set()):
            values = self.global_consts.get(expr.id, set())
            if len(values) == 1:
                return next(iter(values))
        return None


def _iter_axis_exprs(expr: ast.AST):
    """Flatten tuple/list/``tuple(...)`` wrappers into axis elements."""
    if isinstance(expr, (ast.Tuple, ast.List)):
        for elt in expr.elts:
            yield from _iter_axis_exprs(elt)
    elif isinstance(expr, ast.Call) and astutil.dotted_name(
        expr.func
    ) in ("tuple", "list"):
        for a in expr.args:
            yield from _iter_axis_exprs(a)
    elif isinstance(expr, ast.Starred):
        yield from _iter_axis_exprs(expr.value)
    else:
        yield expr


def _param_default(
    index: astutil.FunctionIndex, site: ast.AST, name: str
) -> ast.AST | None:
    """Default value of parameter ``name`` of the function enclosing
    ``site`` (walking outward), used to resolve the
    ``def create(axis_names=(DATA_AXIS, MODEL_AXIS))`` pattern."""
    for scope in jaxast.scope_chain(index.context_of(site)):
        fn = index.funcs.get(scope)
        if fn is None:
            continue
        args = fn.args
        pos = (*args.posonlyargs, *args.args)
        defaults = args.defaults
        offset = len(pos) - len(defaults)
        for i, a in enumerate(pos):
            if a.arg == name and i >= offset:
                return defaults[i - offset]
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if a.arg == name and d is not None:
                return d
    return None


def _pspec_aliases(mod: SourceModule) -> set[str]:
    aliases = set(_PSPEC_DOTTED)
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax.sharding":
            for alias in node.names:
                if alias.name == "PartitionSpec":
                    aliases.add(alias.asname or alias.name)
    return aliases


def check(modules: list[SourceModule]) -> list[Finding]:
    registry = _Registry(modules)
    findings: list[Finding] = []
    for mod in modules:
        index = mod.index()
        aliases = _pspec_aliases(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = astutil.dotted_name(node.func)
            if name in aliases:
                findings.extend(
                    _check_pspec(mod, index, registry, node)
                )
            elif name is not None and name.endswith("shard_map"):
                findings.extend(
                    _check_shard_map(mod, index, node)
                )
            elif name in ("jax.device_put", "device_put"):
                findings.extend(
                    _check_device_put(mod, index, node, aliases)
                )
    return findings


def _check_pspec(
    mod: SourceModule,
    index: astutil.FunctionIndex,
    registry: _Registry,
    call: ast.Call,
) -> list[Finding]:
    if not registry.axes:
        return []  # no mesh anywhere — nothing to validate against
    findings = []
    for arg in call.args:
        if isinstance(arg, ast.Starred):
            continue
        for elt in _iter_axis_exprs(arg):
            if isinstance(elt, ast.Constant) and elt.value is None:
                continue
            resolved = registry.resolve(mod, index, elt, call)
            if resolved is None:
                continue
            if resolved not in registry.axes:
                known = ", ".join(sorted(registry.axes))
                findings.append(
                    _finding(
                        mod, index, elt,
                        f"PartitionSpec names axis {resolved!r} which "
                        f"no mesh defines (known axes: {known})",
                    )
                )
    return findings


def _check_shard_map(
    mod: SourceModule, index: astutil.FunctionIndex, call: ast.Call
) -> list[Finding]:
    findings: list[Finding] = []
    body_fn = None
    if call.args and isinstance(call.args[0], ast.Name):
        body_fn = jaxast.lookup_scope_chain(
            index.funcs, index.context_of(call), call.args[0].id
        )
    in_specs = out_specs = None
    for kw in call.keywords:
        if kw.arg == "in_specs":
            in_specs = kw.value
        elif kw.arg == "out_specs":
            out_specs = kw.value
    if body_fn is None:
        return findings
    if isinstance(in_specs, ast.Tuple) and not body_fn.args.vararg:
        n_params = len(jaxast.param_names(body_fn))
        if len(in_specs.elts) != n_params:
            findings.append(
                _finding(
                    mod, index, in_specs,
                    f"shard_map in_specs has {len(in_specs.elts)} "
                    f"spec(s) but {body_fn.name}() takes {n_params} "
                    "positional parameter(s)",
                )
            )
    if isinstance(out_specs, ast.Tuple):
        n_out = _uniform_return_arity(body_fn)
        if n_out is not None and n_out != len(out_specs.elts):
            findings.append(
                _finding(
                    mod, index, out_specs,
                    f"shard_map out_specs has {len(out_specs.elts)} "
                    f"spec(s) but {body_fn.name}() returns {n_out} "
                    "value(s)",
                )
            )
    return findings


def _uniform_return_arity(fn: ast.AST) -> int | None:
    """Length of the returned tuple when every return in ``fn``'s own
    body is a tuple literal of one consistent length; None otherwise."""
    arity: int | None = None
    for stmt in astutil.walk_statements(fn.body):
        if not isinstance(stmt, ast.Return) or stmt.value is None:
            continue
        if not isinstance(stmt.value, ast.Tuple):
            return None
        n = len(stmt.value.elts)
        if arity is None:
            arity = n
        elif arity != n:
            return None
    return arity


def _check_device_put(
    mod: SourceModule,
    index: astutil.FunctionIndex,
    call: ast.Call,
    aliases: set[str],
) -> list[Finding]:
    if len(call.args) >= 2:
        return []
    if any(kw.arg in ("device", "sharding") for kw in call.keywords):
        return []
    ctx = index.context_of(call)
    fn = index.funcs.get(ctx)
    if fn is None or not _touches_mesh(fn, aliases):
        return []
    return [
        _finding(
            mod, index, call,
            f"jax.device_put without an explicit sharding inside "
            f"mesh-aware function {ctx}() — the array lands on the "
            "default device and is re-laid-out at first collective "
            "use; pass a NamedSharding",
        )
    ]


def _touches_mesh(fn: ast.AST, aliases: set[str]) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = astutil.dotted_name(node.func)
            if name is None:
                continue
            if (
                name in _MESH_MARKERS
                or name in aliases
                or name.endswith(_WSC)
            ):
                return True
        elif isinstance(node, ast.Attribute) and node.attr == "mesh":
            return True
    return False


def _finding(
    mod: SourceModule,
    index: astutil.FunctionIndex,
    node: ast.AST,
    message: str,
) -> Finding:
    line = getattr(node, "lineno", 1)
    return Finding(
        rule="sharding-spec",
        path=mod.rel_path,
        line=line,
        col=getattr(node, "col_offset", 0),
        message=message,
        context=index.context_of(node),
        source=mod.source_line(line),
    )
