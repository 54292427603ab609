"""AST-level lint for TPU footguns in jax training code.

Static checks (no jax import needed to *run* the walker; the mesh-axis
check lazily reads the canonical axis names from ``parallel.mesh``):

- ``host-sync-in-jit`` — ``.item()``, ``jax.device_get`` or
  ``np.asarray``/``np.array`` reached from code that is jit-compiled or
  traced (functions passed to/decorated with ``jax.jit``/``pjit``/
  ``filter_jit``, bodies handed to ``lax.scan``/``fori_loop``/
  ``while_loop``/``shard_map``/``remat``, and anything under
  ``grad``/``value_and_grad``). Each of these forces a device->host
  transfer (or a tracer error) on every step.
- ``unknown-mesh-axis`` — a string literal inside a
  ``PartitionSpec(...)``/``P(...)`` call that is not one of the mesh
  axis names declared in ``parallel.mesh.AXIS_NAMES``. A typo'd axis
  name silently shards nothing.
- ``missing-donate`` — a ``jax.jit`` call site (or decorator) on a
  state-threading function (first parameter named ``state`` /
  ``train_state``) without ``donate_argnums``/``donate_argnames``: the
  step would hold two copies of params + optimizer state in HBM.
- ``no-model-closure-jit`` — in ``midgpt_tpu/serving/`` modules only: a
  ``jax.jit``/``pjit``/``filter_jit`` whose traced function references
  ``model`` as a FREE variable (a closure or global capture) instead of
  taking it as a parameter. Closed over, jax bakes every weight into
  the executable as an HLO constant — and for a quantized model XLA
  constant-folds the dequant back into full f32 matrices, silently
  doubling the weight stream the int8 path halves (the PR 6 bug,
  caught here at the AST level before anything compiles; the
  ``no-dequant-materialization`` HLO rule and the traffic budget gate
  are the compile-time backstops).

Findings are waivable inline with ``# shardlint: disable=<rule>`` (or a
bare ``# shardlint: disable`` for all rules) on the offending line —
waivers are reported but don't fail the pass.

Detection is intentionally static and name-based: it follows references
within one module (a function *named* in a jit/scan call is treated as
traced, transitively through nested defs) but does not build a cross-
module call graph. That bounds false negatives at module boundaries and
keeps the pass milliseconds-fast for CI.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
import typing as tp
from pathlib import Path

RULES = {
    "host-sync-in-jit": "host-device sync inside jit/traced code",
    "unknown-mesh-axis": "PartitionSpec axis literal not a declared mesh axis",
    "missing-donate": "jax.jit on a state-threading function without donation",
    "no-model-closure-jit": (
        "serving jit captures the model instead of taking it as a "
        "parameter"
    ),
    "no-unrolled-layer-loop": (
        "serving jit runs a Python for-loop over model layers outside "
        "the models.gpt forward it calls"
    ),
}

# call targets whose function arguments are traced/compiled
_TRACED_ENTRIES = {
    "jit", "pjit", "filter_jit",
    "scan", "fori_loop", "while_loop", "cond", "shard_map",
    "remat", "checkpoint", "grad", "value_and_grad", "vmap", "pmap",
}
# of those, the ones that compile a *top-level* step (donation applies)
_JIT_ENTRIES = {"jit", "pjit", "filter_jit"}

_PRAGMA_RE = re.compile(r"#\s*shardlint:\s*disable(?:=([\w,\-]+))?")

_STATE_PARAM_NAMES = {"state", "train_state"}


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    lineno: int
    rule: str
    message: str
    waived: bool = False

    def __str__(self) -> str:
        tag = " (waived)" if self.waived else ""
        return f"{self.path}:{self.lineno}: [{self.rule}]{tag} {self.message}"


def _mesh_axis_names() -> tp.FrozenSet[str]:
    from midgpt_tpu.parallel.mesh import AXIS_NAMES

    return frozenset(AXIS_NAMES)


def _dotted(node: ast.AST) -> str:
    """'jax.lax.scan' for nested attributes, 'jit' for bare names."""
    parts: tp.List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _tail(dotted: str) -> str:
    return dotted.rsplit(".", 1)[-1]


def _pragma_waivers(source: str) -> tp.Dict[int, tp.FrozenSet[str]]:
    """line -> rules waived on that line ({'*'} = all)."""
    out: tp.Dict[int, tp.FrozenSet[str]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _PRAGMA_RE.search(tok.string)
            if not m:
                continue
            rules = (
                frozenset(x.strip() for x in m.group(1).split(","))
                if m.group(1)
                else frozenset({"*"})
            )
            out[tok.start[0]] = out.get(tok.start[0], frozenset()) | rules
    except tokenize.TokenizeError:  # pragma: no cover — ast.parse catches 1st
        pass
    return out


def _string_literals(node: ast.AST) -> tp.Iterator[tp.Tuple[str, int]]:
    """(string, lineno) for every str constant under ``node`` (through
    tuples/lists), e.g. the axes of ``P(None, ('replica', 'fsdp'), 'seq')``."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            yield sub.value, sub.lineno


class _ModuleLint:
    def __init__(self, path: str, tree: ast.Module, axis_names: tp.FrozenSet[str]):
        self.path = path
        self.tree = tree
        self.axis_names = axis_names
        self.findings: tp.List[tp.Tuple[int, str, str]] = []
        # every def in the module, by name (last one wins — good enough
        # for the intra-module reference following we do)
        self.defs: tp.Dict[str, ast.AST] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs[node.name] = node

    def add(self, lineno: int, rule: str, message: str) -> None:
        self.findings.append((lineno, rule, message))

    # -- traced-region discovery -------------------------------------------

    def _traced_roots(self) -> tp.List[ast.AST]:
        roots: tp.List[ast.AST] = []
        seen: tp.Set[int] = set()
        names: tp.Set[str] = set()

        def mark(node: tp.Optional[ast.AST]) -> None:
            if node is None:
                return
            if isinstance(node, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef)):
                if id(node) not in seen:
                    seen.add(id(node))
                    roots.append(node)
            elif isinstance(node, ast.Name):
                names.add(node.id)

        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                entry = _tail(_dotted(node.func))
                if entry in _TRACED_ENTRIES:
                    for arg in node.args:
                        mark(arg)
                elif entry == "partial" and node.args:
                    if _tail(_dotted(node.args[0])) in _TRACED_ENTRIES:
                        for arg in node.args[1:]:
                            mark(arg)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    d = deco.func if isinstance(deco, ast.Call) else deco
                    entry = _tail(_dotted(d))
                    if entry in _TRACED_ENTRIES:
                        mark(node)
                    elif entry == "partial" and isinstance(deco, ast.Call):
                        if deco.args and _tail(_dotted(deco.args[0])) in _TRACED_ENTRIES:
                            mark(node)
        # transitively include defs referenced by name from marked code:
        # jax.jit(wrapped) -> wrapped -> step_fn(...) called inside
        frontier = list(names)
        resolved: tp.Set[str] = set()
        while frontier:
            name = frontier.pop()
            if name in resolved:
                continue
            resolved.add(name)
            node = self.defs.get(name)
            if node is None or id(node) in seen:
                continue
            seen.add(id(node))
            roots.append(node)
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id in self.defs:
                    frontier.append(sub.id)
        return roots

    # -- rules --------------------------------------------------------------

    def check_host_sync(self) -> None:
        reported: tp.Set[tp.Tuple[int, str]] = set()
        for root in self._traced_roots():
            for node in ast.walk(root):
                if not isinstance(node, ast.Call):
                    continue
                msg = None
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    msg = ".item() forces a device->host sync in traced code"
                else:
                    dotted = _dotted(node.func)
                    if _tail(dotted) == "device_get":
                        msg = f"{dotted}() forces a device->host sync in traced code"
                    elif dotted in (
                        "np.asarray", "np.array", "numpy.asarray",
                        "numpy.array", "onp.asarray", "onp.array",
                    ):
                        msg = (
                            f"{dotted}() on a traced value forces a host "
                            "round-trip (use jnp instead)"
                        )
                if msg and (node.lineno, msg) not in reported:
                    reported.add((node.lineno, msg))
                    self.add(node.lineno, "host-sync-in-jit", msg)

    def check_mesh_axes(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if _tail(_dotted(node.func)) not in ("P", "PartitionSpec"):
                continue
            for arg in node.args:
                for s, lineno in _string_literals(arg):
                    if s not in self.axis_names:
                        self.add(
                            lineno,
                            "unknown-mesh-axis",
                            f"PartitionSpec axis {s!r} is not a mesh axis "
                            f"(declared: {sorted(self.axis_names)})",
                        )

    def _first_param(self, fn: tp.Optional[ast.AST]) -> tp.Optional[str]:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return None
        args = fn.args.args
        return args[0].arg if args else None

    def check_missing_donate(self) -> None:
        def has_donate(call: ast.Call) -> bool:
            return any(
                kw.arg in ("donate_argnums", "donate_argnames")
                for kw in call.keywords
            )

        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                if _tail(_dotted(node.func)) not in _JIT_ENTRIES:
                    continue
                target = node.args[0] if node.args else None
                fn = (
                    self.defs.get(target.id)
                    if isinstance(target, ast.Name)
                    else target
                )
                first = self._first_param(fn)
                if first in _STATE_PARAM_NAMES and not has_donate(node):
                    self.add(
                        node.lineno,
                        "missing-donate",
                        f"jax.jit on state-threading function "
                        f"(first param {first!r}) without donate_argnums — "
                        "the step holds two copies of the state in HBM",
                    )
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                first = self._first_param(node)
                if first not in _STATE_PARAM_NAMES:
                    continue
                for deco in node.decorator_list:
                    d = deco.func if isinstance(deco, ast.Call) else deco
                    entry = _tail(_dotted(d))
                    donated = isinstance(deco, ast.Call) and (
                        has_donate(deco)
                        or any(  # @partial(jax.jit, donate_argnums=...)
                            kw.arg in ("donate_argnums", "donate_argnames")
                            for kw in deco.keywords
                        )
                    )
                    applies = entry in _JIT_ENTRIES or (
                        entry == "partial"
                        and isinstance(deco, ast.Call)
                        and deco.args
                        and _tail(_dotted(deco.args[0])) in _JIT_ENTRIES
                    )
                    if applies and not donated:
                        self.add(
                            deco.lineno,
                            "missing-donate",
                            f"jit-decorated state-threading function "
                            f"{node.name!r} without donate_argnums",
                        )

    def check_model_closure(self) -> None:
        """``no-model-closure-jit``: any jitted function in a serving
        module that references the model as a free variable. The PR 6
        bug class, caught before a single compile: jax bakes a captured
        model's weights into the executable as constants (and constant-
        folds a quantized model's dequant back to full f32 matrices)."""
        def flag_if_captured(fn: tp.Optional[ast.AST], lineno: int,
                             desc: str) -> None:
            if fn is None:
                return
            captured = _free_names(fn) & _MODEL_NAMES
            if captured:
                self.add(
                    lineno,
                    "no-model-closure-jit",
                    f"{desc} captures {sorted(captured)} from the "
                    "enclosing scope instead of taking it as a "
                    "parameter — jit bakes the weights into the "
                    "executable as constants (and constant-folds a "
                    "quantized model's dequant back to f32)",
                )

        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                if _tail(_dotted(node.func)) not in _JIT_ENTRIES:
                    continue
                target = node.args[0] if node.args else None
                fn = (
                    self.defs.get(target.id)
                    if isinstance(target, ast.Name)
                    else target
                )
                name = (
                    target.id
                    if isinstance(target, ast.Name)
                    else "<lambda>"
                )
                flag_if_captured(
                    fn, node.lineno, f"jitted function {name!r}"
                )
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    d = deco.func if isinstance(deco, ast.Call) else deco
                    entry = _tail(_dotted(d))
                    applies = entry in _JIT_ENTRIES or (
                        entry == "partial"
                        and isinstance(deco, ast.Call)
                        and deco.args
                        and _tail(_dotted(deco.args[0])) in _JIT_ENTRIES
                    )
                    if applies:
                        flag_if_captured(
                            node, deco.lineno,
                            f"jit-decorated function {node.name!r}",
                        )


    def check_unrolled_layer_loop(self) -> None:
        """``no-unrolled-layer-loop`` (serving modules only, waivable):
        a Python-level ``for`` over the model's layers inside jitted/
        traced serving code. Each serving program has exactly one layer
        loop, in the models/ forward it calls
        (``models.gpt.decode_step_paged``, ``prefill_chunk_paged``,
        ``verify_tokens_paged``); a second one in a serving program
        body would run the layers twice or fork the path, silently
        (zero byte movement, so only the dispatch budget or this lint
        sees it). This rule scopes to ``midgpt_tpu/serving/`` where
        program BODIES live."""
        def mentions_layers(node: ast.AST) -> bool:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Attribute) and sub.attr == "n_layer":
                    return True
                if isinstance(sub, ast.Name) and sub.id == "n_layer":
                    return True
            return False

        reported: tp.Set[int] = set()
        for root in self._traced_roots():
            for node in ast.walk(root):
                if not isinstance(node, (ast.For, ast.AsyncFor)):
                    continue
                if node.lineno in reported:
                    continue
                if mentions_layers(node.iter):
                    reported.add(node.lineno)
                    self.add(
                        node.lineno,
                        "no-unrolled-layer-loop",
                        "Python for-loop over model layers in a traced "
                        "serving body — the layer loop belongs in the "
                        "models.gpt forward the program calls, one per "
                        "program (gated by analysis.dispatch budgets)",
                    )


def _free_names(fn: ast.AST) -> tp.Set[str]:
    """Names a function LOADS but never binds — its closure/global
    captures, to the static approximation one module allows. Scope-
    aware: each nested def/lambda is resolved in ITS OWN scope first
    (its params and local Stores bind only there), and only its
    residual free names propagate out — so a nested helper's `model`
    parameter neither hides an enclosing capture nor fabricates one."""
    bound: tp.Set[str] = set()
    loaded: tp.Set[str] = set()
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        a = fn.args
        for arg in [
            *a.args, *a.kwonlyargs, *getattr(a, "posonlyargs", []),
        ]:
            bound.add(arg.arg)
        if a.vararg:
            bound.add(a.vararg.arg)
        if a.kwarg:
            bound.add(a.kwarg.arg)

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                bound.add(child.name)
                loaded.update(_free_names(child))
                # decorators and defaults evaluate in THIS scope
                for d in child.decorator_list:
                    visit(d)
                for d in [
                    *child.args.defaults,
                    *[x for x in child.args.kw_defaults if x],
                ]:
                    visit(d)
                continue
            if isinstance(child, ast.Lambda):
                loaded.update(_free_names(child))
                for d in [
                    *child.args.defaults,
                    *[x for x in child.args.kw_defaults if x],
                ]:
                    visit(d)
                continue
            if isinstance(child, ast.Name):
                if isinstance(child.ctx, (ast.Store, ast.Del)):
                    bound.add(child.id)
                else:
                    loaded.add(child.id)
            elif isinstance(child, (ast.Import, ast.ImportFrom)):
                for alias in child.names:
                    bound.add((alias.asname or alias.name).split(".")[0])
            visit(child)

    visit(fn)
    return loaded - bound


# the captured names the serving closure rule flags: the model pytree
# must always be an ENTRY PARAMETER of a jitted serving program
_MODEL_NAMES = {"model", "qmodel"}


def lint_source(source: str, path: str = "<string>") -> tp.List[Finding]:
    """Lint one module's source text."""
    tree = ast.parse(source, filename=path)
    lint = _ModuleLint(path, tree, _mesh_axis_names())
    lint.check_host_sync()
    lint.check_mesh_axes()
    lint.check_missing_donate()
    # the model-closure rule covers the serving package — where every
    # jitted program's model MUST be an entry parameter (engine.py's
    # program cache and the int8 path both depend on it) — plus the
    # train-side jit sites (train.py, bench.py): a train program that
    # closes over params would silently constant-fold the whole model
    # into the executable and break donation, exactly the PR 6 serving
    # bug class on the other side of the fence. Trainers legitimately
    # close over config-derived structures; only _MODEL_NAMES trip it.
    if (
        "serving" in Path(path).parts
        or Path(path).name in ("train.py", "bench.py")
    ):
        lint.check_model_closure()
    if "serving" in Path(path).parts:
        # the layer-loop rule stays serving-scoped: serving program
        # bodies must take the scan fold; the models/ drivers keep
        # their unrolled branch as the fold's bitwise reference, and
        # train.py's loop structure is gated semantically by the
        # train dispatch budget instead
        lint.check_unrolled_layer_loop()
    waivers = _pragma_waivers(source)
    findings = []
    for lineno, rule, message in sorted(lint.findings):
        waived_rules = waivers.get(lineno, frozenset())
        findings.append(Finding(
            path=path,
            lineno=lineno,
            rule=rule,
            message=message,
            waived="*" in waived_rules or rule in waived_rules,
        ))
    return findings


def lint_paths(paths: tp.Iterable[tp.Union[str, Path]]) -> tp.List[Finding]:
    """Lint every ``.py`` file under the given files/directories."""
    files: tp.List[Path] = []
    for p in paths:
        p = Path(p)
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        else:
            files.append(p)
    findings: tp.List[Finding] = []
    for f in files:
        findings.extend(lint_source(f.read_text(), str(f)))
    return findings


def unwaived(findings: tp.Iterable[Finding]) -> tp.List[Finding]:
    return [f for f in findings if not f.waived]
