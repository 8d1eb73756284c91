"""The reprolint rule catalogue (RPL001–RPL006).

Each rule mechanises one convention this codebase learned the hard way —
see ``docs/ANALYSIS.md`` for the full catalogue with rationale and fix
recipes, and ``tests/test_analysis.py`` for a caught/clean fixture pair per
rule:

  RPL001  PRNG key reuse (the OPD jit-warmup bug fixed in PR 2)
  RPL002  host-side numerics in jit-pure modules (twin-divergence hazard)
  RPL003  raw mesh ``jax.*`` APIs that bypass ``repro.compat``
  RPL004  spec-safety: ``*Spec`` dataclasses frozen + JSON-round-trip safe
  RPL005  CPU loop-lowering anti-patterns (the PR 5 event-loop lessons)
  RPL006  device→host syncs inside a benchmark's timed region
"""
from __future__ import annotations

import ast
from collections.abc import Iterator

from repro.analysis.framework import (ERROR, WARNING, Rule, SourceModule,
                                      register)

# Modules whose traced code must stay host-free: the jitted twins, the
# policy/PPO jit surface, the measured stage executor, and everything
# models/kernels under jit.
JIT_PURE_FILES = ("core/vecenv.py", "core/runtime_vec.py", "core/ppo.py",
                  "core/policy.py", "cluster/executor.py")
JIT_PURE_DIRS = ("/train/", "/nn/", "/kernels/")

# jax.random callables that *create or derive* keys rather than consume one.
_KEY_MAKERS = frozenset({"PRNGKey", "key", "key_data", "wrap_key_data",
                         "clone", "key_impl", "default_prng_impl"})

# Callables that trace a function handed to them by name.
_TRACE_ENTRY = frozenset({
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.vmap", "jax.pmap", "jax.grad",
    "jax.value_and_grad", "jax.checkpoint", "jax.remat",
})

# Raw API -> the repro.compat helper that must be used instead.
_COMPAT_SHIMS = {
    "jax.sharding.get_abstract_mesh": "repro.compat.ambient_mesh",
    "jax.make_mesh": "repro.compat.make_mesh",
}

_JSON_ATOMS = frozenset({"str", "int", "float", "bool", "None"})
_JSON_CONTAINERS = frozenset({"tuple", "list", "dict", "Tuple", "List",
                              "Dict", "Optional", "Union"})


def is_jit_pure(path: str) -> bool:
    return (path.endswith(JIT_PURE_FILES)
            or any(d in path for d in JIT_PURE_DIRS))


def _walk_no_functions(node: ast.AST) -> Iterator[ast.AST]:
    """ast.walk that does not descend into nested function/lambda bodies
    (they are separate scopes, analyzed on their own)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop()
        yield child
        if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
            stack.extend(ast.iter_child_nodes(child))


# --------------------------------------------------------------- RPL001 --

@register
class KeyReuse(Rule):
    """A ``jax.random`` key passed to two calls without an intervening
    re-bind silently correlates the two draws (PR 2 fixed exactly this in
    the OPD jit-warmup). Every use of a key — including ``split`` — consumes
    it; thread the fresh keys forward instead."""
    code = "RPL001"
    name = "prng-key-reuse"
    severity = ERROR
    description = "jax.random key consumed twice without an intervening split"

    def check(self, mod: SourceModule):
        yield from self._scope(mod, self._body(mod.tree))
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._scope(mod, node.body)

    @staticmethod
    def _body(tree: ast.Module) -> list[ast.stmt]:
        return [s for s in tree.body
                if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef))]

    def _scope(self, mod: SourceModule, body: list[ast.stmt]):
        consumed: dict[str, int] = {}
        yield from self._stmts(mod, body, consumed)

    def _stmts(self, mod, stmts, consumed):
        for stmt in stmts:
            yield from self._stmt(mod, stmt, consumed)

    def _stmt(self, mod, stmt, consumed):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return                          # separate scope
        if isinstance(stmt, ast.If):
            yield from self._exprs(mod, stmt.test, consumed)
            yield from self._branches(mod, [stmt.body, stmt.orelse], consumed)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            yield from self._exprs(mod, stmt.iter, consumed)
            loop_state = dict(consumed)
            targets = _assigned_names(stmt.target, mod)
            for t in targets:
                loop_state.pop(t, None)
            inner = dict(loop_state)
            yield from self._stmts(mod, stmt.body, inner)
            # loop-carried reuse: a key consumed in the body that the body
            # (or the loop target) never re-binds is consumed again on the
            # next iteration
            assigned = set(targets) | _assigned_in(stmt.body, mod)
            for name, line in inner.items():
                if name not in loop_state and name not in assigned:
                    yield (line, f"PRNG key {name!r} is consumed on every "
                                 f"loop iteration without being re-split")
            consumed.clear()
            consumed.update(inner)
            yield from self._stmts(mod, stmt.orelse, consumed)
        elif isinstance(stmt, ast.While):
            yield from self._exprs(mod, stmt.test, consumed)
            inner = dict(consumed)
            yield from self._stmts(mod, stmt.body, inner)
            assigned = _assigned_in(stmt.body, mod)
            for name, line in inner.items():
                if name not in consumed and name not in assigned:
                    yield (line, f"PRNG key {name!r} is consumed on every "
                                 f"loop iteration without being re-split")
            consumed.clear()
            consumed.update(inner)
            yield from self._stmts(mod, stmt.orelse, consumed)
        elif isinstance(stmt, ast.Try):
            for block in [stmt.body, stmt.finalbody, stmt.orelse,
                          *[h.body for h in stmt.handlers]]:
                branch = dict(consumed)
                yield from self._stmts(mod, block, branch)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                yield from self._exprs(mod, item.context_expr, consumed)
            yield from self._stmts(mod, stmt.body, consumed)
        else:
            yield from self._exprs(mod, stmt, consumed)
            for name in _assigned_names(stmt, mod):
                consumed.pop(name, None)

    def _branches(self, mod, blocks, consumed):
        """Run each branch on a copy; keep only consumptions common to all
        branches (conservative: never flags across exclusive branches)."""
        results = []
        for block in blocks:
            branch = dict(consumed)
            yield from self._stmts(mod, block, branch)
            results.append(branch)
        keep = set(results[0])
        for r in results[1:]:
            keep &= set(r)
        consumed.clear()
        for name in keep:
            consumed[name] = results[0][name]

    def _exprs(self, mod, node, consumed):
        """Track jax.random consumption inside one statement/expression."""
        shadowed: set[str] = set()
        for sub in ast.walk(node) if not isinstance(node, ast.stmt) else \
                _walk_no_functions(node):
            if isinstance(sub, (ast.ListComp, ast.SetComp, ast.DictComp,
                                ast.GeneratorExp)):
                for gen in sub.generators:
                    shadowed |= _assigned_names(gen.target, mod)
            if not isinstance(sub, ast.Call):
                continue
            fn = mod.resolve(sub.func)
            if not fn or not fn.startswith("jax.random."):
                continue
            if fn.rsplit(".", 1)[1] in _KEY_MAKERS:
                continue
            key = sub.args[0] if sub.args else None
            if key is None:
                for kw in sub.keywords:
                    if kw.arg == "key":
                        key = kw.value
            name = mod.dotted(key) if key is not None else None
            if name is None or name.split(".")[0] in shadowed:
                continue
            if name in consumed:
                yield (sub, f"PRNG key {name!r} reused (already consumed at "
                            f"line {consumed[name]}); split it and use the "
                            f"fresh subkey")
            else:
                consumed[name] = sub.lineno


def _assigned_names(node: ast.AST, mod: SourceModule) -> set[str]:
    """Names (re)bound by a statement or assignment target."""
    out: set[str] = set()
    targets: list[ast.AST] = []
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
        targets = [node.target]
    elif isinstance(node, (ast.Name, ast.Attribute, ast.Tuple, ast.List,
                           ast.Starred)):
        targets = [node]
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            for el in t.elts:
                out |= _assigned_names(el, mod)
        elif isinstance(t, ast.Starred):
            out |= _assigned_names(t.value, mod)
        else:
            name = mod.dotted(t)
            if name:
                out.add(name)
    if isinstance(node, ast.stmt):
        for sub in _walk_no_functions(node):
            if isinstance(sub, ast.NamedExpr):
                out |= _assigned_names(sub.target, mod)
    return out


def _assigned_in(body: list[ast.stmt], mod: SourceModule) -> set[str]:
    out: set[str] = set()
    for stmt in body:
        out |= _assigned_names(stmt, mod)
        for sub in _walk_no_functions(stmt):
            if isinstance(sub, ast.stmt):
                out |= _assigned_names(sub, mod)
    return out


# --------------------------------------------------------------- RPL002 --

@register
class HostNumerics(Rule):
    """Host-side numerics inside traced code of a jit-pure module either
    fail at trace time or — worse — silently bake a trace-time constant
    into the compiled twin, diverging it from the Python reference."""
    code = "RPL002"
    name = "host-numerics-in-traced-code"
    severity = ERROR
    description = "host-side numerics in a jit-pure module's traced code"

    def check(self, mod: SourceModule):
        if not is_jit_pure(mod.path):
            return
        # module-level acknowledgment: importing numpy/time into a jit-pure
        # module is legal only for host-side pre/post-processing — demand an
        # inline suppression stating why
        for node in mod.tree.body:
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name in ("numpy", "time"):
                        yield (node, f"jit-pure module imports {a.name!r}; "
                               f"keep host-side use out of traced code and "
                               f"acknowledge with a reprolint suppression")
            elif (isinstance(node, ast.ImportFrom)
                  and node.module in ("numpy", "time")):
                yield (node, f"jit-pure module imports from {node.module!r}; "
                       f"keep host-side use out of traced code and "
                       f"acknowledge with a reprolint suppression")
        for fn in _traced_functions(mod):
            yield from self._check_traced(mod, fn)

    def _check_traced(self, mod: SourceModule, fn: ast.FunctionDef):
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                callee = mod.resolve(node.func)
                if callee and callee.startswith("time."):
                    yield (node, f"host clock call {callee!r} in traced "
                           f"code — wall time is a trace-time constant "
                           f"under jit")
                if (isinstance(node.func, ast.Name)
                        and node.func.id in ("float", "int", "bool")
                        and node.args
                        and not isinstance(node.args[0], ast.Constant)):
                    yield (node, f"host-side {node.func.id}() cast in "
                           f"traced code forces a device sync and fails "
                           f"under jit; use jnp casts/astype")
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item"
                        and not node.args):
                    yield (node, ".item() in traced code pulls the value "
                           "to host; keep it as a traced array")
            elif isinstance(node, ast.Attribute):
                ref = mod.resolve(node)
                if ref and (ref == "numpy" or ref.startswith("numpy.")):
                    yield (node, f"NumPy reference {ref!r} in traced code "
                           f"— np arrays freeze to trace-time constants; "
                           f"use jax.numpy")
            elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
                for sub in ast.walk(node.test):
                    if isinstance(sub, ast.Call):
                        callee = mod.resolve(sub.func)
                        if callee and (callee.startswith("jax.numpy.")
                                       or callee.startswith("jax.lax.")
                                       or callee.startswith("jax.nn.")):
                            yield (node, "Python branch on a traced "
                                   "expression; use jnp.where / lax.cond")
                            break


def _traced_functions(mod: SourceModule) -> Iterator[ast.FunctionDef]:
    """Functions whose bodies run under trace: jit-decorated defs, defs
    handed by name to lax control flow / vmap, and every def nested inside
    one of those."""
    handed: set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            fn = mod.resolve(node.func)
            if fn in _TRACE_ENTRY:
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Name):
                        handed.add(arg.id)

    def is_traced(fn: ast.FunctionDef) -> bool:
        if fn.name in handed:
            return True
        for deco in fn.decorator_list:
            ref = mod.resolve(deco)
            if ref in ("jax.jit", "jit"):
                return True
            if isinstance(deco, ast.Call):
                head = mod.resolve(deco.func)
                if head in ("jax.jit", "jit"):
                    return True
                if head in ("functools.partial", "partial") and any(
                        mod.resolve(a) in ("jax.jit", "jit")
                        for a in deco.args):
                    return True
        return False

    def walk(node: ast.AST, inside: bool):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                traced = inside or is_traced(child)
                if traced:
                    yield child
                yield from walk(child, traced)
            else:
                yield from walk(child, inside)

    yield from walk(mod.tree, False)


# --------------------------------------------------------------- RPL003 --

@register
class CompatBypass(Rule):
    """``repro.compat`` holds the project's mesh conventions: meshes with
    Auto axes (``jax.make_mesh`` defaults to Explicit, which the sharding
    rules do not expect) and an ambient-mesh query that returns None
    outside a mesh scope. Raw use of those APIs bypasses both."""
    code = "RPL003"
    name = "compat-shim-bypass"
    severity = ERROR
    description = "raw mesh API bypassing repro.compat"

    def check(self, mod: SourceModule):
        if mod.path.endswith("repro/compat.py"):
            return                      # the shim itself
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    full = f"{node.module}.{a.name}"
                    shim = _COMPAT_SHIMS.get(full)
                    if shim:
                        yield (node, f"import of {full!r} bypasses the "
                               f"compat shim; use {shim}")
            elif isinstance(node, (ast.Attribute, ast.Name)):
                ref = mod.resolve(node)
                shim = _COMPAT_SHIMS.get(ref) if ref else None
                if shim:
                    yield (node, f"raw {ref!r} bypasses the project's mesh "
                           f"conventions; use {shim}")


# --------------------------------------------------------------- RPL004 --

@register
class SpecSafety(Rule):
    """``*Spec`` dataclasses are the bit-for-bit reproducibility contract
    (PR 2): frozen, JSON-safe fields, ``to_dict``/``from_dict`` round-trip.
    A mutable or non-serializable spec breaks replay-from-JSON silently."""
    code = "RPL004"
    name = "spec-safety"
    severity = ERROR
    description = "*Spec dataclass not frozen / not JSON-round-trip safe"

    def check(self, mod: SourceModule):
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.ClassDef)
                    and node.name.endswith("Spec")):
                continue
            if not self._frozen_dataclass(mod, node):
                yield (node, f"{node.name} must be @dataclass(frozen=True) "
                       f"— specs are immutable reproducibility artifacts")
            methods = {n.name for n in node.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            for required in ("to_dict", "from_dict"):
                if required not in methods:
                    yield (node, f"{node.name} must define {required}() — "
                           f"specs round-trip through JSON")
            for field in node.body:
                if (isinstance(field, ast.AnnAssign)
                        and isinstance(field.target, ast.Name)
                        and not self._json_safe(field.annotation)):
                    ann = ast.unparse(field.annotation)
                    yield (field, f"{node.name}.{field.target.id}: {ann} is "
                           f"not JSON-safe; allowed: str/int/float/bool, "
                           f"tuple/list/dict of those, nested *Spec")

    @staticmethod
    def _frozen_dataclass(mod: SourceModule, node: ast.ClassDef) -> bool:
        for deco in node.decorator_list:
            if isinstance(deco, ast.Call):
                head = mod.resolve(deco.func)
                if head in ("dataclasses.dataclass", "dataclass"):
                    return any(k.arg == "frozen"
                               and isinstance(k.value, ast.Constant)
                               and k.value.value is True
                               for k in deco.keywords)
        return False

    @classmethod
    def _json_safe(cls, ann: ast.AST) -> bool:
        if isinstance(ann, ast.Constant):
            if ann.value is None or ann.value is Ellipsis:
                return True
            if isinstance(ann.value, str):        # stringified annotation
                try:
                    return cls._json_safe(
                        ast.parse(ann.value, mode="eval").body)
                except SyntaxError:
                    return False
            return False
        if isinstance(ann, ast.Name):
            return ann.id in _JSON_ATOMS or ann.id.endswith("Spec")
        if isinstance(ann, ast.Attribute):
            return ann.attr in _JSON_ATOMS or ann.attr.endswith("Spec")
        if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
            return cls._json_safe(ann.left) and cls._json_safe(ann.right)
        if isinstance(ann, ast.Subscript):
            head = ann.value
            name = head.id if isinstance(head, ast.Name) else (
                head.attr if isinstance(head, ast.Attribute) else None)
            if name not in _JSON_CONTAINERS:
                return False
            inner = ann.slice
            elts = inner.elts if isinstance(inner, ast.Tuple) else [inner]
            return all(cls._json_safe(e) for e in elts)
        return False


# --------------------------------------------------------------- RPL005 --

@register
class CpuLoopLowering(Rule):
    """PR 5's hard-won CPU XLA lessons: a vmapped dynamic-index ``.at[i]
    .set(payload)`` lowers to a sequential per-env loop, and
    ``sum(cumprod)`` window math lowers to an O(window²) reduce_window.
    Both have documented fast shapes (see core/runtime_vec.py)."""
    code = "RPL005"
    name = "cpu-loop-lowering"
    severity = WARNING
    description = "CPU loop-lowering anti-pattern (dynamic scatter / " \
                  "reduce-window-shaped math)"

    def check(self, mod: SourceModule):
        if not is_jit_pure(mod.path):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            # x.at[<dynamic>].set(payload): scatter with a traced index
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "set"
                    and isinstance(node.func.value, ast.Subscript)
                    and isinstance(node.func.value.value, ast.Attribute)
                    and node.func.value.value.attr == "at"
                    and self._dynamic_index(node.func.value.slice)
                    and node.args
                    and not isinstance(node.args[0], ast.Constant)):
                yield (node, "dynamic-index .at[i].set(payload) is a "
                       "batched dynamic-update-slice — vmapped it "
                       "loop-lowers on CPU XLA; pin an index and gather "
                       "at read time instead (see core/runtime_vec.py)")
            # jnp.sum(... cumprod ...): reduce_window-shaped window math
            callee = mod.resolve(node.func)
            if callee in ("jax.numpy.sum", "numpy.sum"):
                for sub in ast.walk(node):
                    if sub is node:
                        continue
                    if isinstance(sub, ast.Call):
                        inner = mod.resolve(sub.func)
                        inner_name = (inner or "").rsplit(".", 1)[-1]
                        attr = (sub.func.attr
                                if isinstance(sub.func, ast.Attribute)
                                else "")
                        if "cumprod" in (inner_name, attr):
                            yield (node, "sum(cumprod(...)) window math "
                                   "lowers to an O(window²) reduce_window "
                                   "on CPU; use argmin on the bool mask "
                                   "(see core/runtime_vec.py)")
                            break

    @staticmethod
    def _dynamic_index(idx: ast.AST) -> bool:
        parts = idx.elts if isinstance(idx, ast.Tuple) else [idx]
        for p in parts:
            if isinstance(p, (ast.Constant, ast.Slice)):
                continue
            if (isinstance(p, ast.UnaryOp)
                    and isinstance(p.operand, ast.Constant)):
                continue
            return True
        return False


# --------------------------------------------------------------- RPL006 --

# Calls that force a device→host sync (and its transfer) onto the clock.
_SYNC_CALLS = frozenset({"numpy.asarray", "numpy.array", "jax.device_get"})
# The shared min-of-k helpers; functions handed to them by name are timed.
_TIMING_HELPERS = ("time_fn", "time_interleaved")


@register
class TimedRegionSync(Rule):
    """A device→host sync (``.item()``, ``np.asarray`` on a device value,
    ``jax.device_get``) inside a benchmark's timed region bills the
    transfer and the forced pipeline flush to the thing being measured.
    Syncs belong outside the clock; inside it, only ``jax.
    block_until_ready`` (what ``repro.timing`` already does) may wait.

    Timed regions are (a) statements between ``t0 = time.perf_counter()``
    and the first statement that reads ``t0`` back, and (b) bodies of
    functions handed by name to ``time_fn`` / ``time_interleaved``."""
    code = "RPL006"
    name = "sync-in-timed-region"
    severity = ERROR
    description = "device→host sync inside a benchmark's timed region"

    def check(self, mod: SourceModule):
        if "benchmarks/" not in mod.path:
            return
        timed_fns = self._handed_to_timers(mod)
        for body in self._stmt_lists(mod.tree):
            yield from self._perf_counter_regions(mod, body)
        for node in ast.walk(mod.tree):
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name in timed_fns):
                for stmt in node.body:
                    yield from self._syncs(mod, stmt)

    @staticmethod
    def _stmt_lists(tree: ast.Module):
        """Every list of statements in the module (module body, function
        bodies, loop/branch/with bodies) — perf_counter windows live
        within one such list."""
        yield tree.body
        for node in ast.walk(tree):
            for attr in ("body", "orelse", "finalbody"):
                block = getattr(node, attr, None)
                if (block and isinstance(block, list)
                        and not isinstance(node, ast.Module)
                        and isinstance(block[0], ast.stmt)):
                    yield block

    def _perf_counter_regions(self, mod: SourceModule, body):
        """Flag syncs between ``t = time.perf_counter()`` and the first
        statement reading ``t`` (the stop-the-clock statement)."""
        i = 0
        while i < len(body):
            started = self._perf_start(mod, body[i])
            i += 1
            if not started:
                continue
            while i < len(body) and not self._reads(mod, body[i], started):
                yield from self._syncs(mod, body[i])
                i += 1

    @staticmethod
    def _perf_start(mod: SourceModule, stmt) -> str | None:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and isinstance(stmt.value, ast.Call)):
            callee = mod.resolve(stmt.value.func)
            if callee in ("time.perf_counter", "time.monotonic", "time.time"):
                return stmt.targets[0].id
        return None

    @staticmethod
    def _reads(mod: SourceModule, stmt, name: str) -> bool:
        return any(isinstance(n, ast.Name) and n.id == name
                   for n in ast.walk(stmt))

    def _handed_to_timers(self, mod: SourceModule) -> set[str]:
        """Names of module functions passed (anywhere in the argument
        expressions) to the shared timing helpers."""
        defined = {n.name for n in ast.walk(mod.tree)
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        handed: set[str] = set()
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = mod.resolve(node.func) or ""
            if callee.rsplit(".", 1)[-1] not in _TIMING_HELPERS:
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name) and sub.id in defined:
                        handed.add(sub.id)
        return handed

    def _syncs(self, mod: SourceModule, stmt):
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                yield (node, ".item() inside a timed region forces a "
                       "device→host sync onto the clock; hoist it out of "
                       "the timed window")
            callee = mod.resolve(node.func)
            if callee in _SYNC_CALLS:
                yield (node, f"{callee}() inside a timed region copies "
                       f"device values to host on the clock; move the "
                       f"conversion outside the timed window")
