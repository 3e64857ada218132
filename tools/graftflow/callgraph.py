"""graftflow callgraph: the whole-program layer over resolve.ModuleDefs.

Builds, from a package tree, the tables interprocedural dataflow needs:

  functions    every module function / method / self-bound lambda, keyed
               by a repo-relative qualname ("path.py:Class.meth")
  classes      with bases resolved across modules (method lookup walks
               them, a one-file MRO approximation)
  attr types   ``self.attr`` -> candidate classes, from constructor
               assignments (``self.x = Cls(...)``) AND factory return
               unions (``self.x = new_vector_index(...)`` resolves to
               every class the factory's return statements construct)
  lock model   every ``register_lock(..., "name")`` bound to an instance
               attr or module global, ``threading.Condition(self._lock)``
               aliasing, and the unregistered Lock/RLock constructions
               the drift check audits
  jit entries  jit-decorated defs and module-level ``f = jax.jit(g,
               static_argnames=...)`` bindings, with their static
               parameter names resolved against the underlying signature

Resolution is deliberately name-and-type-table based — no class-hierarchy
analysis over bare method names (a ``.get()`` call does NOT resolve to
every class defining ``get``). What the tables cannot resolve is skipped,
an under-approximation documented in docs/static_analysis.md; the runtime
graftsan sanitizers witness whatever static resolution misses.

Pure ``ast`` + stdlib: no JAX, no package imports, picklable (the CI
call-graph cache keys the pickle on file mtimes).
"""

from __future__ import annotations

import ast
import json
import os
import pickle
from typing import Optional

from tools.graftflow import HIERARCHY_PATH, resolve
from tools.graftlint.engine import default_root, iter_python_files

CACHE_VERSION = 1

_LOCK_CTORS = ("threading.Lock", "threading.RLock", "Lock", "RLock")
_CONDITION_CTORS = ("threading.Condition", "Condition")


class FuncInfo:
    """One function-like node (def, async def, or self-bound lambda)."""

    def __init__(self, qual: str, rel: str, module: str,
                 cls: Optional[str], name: str, node) -> None:
        self.qual = qual          # "weaviate_tpu/db/shard.py:Shard.put_object"
        self.rel = rel            # repo-relative posix path
        self.module = module      # dotted module name
        self.cls = cls            # enclosing class name, or None
        self.name = name
        self.node = node

    def symbol(self) -> str:
        """Finding symbol, graftlint qualname style."""
        return f"{self.cls}.{self.name}" if self.cls else self.name

    def params(self) -> list[str]:
        """Parameter names as a CALLER's positional arguments map to them
        (methods drop the bound ``self``)."""
        a = self.node.args if not isinstance(self.node, ast.Lambda) \
            else self.node.args
        names = [p.arg for p in list(a.posonlyargs) + list(a.args)]
        if self.cls is not None and names and names[0] in ("self", "cls"):
            names = names[1:]
        return names


class JitSpec:
    """A jit entry point: its callable name, the static parameter names,
    and the underlying positional signature (to map call-site args)."""

    def __init__(self, name: str, static_names: frozenset,
                 params: tuple) -> None:
        self.name = name
        self.static_names = static_names
        self.params = params


class ModuleInfo:
    def __init__(self, rel: str, name: str, tree: ast.Module) -> None:
        self.rel = rel
        self.name = name                     # dotted module name
        self.tree = tree
        self.defs = resolve.ModuleDefs(tree)
        self.imports: dict[str, str] = {}    # local alias -> dotted module
        self.from_symbols: dict[str, tuple] = {}  # local -> (module, symbol)
        self.module_locks: dict[str, Optional[str]] = {}  # var -> lock name
        self.jit_entries: dict[str, JitSpec] = {}


class Program:
    def __init__(self) -> None:
        self.modules: dict[str, ModuleInfo] = {}        # dotted -> info
        self.modules_by_rel: dict[str, ModuleInfo] = {}
        self.functions: dict[str, FuncInfo] = {}        # qual -> info
        # (module, class) -> ClassDef; bases -> [(module, class), ...]
        self.classes: dict[tuple, ast.ClassDef] = {}
        self.class_bases: dict[tuple, list] = {}
        # (module, class, attr) -> {(module, class), ...}
        self.attr_types: dict[tuple, set] = {}
        # (module, class, attr) -> hierarchy name | None (None=unregistered)
        self.lock_attrs: dict[tuple, Optional[str]] = {}
        self.registered_locks: dict[str, list] = {}     # name -> [sites]
        self.unregistered_locks: list[tuple] = []       # (rel, line, owner)
        self.hierarchy: dict[str, dict] = {}            # name -> table row

    # -- method / class lookup -----------------------------------------------

    def lookup_method(self, module: str, cls: str,
                      name: str, _seen=None) -> Optional[FuncInfo]:
        """The def a bound method call reaches, walking base classes."""
        if _seen is None:
            _seen = set()
        if (module, cls) in _seen or (module, cls) not in self.classes:
            return None
        _seen.add((module, cls))
        mod = self.modules.get(module)
        if mod is not None and (cls, name) in mod.defs.methods:
            return self.functions.get(f"{mod.rel}:{cls}.{name}")
        for base in self.class_bases.get((module, cls), ()):
            hit = self.lookup_method(base[0], base[1], name, _seen)
            if hit is not None:
                return hit
        return None

    def _func(self, module: str, name: str) -> Optional[FuncInfo]:
        mod = self.modules.get(module)
        if mod is None or name not in mod.defs.functions:
            return None
        return self.functions.get(f"{mod.rel}:{name}")

    def _init_of(self, module: str, cls: str) -> Optional[FuncInfo]:
        return self.lookup_method(module, cls, "__init__")

    def _symbol_target(self, module: str, name: str):
        """What a from-imported symbol names in its home module:
        ('func', FuncInfo) | ('class', (module, cls)) | None."""
        mod = self.modules.get(module)
        if mod is None:
            return None
        if name in mod.defs.functions:
            return ("func", self._func(module, name))
        if name in mod.defs.classes:
            return ("class", (module, name))
        if name in mod.from_symbols:          # re-export, one hop
            tm, sym = mod.from_symbols[name]
            if tm != module:
                return self._symbol_target(tm, sym)
        return None

    def _module_of_dotted(self, d: str, mod: ModuleInfo) -> Optional[tuple]:
        """('weaviate_tpu.index.tpu', 'fnname') for a dotted call path like
        ``tpu.fnname`` / ``weaviate_tpu.index.tpu.fnname``, via the import
        aliases of `mod` (longest module prefix wins)."""
        parts = d.split(".")
        if parts[0] in mod.imports:
            parts = mod.imports[parts[0]].split(".") + parts[1:]
        for cut in range(len(parts) - 1, 0, -1):
            cand = ".".join(parts[:cut])
            if cand in self.modules:
                if cut == len(parts) - 1:
                    return (cand, parts[-1])
                return None  # attr chain deeper than module.symbol
        return None

    # -- call resolution -----------------------------------------------------

    def resolve_call(self, call: ast.Call, ctx: FuncInfo,
                     local_types: Optional[dict] = None) -> list[FuncInfo]:
        """Every function a call site can reach, by the documented tiers.
        `local_types` optionally maps local variable names to candidate
        (module, class) types (the caller's own-body constructor
        assignments)."""
        f = call.func
        mod = self.modules.get(ctx.module)
        if mod is None:
            return []
        out: list[FuncInfo] = []
        if isinstance(f, ast.Name):
            nm = f.id
            if nm in mod.defs.functions:
                fi = self._func(ctx.module, nm)
                return [fi] if fi else []
            if nm in mod.defs.classes:
                fi = self._init_of(ctx.module, nm)
                return [fi] if fi else []
            if nm in mod.from_symbols:
                tgt = self._symbol_target(*mod.from_symbols[nm])
                if tgt is None:
                    return []
                if tgt[0] == "func" and tgt[1] is not None:
                    return [tgt[1]]
                if tgt[0] == "class":
                    fi = self._init_of(*tgt[1])
                    return [fi] if fi else []
            return []
        if not isinstance(f, ast.Attribute):
            return []
        meth = f.attr
        bd = resolve.dotted(f.value)
        if bd == "self" and ctx.cls is not None:
            hit = self.lookup_method(ctx.module, ctx.cls, meth)
            if hit is not None:
                out.append(hit)
            else:
                # the self._x callback idiom: anything any method of the
                # class binds to this attribute
                for nm in sorted(mod.defs.self_callbacks.get(
                        (ctx.cls, meth), ())):
                    cb = self.lookup_method(ctx.module, ctx.cls, nm) \
                        or self._func(ctx.module, nm)
                    if cb is not None:
                        out.append(cb)
                for lam in mod.defs.self_lambda_callbacks.get(
                        (ctx.cls, meth), ()):
                    fi = self.functions.get(
                        f"{mod.rel}:{ctx.cls}.<lambda:{lam.lineno}>")
                    if fi is not None:
                        out.append(fi)
            return out
        if bd is not None and bd.startswith("self.") \
                and bd.count(".") == 1 and ctx.cls is not None:
            # self.ATTR.meth(): the attribute-type table (constructor
            # assignments + factory return unions)
            attr = bd.split(".", 1)[1]
            for tm, tc in sorted(self._attr_types_with_bases(
                    ctx.module, ctx.cls, attr)):
                hit = self.lookup_method(tm, tc, meth)
                if hit is not None:
                    out.append(hit)
            return out
        if bd is not None and "." not in bd and local_types \
                and bd in local_types:
            # a local variable typed by its own-body constructor assign
            for tm, tc in sorted(local_types[bd]):
                hit = self.lookup_method(tm, tc, meth)
                if hit is not None:
                    out.append(hit)
            return out
        if bd is not None:
            # module-alias path: tpu._score_rows_topk(...), gmin_scan.gmin_topk
            tgt = self._module_of_dotted(f"{bd}.{meth}", mod)
            if tgt is not None:
                tm, sym = tgt
                r = self._symbol_target(tm, sym)
                if r is not None and r[0] == "func" and r[1] is not None:
                    return [r[1]]
                if r is not None and r[0] == "class":
                    fi = self._init_of(*r[1])
                    return [fi] if fi else []
        return out

    def _attr_types_with_bases(self, module: str, cls: str,
                               attr: str) -> set:
        """attr_types for a class, including what base-class methods
        assigned (a subclass inherits its base's constructor wiring)."""
        out = set(self.attr_types.get((module, cls, attr), ()))
        for base in self.class_bases.get((module, cls), ()):
            out |= self._attr_types_with_bases(base[0], base[1], attr)
        return out

    # -- lock resolution -----------------------------------------------------

    def lock_name(self, expr: ast.AST, ctx: FuncInfo):
        """(kind, name) for a ``with <expr>:`` context expression:
        ('named', hierarchy_name) for a registered lock (Condition
        aliasing already folded), ('unregistered', attr) for a bare
        Lock/RLock this context constructs, (None, None) otherwise."""
        d = resolve.dotted(expr)
        if d is None:
            return (None, None)
        parts = d.split(".")
        if len(parts) == 2 and parts[0] == "self" and ctx.cls is not None:
            key = self._lock_attr_key(ctx.module, ctx.cls, parts[1])
            if key is not None:
                name = self.lock_attrs[key]
                return ("named", name) if name else ("unregistered",
                                                     parts[1])
        if len(parts) == 1:
            mod = self.modules.get(ctx.module)
            if mod is not None and parts[0] in mod.module_locks:
                name = mod.module_locks[parts[0]]
                return ("named", name) if name else ("unregistered",
                                                     parts[0])
        return (None, None)

    def _lock_attr_key(self, module: str, cls: str,
                       attr: str, _seen=None) -> Optional[tuple]:
        if _seen is None:
            _seen = set()
        if (module, cls) in _seen:
            return None
        _seen.add((module, cls))
        if (module, cls, attr) in self.lock_attrs:
            return (module, cls, attr)
        for base in self.class_bases.get((module, cls), ()):
            key = self._lock_attr_key(base[0], base[1], attr, _seen)
            if key is not None:
                return key
        return None

    def jit_spec_for_call(self, call: ast.Call,
                          ctx: FuncInfo) -> Optional[JitSpec]:
        """The JitSpec a call site invokes, if its callee is a jit entry
        (bare name, from-import, or module-alias path)."""
        f = call.func
        mod = self.modules.get(ctx.module)
        if mod is None:
            return None
        if isinstance(f, ast.Name):
            if f.id in mod.jit_entries:
                return mod.jit_entries[f.id]
            if f.id in mod.from_symbols:
                tm, sym = mod.from_symbols[f.id]
                tmod = self.modules.get(tm)
                if tmod is not None:
                    return tmod.jit_entries.get(sym)
            return None
        bd = resolve.dotted(f.value) if isinstance(f, ast.Attribute) \
            else None
        if bd is not None:
            tgt = self._module_of_dotted(f"{bd}.{f.attr}", mod)
            if tgt is not None:
                tmod = self.modules.get(tgt[0])
                if tmod is not None:
                    return tmod.jit_entries.get(tgt[1])
        return None


# -- build ------------------------------------------------------------------

def _module_dotted(rel: str) -> str:
    name = rel[:-3] if rel.endswith(".py") else rel
    name = name.replace("/", ".")
    if name.endswith(".__init__"):
        name = name[: -len(".__init__")]
    return name


def _collect_imports(mi: ModuleInfo, known: set) -> None:
    """Import/ImportFrom anywhere in the module (function-local imports —
    the `_compress_locked` idiom — bind module-wide here, a deliberate
    over-approximation)."""
    pkg = mi.name if mi.rel.endswith("__init__.py") \
        else mi.name.rsplit(".", 1)[0] if "." in mi.name else ""
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                mi.imports[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
                if a.asname is None and a.name in known:
                    # `import x.y.z` binds root `x`, but dotted call
                    # paths through the full name resolve via the known
                    # module table (longest-prefix match)
                    pass
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = pkg.split(".") if pkg else []
                if node.level > 1:
                    up = up[: len(up) - (node.level - 1)]
                base = ".".join(up + ([node.module] if node.module else []))
            for a in node.names:
                if a.name == "*":
                    continue
                local = a.asname or a.name
                full = f"{base}.{a.name}" if base else a.name
                if full in known:
                    mi.imports[local] = full    # `from pkg import module`
                else:
                    mi.from_symbols[local] = (base, a.name)


def _register_lock_name(value: ast.Call) -> Optional[str]:
    """The literal name of a ``register_lock(<ctor>, "name")`` call, or
    '<dynamic>' when non-literal, or None when not a register_lock."""
    fd = resolve.dotted(value.func) or ""
    if fd.split(".")[-1] != "register_lock":
        return None
    if len(value.args) >= 2 and isinstance(value.args[1], ast.Constant) \
            and isinstance(value.args[1].value, str):
        return value.args[1].value
    return "<dynamic>"


def _jit_spec_from(fn_name: str, static_kw: list,
                   underlying) -> JitSpec:
    """Resolve static_argnames/static_argnums keywords against the
    underlying def's positional signature."""
    params: tuple = ()
    if underlying is not None and not isinstance(underlying, ast.Lambda):
        a = underlying.args
        params = tuple(p.arg for p in list(a.posonlyargs) + list(a.args))
    names: set = set()
    for kw in static_kw:
        if kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    names.add(e.value)
        elif kw.arg == "static_argnums":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                        and 0 <= e.value < len(params):
                    names.add(params[e.value])
    return JitSpec(fn_name, frozenset(names), params)


def _jit_static_kwargs(expr: ast.AST) -> Optional[list]:
    """The keyword list carrying static specs for a jit expression:
    ``jax.jit(f, static_argnames=...)`` / ``partial(jax.jit, ...)`` /
    plain ``jax.jit``. None when `expr` is not a jit spelling."""
    d = resolve.dotted(expr)
    if d in ("jax.jit", "jit"):
        return []
    if isinstance(expr, ast.Call):
        f = resolve.dotted(expr.func)
        if f in ("jax.jit", "jit"):
            return list(expr.keywords)
        if f in ("functools.partial", "partial") and expr.args \
                and resolve.is_jit_expr(expr.args[0]):
            return list(expr.keywords)
        inner = _jit_static_kwargs(expr.func)
        if inner is not None:
            return inner + list(expr.keywords)
    return None


def _index_jit_entries(mi: ModuleInfo) -> None:
    for name, fn in mi.defs.functions.items():
        for dec in fn.decorator_list:
            kw = _jit_static_kwargs(dec)
            if kw is not None:
                mi.jit_entries[name] = _jit_spec_from(name, kw, fn)
                break
    for node in mi.tree.body:
        if not isinstance(node, ast.Assign) \
                or not isinstance(node.value, ast.Call):
            continue
        targets = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if not targets:
            continue
        call = node.value
        kw = _jit_static_kwargs(call.func)
        if kw is None and resolve.is_jit_expr(call.func):
            kw = []
        if kw is None:
            continue
        kw = kw + list(call.keywords)
        underlying = None
        if call.args and isinstance(call.args[0], ast.Name):
            underlying = mi.defs.functions.get(call.args[0].id)
        for t in targets:
            mi.jit_entries[t] = _jit_spec_from(t, kw, underlying)


def _scan_class_attrs(prog: Program, mi: ModuleInfo,
                      cls: ast.ClassDef) -> None:
    """Attr types, lock attrs, and Condition aliases from every
    ``self.attr = <expr>`` in the class body."""
    pending_aliases: list[tuple] = []   # (attr, aliased_attr)
    for sub in ast.walk(cls):
        if not isinstance(sub, ast.Assign) \
                or not isinstance(sub.value, ast.Call):
            continue
        value = sub.value
        for t in sub.targets:
            if not (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                continue
            key = (mi.name, cls.name, t.attr)
            lock = _register_lock_name(value)
            if lock is not None:
                prog.lock_attrs[key] = lock
                prog.registered_locks.setdefault(lock, []).append(
                    f"{mi.rel}:{sub.lineno}")
                continue
            fd = resolve.dotted(value.func) or ""
            if fd in _LOCK_CTORS:
                prog.lock_attrs.setdefault(key, None)
                prog.unregistered_locks.append(
                    (mi.rel, sub.lineno, f"{cls.name}.{t.attr}"))
                continue
            if fd in _CONDITION_CTORS:
                arg = resolve.dotted(value.args[0]) if value.args else None
                if arg and arg.startswith("self.") and arg.count(".") == 1:
                    pending_aliases.append((t.attr, arg.split(".", 1)[1]))
                else:
                    prog.lock_attrs.setdefault(key, None)
                    prog.unregistered_locks.append(
                        (mi.rel, sub.lineno, f"{cls.name}.{t.attr}"))
                continue
            # attribute type: constructor call or factory return union
            for tm, tc in _call_result_types(prog, mi, value):
                prog.attr_types.setdefault(key, set()).add((tm, tc))
    for attr, target in pending_aliases:
        # threading.Condition(self._lock): the Condition IS the lock for
        # ordering purposes (`with self._cv:` acquires the same mutex)
        tkey = (mi.name, cls.name, target)
        if tkey in prog.lock_attrs:
            prog.lock_attrs[(mi.name, cls.name, attr)] = \
                prog.lock_attrs[tkey]


def _call_result_types(prog: Program, mi: ModuleInfo,
                       call: ast.Call) -> set:
    """(module, class) candidates for a call's result: the class itself
    for a constructor, or the union of classes a resolvable factory's
    return statements construct (one level — the new_vector_index
    shape)."""
    f = call.func
    d = resolve.dotted(f)
    if d is None:
        return set()
    # constructor?
    cls = _resolve_class_name(prog, mi, d)
    if cls is not None:
        return {cls}
    # factory?
    fn_mi, fn = _resolve_function_name(prog, mi, d)
    if fn is None:
        return set()
    out: set = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and isinstance(node.value, ast.Call):
            rd = resolve.dotted(node.value.func)
            if rd is not None:
                rc = _resolve_class_name(prog, fn_mi, rd)
                if rc is not None:
                    out.add(rc)
    return out


def _resolve_class_name(prog: Program, mi: ModuleInfo,
                        d: str) -> Optional[tuple]:
    if "." not in d:
        if d in mi.defs.classes:
            return (mi.name, d)
        if d in mi.from_symbols:
            tgt = prog._symbol_target(*mi.from_symbols[d])
            if tgt is not None and tgt[0] == "class":
                return tgt[1]
        return None
    tgt = prog._module_of_dotted(d, mi)
    if tgt is not None:
        tmod = prog.modules.get(tgt[0])
        if tmod is not None and tgt[1] in tmod.defs.classes:
            return (tgt[0], tgt[1])
    return None


def _resolve_function_name(prog: Program, mi: ModuleInfo, d: str):
    if "." not in d:
        if d in mi.defs.functions:
            return mi, mi.defs.functions[d]
        if d in mi.from_symbols:
            tm, sym = mi.from_symbols[d]
            tmod = prog.modules.get(tm)
            if tmod is not None and sym in tmod.defs.functions:
                return tmod, tmod.defs.functions[sym]
        return None, None
    tgt = prog._module_of_dotted(d, mi)
    if tgt is not None:
        tmod = prog.modules.get(tgt[0])
        if tmod is not None and tgt[1] in tmod.defs.functions:
            return tmod, tmod.defs.functions[tgt[1]]
    return None, None


def _scan_module_locks(prog: Program, mi: ModuleInfo) -> None:
    for node in mi.tree.body:
        if not isinstance(node, ast.Assign) \
                or not isinstance(node.value, ast.Call):
            continue
        names = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if not names:
            continue
        lock = _register_lock_name(node.value)
        fd = resolve.dotted(node.value.func) or ""
        if lock is not None:
            for n in names:
                mi.module_locks[n] = lock
            prog.registered_locks.setdefault(lock, []).append(
                f"{mi.rel}:{node.lineno}")
        elif fd in _LOCK_CTORS:
            for n in names:
                mi.module_locks[n] = None
                prog.unregistered_locks.append((mi.rel, node.lineno, n))


def build_program(target: str, root: Optional[str] = None,
                  hierarchy_path: str = HIERARCHY_PATH) -> Program:
    target = os.path.realpath(target)
    root = os.path.realpath(root) if root else default_root(target)
    prog = Program()
    try:
        with open(hierarchy_path, encoding="utf-8") as f:
            prog.hierarchy = {e["name"]: e
                              for e in json.load(f).get("locks", [])}
    except (OSError, ValueError):
        prog.hierarchy = {}
    # pass 1: parse + per-module defs
    for abs_path, rel in iter_python_files(target, root):
        try:
            with open(abs_path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
        except (SyntaxError, UnicodeDecodeError, ValueError):
            continue  # graftlint reports unparseable files (JGL999)
        mi = ModuleInfo(rel, _module_dotted(rel), tree)
        prog.modules[mi.name] = mi
        prog.modules_by_rel[rel] = mi
    known = set(prog.modules)
    # pass 2: imports, functions, classes, jit entries, locks
    for mi in prog.modules.values():
        _collect_imports(mi, known)
        _index_jit_entries(mi)
        _scan_module_locks(prog, mi)
        for name, fn in mi.defs.functions.items():
            q = f"{mi.rel}:{name}"
            prog.functions[q] = FuncInfo(q, mi.rel, mi.name, None, name, fn)
        for (cname, mname), fn in mi.defs.methods.items():
            q = f"{mi.rel}:{cname}.{mname}"
            prog.functions[q] = FuncInfo(q, mi.rel, mi.name, cname,
                                         mname, fn)
        for (cname, attr), lams in mi.defs.self_lambda_callbacks.items():
            for lam in lams:
                nm = f"<lambda:{lam.lineno}>"
                q = f"{mi.rel}:{cname}.{nm}"
                prog.functions[q] = FuncInfo(q, mi.rel, mi.name, cname,
                                             nm, lam)
        for cname, cls in mi.defs.classes.items():
            prog.classes[(mi.name, cname)] = cls
    # pass 3: class bases (needs the full class table)
    for mi in prog.modules.values():
        for cname, cls in mi.defs.classes.items():
            bases = []
            for b in cls.bases:
                bd = resolve.dotted(b)
                if bd is None:
                    continue
                bc = _resolve_class_name(prog, mi, bd)
                if bc is not None:
                    bases.append(bc)
            prog.class_bases[(mi.name, cname)] = bases
    # pass 4: attr types + instance lock attrs (needs bases for factories)
    for mi in prog.modules.values():
        for cls in mi.defs.classes.values():
            _scan_class_attrs(prog, mi, cls)
    return prog


# -- mtime-keyed pickle cache (the CI call-graph cache) ----------------------

def _tree_key(target: str, root: str) -> dict:
    key = {}
    for abs_path, rel in iter_python_files(target, root):
        st = os.stat(abs_path)
        key[rel] = (st.st_mtime_ns, st.st_size)
    return key


def load_or_build(target: str, root: Optional[str] = None,
                  cache_path: Optional[str] = None,
                  hierarchy_path: str = HIERARCHY_PATH) -> Program:
    """build_program with an optional pickle cache keyed on the mtime+size
    of every analyzed file (the tier-1/CI gate path — a no-change rerun
    skips the whole parse+index build)."""
    target = os.path.realpath(target)
    root = os.path.realpath(root) if root else default_root(target)
    if not cache_path:
        return build_program(target, root, hierarchy_path)
    key = _tree_key(target, root)
    try:
        with open(cache_path, "rb") as f:
            doc = pickle.load(f)
        if doc.get("version") == CACHE_VERSION and doc.get("key") == key \
                and doc.get("hierarchy_mtime") == _hier_mtime():
            return doc["program"]
    except (OSError, pickle.PickleError, EOFError, AttributeError,
            KeyError, ValueError):
        pass
    prog = build_program(target, root, hierarchy_path)
    try:
        tmp = f"{cache_path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump({"version": CACHE_VERSION, "key": key,
                         "hierarchy_mtime": _hier_mtime(),
                         "program": prog}, f)
        os.replace(tmp, cache_path)
    except OSError:
        pass  # a read-only checkout still analyzes, just uncached
    return prog


def _hier_mtime() -> Optional[int]:
    try:
        return os.stat(HIERARCHY_PATH).st_mtime_ns
    except OSError:
        return None
