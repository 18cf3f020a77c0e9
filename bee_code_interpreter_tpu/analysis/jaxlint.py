"""JAX-aware static lint for the accelerator stack (docs/analysis.md
"Accelerator lint").

asynclint/concurrencylint hold the asyncio control plane; their exclude
lists (``models/``, ``parallel/``, ``ops/``, ``runtime/shim/``) are
exactly the trees THIS linter owns — the two scopes partition the package
so no module ships unlinted by omission. The invariants here are the ones
that silently destroy TPU throughput instead of correctness: a decode
loop that round-trips the device per token, a ``jax.jit`` rebuilt per
call, a step function that copies its whole state pytree because nothing
was donated, a Python branch that forks the trace, a collective whose
axis no mesh ever binds. vLLM-class engines hold these by review; here
they are a tier-1 lint (tests/test_jaxlint.py) with the same explicit
suppression contract as the other self-lints — every sanctioned site
carries a justification, and a stale suppression FAILS.

Rules:

- ``host-sync-in-hot-loop``   a device→host transfer — ``jax.device_get``,
  ``.block_until_ready()``, ``.item()`` / ``np.asarray`` / ``np.array`` /
  ``float()`` / ``int()`` applied to a value the dataflow layer tracks to
  a jitted callable or a ``jnp``/``lax`` producer — inside a loop, or
  anywhere in a method reachable from a class's ``step()`` (the batcher
  hot path: ``step`` itself runs in the serving loop, so everything it
  calls is per-token even without a lexical loop).
- ``jit-in-loop``             ``jax.jit`` / ``jax.pmap`` constructed
  inside a loop body — a fresh wrapper per iteration retraces every time.
- ``retrace-hazard``          ``jax.jit(f)(...)`` called immediately (a
  fresh cache per call), a jit built AND called inside the same function
  body (rebuilt per invocation), or ``static_argnums``/``static_argnames``
  that are not compile-time constants.
- ``missing-donation``        a jitted state-in/state-out function — its
  return includes one of its own parameters (the ``cache``/``params``
  shape) — jitted without ``donate_argnums``/``donate_argnames``: every
  call pays a full copy of the state it threads. ``models/mnist.py``'s
  ``make_train_step`` is the sanctioned spelling.
- ``traced-python-branch``    Python ``if``/``while`` on a TRACED
  parameter's value inside a function that is jitted in the corpus —
  branch-by-value forks the trace (ConcretizationTypeError on abstract
  values, or a silent retrace per branch taken). Shape/dtype/ndim/size
  attributes, ``len()``, and ``is None`` tests are static and sanctioned.
- ``collective-axis-mismatch`` ``lax.psum``/``ppermute``/``all_to_all``/
  ``axis_index``/… with a literal ``axis_name`` that no ``shard_map``/
  ``Mesh``/``pmap``/``PartitionSpec`` in the file binds and no enclosing
  parameter supplies — the call can only ever raise "unbound axis name"
  at trace time, on hardware, far from the edit that broke it.

Approximation stance matches the engine underneath (dataflow.py): paths
over-approximate, values under-approximate — a finding is a real shape in
the code, and the suppression list is where a real-but-sanctioned shape
gets its justification recorded.
"""

from __future__ import annotations

import ast
import dataclasses
from dataclasses import dataclass, field
from pathlib import Path

from bee_code_interpreter_tpu.analysis.asynclint import (
    DEFAULT_EXCLUDES,
    PACKAGE_ROOT,
    Suppression,
    Violation,
)
from bee_code_interpreter_tpu.analysis.inspect import (
    collect_aliases,
    resolve_call_name,
)

#: The derived accelerator scope: exactly the subtrees the asyncio lints
#: exclude (asynclint.DEFAULT_EXCLUDES), so the two lint families
#: partition the package tree — a new module under models/ or parallel/
#: is jaxlint-scoped the moment it exists, and a new top-level package
#: lands in asynclint's derived scope instead.
ACCELERATOR_SCOPE: tuple[str, ...] = DEFAULT_EXCLUDES

_JIT_WRAPPERS = frozenset({"jax.jit", "jax.pmap"})

#: Call roots whose results live on device. jnp/lax/random cover the
#: producers; jax.device_put is an explicit placement; jax.jit results
#: are tracked separately (per-scope jitted-callable sets).
_DEVICE_PRODUCER_PREFIXES = (
    "jax.numpy.",
    "jax.lax.",
    "jax.random.",
    "jax.nn.",
)
_DEVICE_PRODUCERS = frozenset({"jax.device_put", "jax.jit", "jax.pmap"})

#: Host-materialization sinks by dotted call name. float/int are listed
#: builtins; np.asarray/np.array resolve through aliases to numpy.*.
_SYNC_CALLS = frozenset(
    {"numpy.asarray", "numpy.array", "float", "int", "jax.device_get"}
)

_COLLECTIVES: dict[str, int] = {
    # dotted name -> positional index of axis_name when not a kwarg
    "jax.lax.psum": 1,
    "jax.lax.pmean": 1,
    "jax.lax.pmax": 1,
    "jax.lax.pmin": 1,
    "jax.lax.ppermute": 1,
    "jax.lax.all_to_all": 1,
    "jax.lax.all_gather": 1,
    "jax.lax.psum_scatter": 1,
    "jax.lax.axis_index": 0,
    "jax.lax.axis_size": 0,
}

#: Identifier tokens whose absence proves a file cannot contain anything
#: this linter flags — the same cheap pre-scan discipline as
#: ``dataflow.has_dynamic_triggers`` (a jax-free file costs one token
#: scan, no CFG, no class graph).
JAX_TRIGGER_NAMES = frozenset(
    {"jax", "jnp", "lax", "shard_map", "pmap", "jit", "block_until_ready"}
)


# The shipped suppression budget — same contract as the other self-lints:
# every entry names WHY the flagged shape is sound, and an entry that no
# longer matches any violation fails tests/test_jaxlint.py.
SUPPRESSIONS: tuple[Suppression, ...] = (
    Suppression(
        path="models/serving.py",
        rule="host-sync-in-hot-loop",
        reason=(
            "the batcher's step-path transfers are the DESIGNED device/"
            "host split (module docstring): ONE bounded pull per compiled "
            "step — greedy and sampled tokens reduce on device to [B] "
            "int32 before crossing, the full logits rows cross only when "
            "some active row records logprobs/is steered, and the "
            "speculative round pulls [B,gamma+1] predictions once per "
            "gamma+1 tokens — plus per-WINDOW (page-aligned, never "
            "per-token) pulls on the admission prefill paths; host-side "
            "numpy selection is left to what is Python per request (bias, "
            "constraints), which the fixed-shape device programs "
            "deliberately exclude (tests/test_serving.py pins the split)"
        ),
    ),
)


@dataclass
class JaxLintReport:
    violations: list[Violation] = field(default_factory=list)
    suppressed: list[tuple[Violation, Suppression]] = field(default_factory=list)
    stale_suppressions: list[Suppression] = field(default_factory=list)
    files_scanned: int = 0

    @property
    def clean(self) -> bool:
        return not self.violations and not self.stale_suppressions

    def summary(self) -> str:
        lines = [str(v) for v in self.violations]
        lines += [
            f"stale suppression ({s.path} [{s.rule}]): no matching violation"
            for s in self.stale_suppressions
        ]
        return "\n".join(lines) or "clean"


def has_jax_triggers(tree: ast.AST) -> bool:
    """Cheap pre-scan: can this file possibly contain a jax shape? Any
    import of jax/its aliases, or a bare trigger identifier."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id in JAX_TRIGGER_NAMES:
            return True
        if isinstance(node, ast.Attribute) and node.attr in (
            "block_until_ready",
            "device_get",
        ):
            return True
        if isinstance(node, ast.Import) and any(
            alias.name.split(".", 1)[0] == "jax" for alias in node.names
        ):
            return True
        if isinstance(node, ast.ImportFrom) and (node.module or "").split(
            ".", 1
        )[0] == "jax":
            return True
    return False


# --------------------------------------------------------------------------
# shared facts about one file
# --------------------------------------------------------------------------


@dataclass
class _FunctionFacts:
    """What the donation/traced-branch rules need to know about one
    function definition."""

    node: ast.AST
    params: tuple[str, ...]
    returned_params: frozenset[str]  # params appearing bare in a return


def _function_params(func: ast.AST) -> tuple[str, ...]:
    args = func.args
    names = [a.arg for a in (*args.posonlyargs, *args.args)]
    return tuple(n for n in names if n != "self")


def _returned_params(func: ast.AST, params: tuple[str, ...]) -> frozenset[str]:
    """Params whose NAME appears as a bare element of some return value —
    the state-in/state-out shape (``return logits, cache``). Rebinding the
    name first (``cache = update(cache)``) still counts: the function
    threads that state through, which is exactly when donation pays."""
    pset = set(params)
    out: set[str] = set()

    def elements(expr: ast.expr):
        if isinstance(expr, ast.Tuple):
            for e in expr.elts:
                yield from elements(e)
        else:
            yield expr

    for node in ast.walk(func):
        if isinstance(node, ast.Return) and node.value is not None:
            for e in elements(node.value):
                if isinstance(e, ast.Name) and e.id in pset:
                    out.add(e.id)
    return frozenset(out)


def _collect_functions(tree: ast.AST) -> dict[str, _FunctionFacts]:
    """Every FunctionDef in the file keyed by bare name (innermost wins on
    collision — good enough for the factory pattern where the nested def
    is the jit target)."""
    out: dict[str, _FunctionFacts] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            params = _function_params(node)
            out[node.name] = _FunctionFacts(
                node=node,
                params=params,
                returned_params=_returned_params(node, params),
            )
    return out


def _const_str_tuple(expr: ast.expr) -> bool:
    """Is this expression a compile-time constant suitable for
    static_argnums/static_argnames? (int/str constant, or a tuple/list of
    them)."""
    if isinstance(expr, ast.Constant):
        return isinstance(expr.value, (int, str))
    if isinstance(expr, (ast.Tuple, ast.List)):
        return all(
            isinstance(e, ast.Constant) and isinstance(e.value, (int, str))
            for e in expr.elts
        )
    return False


@dataclass
class _JitSite:
    """One ``jax.jit(...)`` call, decomposed."""

    call: ast.Call
    target_name: str | None  # bare name of the jitted function, if a Name
    partial_kwargs: frozenset[str]  # kwargs bound via functools.partial
    static_names: frozenset[str]
    static_nums: frozenset[int]  # positional static_argnums indices
    has_donation: bool
    static_args_constant: bool


def _decompose_jit(call: ast.Call, aliases: dict[str, str]) -> _JitSite | None:
    name = resolve_call_name(call.func, aliases)
    if name not in _JIT_WRAPPERS:
        return None
    target: ast.expr | None = call.args[0] if call.args else None
    partial_kwargs: set[str] = set()
    # unwrap functools.partial(f, **bound): bound kwargs become static
    # Python values at trace time
    if isinstance(target, ast.Call) and resolve_call_name(
        target.func, aliases
    ) in ("functools.partial", "partial"):
        partial_kwargs = {kw.arg for kw in target.keywords if kw.arg}
        target = target.args[0] if target.args else None
    target_name = target.id if isinstance(target, ast.Name) else None
    static_names: set[str] = set()
    static_nums: set[int] = set()
    has_donation = False
    static_constant = True
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            has_donation = True
        elif kw.arg in ("static_argnums", "static_argnames"):
            if not _const_str_tuple(kw.value):
                static_constant = False
                continue
            consts = (
                [kw.value]
                if isinstance(kw.value, ast.Constant)
                else list(kw.value.elts)
            )
            for e in consts:
                if isinstance(e.value, str):
                    static_names.add(e.value)
                elif isinstance(e.value, int):
                    static_nums.add(e.value)
    return _JitSite(
        call=call,
        target_name=target_name,
        partial_kwargs=frozenset(partial_kwargs),
        static_names=frozenset(static_names),
        static_nums=frozenset(static_nums),
        has_donation=has_donation,
        static_args_constant=static_constant,
    )


# --------------------------------------------------------------------------
# loop / hot-path context
# --------------------------------------------------------------------------

_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While)
_COMPREHENSIONS = (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)
_FUNCTIONS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _walk_scope(node: ast.AST):
    """This scope's own nodes, NOT descending into nested defs/lambdas —
    ``ast.walk`` with a ``continue`` on FunctionDef still yields the
    skipped function's descendants, which is exactly the bug class this
    helper exists to avoid (same shape as concurrencylint's
    ``_walk_excluding_nested``)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, _FUNCTIONS):
            continue
        stack.extend(ast.iter_child_nodes(n))


def _call_contexts(
    tree: ast.AST,
) -> dict[int, tuple[bool, tuple[ast.AST, ...]]]:
    """id(Call) -> (lexically inside a loop?, enclosing-function chain,
    outermost first). Loop context resets at function boundaries (a def
    in a loop executes its body only when called), mirroring
    inspect._walk_calls; the comprehension's outermost iterable evaluates
    once and stays out. The full chain (not just the nearest function)
    matters because closures over an outer function's ``axis_name``
    parameter are THE idiom shard_map bodies use."""
    out: dict[int, tuple[bool, tuple[ast.AST, ...]]] = {}
    Chain = tuple[ast.AST, ...]
    stack: list[tuple[ast.AST, bool, Chain]] = [(tree, False, ())]
    while stack:
        node, in_loop, funcs = stack.pop()
        if isinstance(node, ast.Call):
            out[id(node)] = (in_loop, funcs)
        if isinstance(node, _FUNCTIONS):
            inner = (*funcs, node)
            # a def in a loop executes its body only when called; a
            # lambda is almost always invoked where it is written (sort
            # keys, callbacks), so it INHERITS the loop context
            body_loop = in_loop if isinstance(node, ast.Lambda) else False
            for child in ast.iter_child_nodes(node):
                stack.append((child, body_loop, inner))
            continue
        if isinstance(node, _LOOP_NODES):
            body_loop = True
            if isinstance(node, (ast.For, ast.AsyncFor)):
                stack.append((node.iter, in_loop, funcs))
                stack.append((node.target, in_loop, funcs))
                for child in node.orelse:
                    stack.append((child, in_loop, funcs))
                for child in node.body:
                    stack.append((child, body_loop, funcs))
            else:  # While: the test re-evaluates per iteration
                stack.append((node.test, body_loop, funcs))
                for child in node.orelse:
                    stack.append((child, in_loop, funcs))
                for child in node.body:
                    stack.append((child, body_loop, funcs))
            continue
        if isinstance(node, _COMPREHENSIONS):
            for i, gen in enumerate(node.generators):
                stack.append((gen.iter, in_loop if i == 0 else True, funcs))
                for cond in gen.ifs:
                    stack.append((cond, True, funcs))
            if isinstance(node, ast.DictComp):
                stack.append((node.key, True, funcs))
                stack.append((node.value, True, funcs))
            else:
                stack.append((node.elt, True, funcs))
            continue
        for child in ast.iter_child_nodes(node):
            stack.append((child, in_loop, funcs))
    return out


#: Method names that seed a class's hot path: ``step`` is called per
#: decode step by every serving loop, so everything it reaches is
#: per-token work even without a lexical loop around the call site.
HOT_SEEDS = frozenset({"step"})


def _hot_methods(cls: ast.ClassDef) -> dict[str, ast.AST]:
    """Methods reachable from the class's HOT_SEEDS via ``self.m(...)``
    calls — the intra-class call graph BFS."""
    methods: dict[str, ast.AST] = {
        m.name: m
        for m in cls.body
        if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    edges: dict[str, set[str]] = {}
    for name, func in methods.items():
        callees: set[str] = set()
        for node in ast.walk(func):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "self"
                and node.func.attr in methods
            ):
                callees.add(node.func.attr)
        edges[name] = callees
    hot: set[str] = set()
    frontier = [m for m in methods if m in HOT_SEEDS]
    while frontier:
        name = frontier.pop()
        if name in hot:
            continue
        hot.add(name)
        frontier.extend(edges.get(name, ()))
    return {name: methods[name] for name in hot}


# --------------------------------------------------------------------------
# device-value tracking (per function scope, flow-insensitive)
# --------------------------------------------------------------------------


def _class_jit_attrs(cls: ast.ClassDef, aliases: dict[str, str]) -> set[str]:
    """Attribute names any method binds to a jit/pmap result
    (``self._decode = jax.jit(...)``) — callable device programs.
    ``self.X = self.Y`` aliases propagate to a fixpoint (the
    ``self._verify = self._window`` idiom: one compiled program, two
    roles)."""
    out: set[str] = set()
    attr_aliases: list[tuple[str, str]] = []  # (target attr, source attr)
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign):
            self_targets = [
                t.attr
                for t in node.targets
                if isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
            ]
            if not self_targets:
                continue
            if isinstance(node.value, ast.Call) and (
                resolve_call_name(node.value.func, aliases) in _JIT_WRAPPERS
            ):
                out.update(self_targets)
            elif (
                isinstance(node.value, ast.Attribute)
                and isinstance(node.value.value, ast.Name)
                and node.value.value.id == "self"
            ):
                attr_aliases.extend(
                    (t, node.value.attr) for t in self_targets
                )
    changed = True
    while changed:
        changed = False
        for target, source in attr_aliases:
            if source in out and target not in out:
                out.add(target)
                changed = True
    return out


def _is_device_call(
    call: ast.Call,
    aliases: dict[str, str],
    jit_attrs: set[str],
    jitted_names: set[str],
) -> bool:
    """Does this call produce a device value? jnp/lax/random producers,
    jax.device_put, calls THROUGH a jitted attribute/name, and immediate
    ``jax.jit(f)(...)`` invocations."""
    name = resolve_call_name(call.func, aliases)
    if name is not None:
        if name in _DEVICE_PRODUCERS or name.startswith(
            _DEVICE_PRODUCER_PREFIXES
        ):
            return True
        root = name.split(".", 1)[0]
        if root in jitted_names and "." not in name:
            return True
    func = call.func
    if (
        isinstance(func, ast.Attribute)
        and isinstance(func.value, ast.Name)
        and func.value.id == "self"
        and func.attr in jit_attrs
    ):
        return True
    if isinstance(func, ast.Call):
        inner = resolve_call_name(func.func, aliases)
        if inner in _JIT_WRAPPERS:
            return True
    return False


def _device_names_in_scope(
    func: ast.AST,
    aliases: dict[str, str],
    jit_attrs: set[str],
    jitted_names: set[str],
) -> set[str]:
    """Names bound (incl. tuple unpacking) from a device-producing call in
    this function's own statements — the alias set the sink checks test.
    Flow-insensitive union over definitions: over-approximating, the safe
    direction for a hint-grade rule with a suppression ledger."""
    out: set[str] = set()

    def bind_targets(target: ast.expr) -> None:
        if isinstance(target, ast.Name):
            out.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                bind_targets(e)
        elif isinstance(target, ast.Starred):
            bind_targets(target.value)

    # own statements only: a nested def's bindings are ITS scope's names,
    # and letting them leak out would mark same-named host locals here
    for node in _walk_scope(func):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if _is_device_call(node.value, aliases, jit_attrs, jitted_names):
                for t in node.targets:
                    bind_targets(t)
    return out


def _expr_is_deviceish(
    expr: ast.expr,
    device_names: set[str],
    aliases: dict[str, str],
    jit_attrs: set[str],
    jitted_names: set[str],
) -> bool:
    """Is this expression rooted in a tracked device value? A bare name in
    the device set, a subscript/attribute/method chain over one
    (``logits[0, i]``, ``logits[i].sum()``), or directly a
    device-producing call."""
    node = expr
    while True:
        if isinstance(node, (ast.Subscript, ast.Attribute)):
            node = node.value
        elif isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            if _is_device_call(node, aliases, jit_attrs, jitted_names):
                return True
            # a method call on a device value yields a device value
            # (.sum(), .astype(), .reshape(), ...)
            node = node.func.value
        else:
            break
    if isinstance(node, ast.Name):
        return node.id in device_names
    if isinstance(node, ast.Call):
        return _is_device_call(node, aliases, jit_attrs, jitted_names)
    return False


# --------------------------------------------------------------------------
# the per-file walk
# --------------------------------------------------------------------------


def _bound_axes(tree: ast.AST, aliases: dict[str, str]) -> set[str]:
    """Axis names SOME context in this file binds: string literals inside
    ``PartitionSpec``/``P`` calls, ``Mesh``/``make_mesh`` axis tuples,
    ``shard_map``/``pmap`` ``axis_name=`` kwargs, and the string defaults
    of parameters named ``axis_name`` (the default-parameter chain the
    ``*_sharded`` wrappers complete)."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = resolve_call_name(node.func, aliases) or ""
            leaf = name.rsplit(".", 1)[-1]
            if leaf in ("PartitionSpec", "P"):
                for a in node.args:
                    if isinstance(a, ast.Constant) and isinstance(a.value, str):
                        out.add(a.value)
            if leaf in ("Mesh", "make_mesh", "create_device_mesh"):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Constant) and isinstance(
                        sub.value, str
                    ):
                        out.add(sub.value)
            # a collective's own axis_name kwarg is a USE, not a binding —
            # counting it would make every literal self-sanctioning
            if name in _COLLECTIVES:
                continue
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis_names"):
                    for sub in ast.walk(kw.value):
                        if isinstance(sub, ast.Constant) and isinstance(
                            sub.value, str
                        ):
                            out.add(sub.value)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            named = [*args.posonlyargs, *args.args, *args.kwonlyargs]
            defaults = [*args.defaults, *args.kw_defaults]
            for a, d in zip(reversed(named), reversed(defaults)):
                if (
                    a.arg == "axis_name"
                    and isinstance(d, ast.Constant)
                    and isinstance(d.value, str)
                ):
                    out.add(d.value)
    return out


def _enclosing_param_names(funcs: tuple[ast.AST, ...]) -> set[str]:
    """Parameter names visible anywhere in an enclosing-function chain —
    what a closure can legitimately read its axis name from."""
    out: set[str] = set()
    for func in funcs:
        args = getattr(func, "args", None)
        if args is not None:
            out.update(
                a.arg
                for a in (*args.posonlyargs, *args.args, *args.kwonlyargs)
            )
    return out


def _params_without_defaults(func: ast.AST) -> frozenset[str]:
    """Positional params that have NO default value — the ones a jit call
    must supply, hence the ones that arrive as tracers. A default-valued
    flag param the caller leaves alone stays a concrete Python value, so
    branching on it is fine (the ``return_kv``/``lora_bank`` idiom)."""
    args = func.args
    named = [*args.posonlyargs, *args.args]
    n_without = len(named) - len(args.defaults)
    return frozenset(a.arg for a in named[:n_without] if a.arg != "self")


_STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size"})


def _test_uses_traced_value(test: ast.expr, traced: frozenset[str]) -> bool:
    """Does a branch test read a traced param's VALUE (vs its static
    shape/dtype metadata or identity-vs-None)?"""

    def walk(node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return False  # x.shape[...] and friends are static under trace
        if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            return False  # `x is None` tests identity of the Python object
        if isinstance(node, ast.Call):
            fname = node.func
            if isinstance(fname, ast.Name) and fname.id in ("len", "isinstance"):
                return False  # len() reads shape; isinstance reads the type
        if isinstance(node, ast.Name) and node.id in traced:
            return True
        return any(walk(child) for child in ast.iter_child_nodes(node))

    return walk(test)


class _FileLint:
    """One file's full pass: shared fact collection + every rule."""

    def __init__(
        self,
        tree: ast.Module,
        path: str,
        corpus: "_CorpusFacts | None" = None,
    ) -> None:
        self.tree = tree
        self.path = path
        self.corpus = corpus
        self.aliases = collect_aliases(tree)
        self.violations: list[Violation] = []
        self.functions = _collect_functions(tree)
        self.contexts = _call_contexts(tree)
        self.bound_axes = _bound_axes(tree, self.aliases)
        # module/local names bound to a jit result (`m = jax.jit(f)`)
        self.jitted_names: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                if (
                    resolve_call_name(node.value.func, self.aliases)
                    in _JIT_WRAPPERS
                ):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            self.jitted_names.add(t.id)
        # class facts
        self.jit_attrs: dict[int, set[str]] = {}
        self.hot_funcs: set[int] = set()
        self.func_to_class: dict[int, ast.ClassDef] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                self.jit_attrs[id(node)] = _class_jit_attrs(node, self.aliases)
                for m in _hot_methods(node).values():
                    self.hot_funcs.add(id(m))
                for m in node.body:
                    if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        self.func_to_class[id(m)] = node
        # which local function names are jitted anywhere in this file, and
        # with what static/partial-bound names — traced-branch's input
        self.jit_sites: list[_JitSite] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                site = _decompose_jit(node, self.aliases)
                if site is not None:
                    self.jit_sites.append(site)

    def _flag(self, node: ast.AST, rule: str, message: str) -> None:
        self.violations.append(
            Violation(
                path=self.path,
                line=getattr(node, "lineno", 0),
                rule=rule,
                message=message,
            )
        )

    # ------------------------------------------------------------- rules
    def run(self) -> list[Violation]:
        self._check_jit_sites()
        self._check_traced_branches()
        self._check_collectives()
        self._check_host_sync()
        self.violations.sort(key=lambda v: (v.path, v.line, v.rule))
        return self.violations

    def _check_jit_sites(self) -> None:
        # jit-in-loop + retrace-hazard (immediate call / non-constant
        # statics / built-and-called-in-same-function)
        for site in self.jit_sites:
            call = site.call
            in_loop, func = self.contexts.get(id(call), (False, None))
            if in_loop:
                self._flag(
                    call,
                    "jit-in-loop",
                    "jax.jit constructed inside a loop: every iteration "
                    "builds a fresh wrapper with an empty trace cache — "
                    "hoist the jit out of the loop",
                )
            if not site.static_args_constant:
                self._flag(
                    call,
                    "retrace-hazard",
                    "static_argnums/static_argnames is not a compile-time "
                    "constant: the static set can drift per call site and "
                    "every new static VALUE retraces",
                )
            self._check_missing_donation(site)
        # immediate invocation: jax.jit(f)(args) — the wrapper and its
        # cache die with the statement
        for node in ast.walk(self.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Call)
                and resolve_call_name(node.func.func, self.aliases)
                in _JIT_WRAPPERS
            ):
                self._flag(
                    node,
                    "retrace-hazard",
                    "jax.jit(f)(...) invoked immediately: the compiled "
                    "program is thrown away after one call — bind the "
                    "jitted callable once and reuse it",
                )
        # built AND called inside the same function body (rebuilt per
        # invocation of the enclosing function)
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            built: dict[str, int] = {}
            returned: set[str] = set()
            called: dict[str, int] = {}
            # own body only: `g = jax.jit(f); def step(x): return g(x);
            # return step` is the canonical closure factory — the nested
            # call must not read as "called per invocation of THIS fn"
            for inner in _walk_scope(node):
                if isinstance(inner, ast.Assign) and isinstance(
                    inner.value, ast.Call
                ):
                    if (
                        resolve_call_name(inner.value.func, self.aliases)
                        in _JIT_WRAPPERS
                    ):
                        for t in inner.targets:
                            if isinstance(t, ast.Name):
                                built[t.id] = inner.lineno
                elif isinstance(inner, ast.Return) and inner.value is not None:
                    # the jit OBJECT escaping (factory pattern) sanctions
                    # the build: `return g` / `return g, opt`; `return
                    # g(x)` is a CALL of it and must not count
                    elts = (
                        inner.value.elts
                        if isinstance(inner.value, ast.Tuple)
                        else [inner.value]
                    )
                    returned.update(
                        e.id for e in elts if isinstance(e, ast.Name)
                    )
                elif isinstance(inner, ast.Call) and isinstance(
                    inner.func, ast.Name
                ):
                    called.setdefault(inner.func.id, inner.lineno)
            for name, line in built.items():
                if name in called and name not in returned:
                    self._flag(
                        ast.copy_location(ast.Pass(), node),
                        "retrace-hazard",
                        f"jax.jit bound to `{name}` (line {line}) is built "
                        f"and called inside {node.name}(): each call of "
                        f"{node.name} re-jits from scratch — build once "
                        "(module level, __init__, or a returned factory)",
                    )

    def _resolve_jit_target(self, site: _JitSite) -> _FunctionFacts | None:
        if site.target_name is None:
            return None
        facts = self.functions.get(site.target_name)
        if facts is not None:
            return facts
        if self.corpus is not None:
            dotted = self.aliases.get(site.target_name)
            if dotted is not None:
                return self.corpus.functions.get(dotted)
        return None

    def _check_missing_donation(self, site: _JitSite) -> None:
        if site.has_donation:
            return
        facts = self._resolve_jit_target(site)
        if facts is None:
            return
        threaded = facts.returned_params - site.partial_kwargs - site.static_names
        if threaded:
            names = ", ".join(sorted(threaded))
            self._flag(
                site.call,
                "missing-donation",
                f"jitted function returns its own parameter(s) {names} "
                "(state-in/state-out) but the jit has no donate_argnums: "
                "every call copies the full state buffers — donate the "
                "threaded state (see models/mnist.py make_train_step)",
            )

    def _check_traced_branches(self) -> None:
        # Every function defined IN THIS FILE that some corpus jit site
        # targets (own sites resolve locally; sites in other files whose
        # target lives here arrive via corpus.foreign_sites), with its
        # traced params. Resolution is local-only so the violation is
        # reported against the file holding the branch, never the caller.
        seen: set[int] = set()
        sites = list(self.jit_sites)
        if self.corpus is not None:
            sites += self.corpus.foreign_sites.get(self.path, [])
        for site in sites:
            facts = (
                self.functions.get(site.target_name)
                if site.target_name is not None
                else None
            )
            if facts is None or id(facts.node) in seen:
                continue
            seen.add(id(facts.node))
            static_by_pos = frozenset(
                facts.params[i]
                for i in site.static_nums
                if i < len(facts.params)
            )
            traced = _params_without_defaults(facts.node) - (
                site.partial_kwargs | site.static_names | static_by_pos
            )
            if not traced:
                continue
            for node in ast.walk(facts.node):
                if isinstance(node, (ast.If, ast.While)):
                    if _test_uses_traced_value(node.test, traced):
                        kind = "while" if isinstance(node, ast.While) else "if"
                        self._flag(
                            node,
                            "traced-python-branch",
                            f"Python `{kind}` on a traced argument's value "
                            f"inside jitted `{facts.node.name}`: the branch "
                            "runs at trace time, not per element — use "
                            "jnp.where/lax.cond, or mark the argument "
                            "static",
                        )

    def _check_collectives(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = resolve_call_name(node.func, self.aliases)
            if name not in _COLLECTIVES:
                continue
            axis_expr: ast.expr | None = None
            for kw in node.keywords:
                if kw.arg == "axis_name":
                    axis_expr = kw.value
            if axis_expr is None:
                idx = _COLLECTIVES[name]
                if len(node.args) > idx:
                    axis_expr = node.args[idx]
            if axis_expr is None:
                continue
            leaf = name.rsplit(".", 1)[-1]
            if isinstance(axis_expr, ast.Constant) and isinstance(
                axis_expr.value, str
            ):
                if axis_expr.value not in self.bound_axes:
                    self._flag(
                        node,
                        "collective-axis-mismatch",
                        f"lax.{leaf} over axis {axis_expr.value!r}, which "
                        "no shard_map/Mesh/PartitionSpec/pmap in this file "
                        "binds and no parameter default declares — this "
                        "can only raise 'unbound axis name' at trace time",
                    )
            elif isinstance(axis_expr, ast.Name):
                _, funcs = self.contexts.get(id(node), (False, ()))
                if axis_expr.id not in _enclosing_param_names(funcs):
                    self._flag(
                        node,
                        "collective-axis-mismatch",
                        f"lax.{leaf} axis_name `{axis_expr.id}` is neither "
                        "a parameter of the enclosing function nor a "
                        "literal a mesh context binds — the axis chain "
                        "cannot be audited",
                    )

    def _check_host_sync(self) -> None:
        # per enclosing function: the device-name set, then sink calls
        scopes: list[ast.AST] = [self.tree] + [
            n
            for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for scope in scopes:
            cls = self.func_to_class.get(id(scope))
            jit_attrs = self.jit_attrs.get(id(cls), set()) if cls else set()
            device_names = _device_names_in_scope(
                scope, self.aliases, jit_attrs, self.jitted_names
            )
            hot_method = id(scope) in self.hot_funcs
            for node in ast.walk(scope):
                if not isinstance(node, ast.Call):
                    continue
                in_loop, funcs = self.contexts.get(id(node), (False, ()))
                # attribute each call to its nearest NON-LAMBDA function:
                # a lambda body (a sort key, a callback) reads the
                # enclosing scope's names and runs in its loop context —
                # `sorted(rows, key=lambda i: float(logits[i]))` is still
                # a per-iteration sync of the enclosing function
                nearest = next(
                    (
                        f
                        for f in reversed(funcs)
                        if not isinstance(f, ast.Lambda)
                    ),
                    None,
                )
                if nearest is not scope and scope is not self.tree:
                    continue
                if scope is self.tree and nearest is not None:
                    continue
                hot = in_loop or hot_method
                if not hot:
                    continue
                sink = self._sync_sink(
                    node, device_names, jit_attrs
                )
                if sink is not None:
                    where = (
                        "inside a loop"
                        if in_loop
                        else f"on the step path (via {getattr(scope, 'name', '?')})"
                    )
                    self._flag(
                        node,
                        "host-sync-in-hot-loop",
                        f"{sink} {where}: a device→host transfer per "
                        "iteration serializes the pipeline — batch the "
                        "transfer per step, reduce on device first, or "
                        "sanction it with a justified suppression",
                    )

    def _sync_sink(
        self,
        call: ast.Call,
        device_names: set[str],
        jit_attrs: set[str],
    ) -> str | None:
        """The spelled sink name when this call host-materializes a
        tracked device value, else None."""
        func = call.func
        if isinstance(func, ast.Attribute):
            if func.attr == "block_until_ready":
                return "block_until_ready()"  # only exists on jax arrays
            if (
                func.attr == "item"
                and not call.args
                and _expr_is_deviceish(
                    func.value, device_names, self.aliases, jit_attrs,
                    self.jitted_names,
                )
            ):
                return ".item()"
        name = resolve_call_name(func, self.aliases)
        if name in _SYNC_CALLS:
            if name == "jax.device_get":
                return "jax.device_get()"
            if call.args and _expr_is_deviceish(
                call.args[0], device_names, self.aliases, jit_attrs,
                self.jitted_names,
            ):
                return f"{name}()"
        return None


# --------------------------------------------------------------------------
# corpus aggregation + entry points
# --------------------------------------------------------------------------


@dataclass
class _CorpusFacts:
    """Cross-file facts: top-level function defs keyed by dotted module
    path (``bee_code_interpreter_tpu.models.transformer.forward``), and
    jit sites whose target resolves INTO another file (so that file's
    traced-branch pass sees them)."""

    functions: dict[str, _FunctionFacts] = field(default_factory=dict)
    foreign_sites: dict[str, list[_JitSite]] = field(default_factory=dict)


def _module_dotted(rel_path: str) -> str:
    return rel_path[: -len(".py")].replace("/", ".")


def accelerator_files(
    root: Path | str = PACKAGE_ROOT,
    scope: tuple[str, ...] = ACCELERATOR_SCOPE,
) -> list[Path]:
    """Every .py file under the accelerator subtrees. The scope is the
    SAME tuple asynclint excludes, so the partition cannot drift: editing
    one side's list edits the other's."""
    root = Path(root)
    out: list[Path] = []
    for entry in scope:
        base = root / entry
        if base.is_dir():
            out.extend(sorted(base.rglob("*.py")))
    return out


def lint_jax_source(source: str, path: str = "<memory>") -> list[Violation]:
    """Lint one source blob file-locally (unit-test entry point)."""
    import textwrap

    tree = ast.parse(textwrap.dedent(source), filename=path)
    if not has_jax_triggers(tree):
        return []
    return _FileLint(tree, path).run()


def lint_jax_paths(
    root: Path | str = PACKAGE_ROOT,
    scope: tuple[str, ...] = ACCELERATOR_SCOPE,
    suppressions: tuple[Suppression, ...] = SUPPRESSIONS,
) -> JaxLintReport:
    """Lint the accelerator subtrees, apply the suppression ledger, and
    report what remains — the tier-1 entry point."""
    root = Path(root)
    report = JaxLintReport()
    files = accelerator_files(root, scope)
    trees: list[tuple[ast.Module, str]] = []
    corpus = _CorpusFacts()
    for py in files:
        rel = str(py.relative_to(root.parent))
        tree = ast.parse(py.read_text(), filename=rel)
        report.files_scanned += 1
        if not has_jax_triggers(tree):
            continue
        trees.append((tree, rel))
        dotted_mod = _module_dotted(rel)
        for stmt in tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                params = _function_params(stmt)
                corpus.functions[f"{dotted_mod}.{stmt.name}"] = _FunctionFacts(
                    node=stmt,
                    params=params,
                    returned_params=_returned_params(stmt, params),
                )
    # pass 2: route each file's cross-file jit sites to the defining file
    # so ITS traced-branch pass runs with the real static/partial sets
    dotted_to_rel = {
        _module_dotted(str(py.relative_to(root.parent))): str(
            py.relative_to(root.parent)
        )
        for py in files
    }
    for tree, rel in trees:
        aliases = collect_aliases(tree)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                site = _decompose_jit(node, aliases)
                if site is None or site.target_name is None:
                    continue
                dotted = aliases.get(site.target_name)
                if dotted and dotted in corpus.functions:
                    target_rel = dotted_to_rel.get(
                        dotted.rsplit(".", 1)[0]
                    )
                    if target_rel and target_rel != rel:
                        # route under the DEFINING file's bare function
                        # name: `from m import forward as fwd` must hit
                        # m's `forward`, not a nonexistent `fwd`
                        corpus.foreign_sites.setdefault(
                            target_rel, []
                        ).append(
                            dataclasses.replace(
                                site,
                                target_name=dotted.rsplit(".", 1)[1],
                            )
                        )
    all_violations: list[Violation] = []
    for tree, rel in trees:
        all_violations.extend(_FileLint(tree, rel, corpus).run())
    used: set[Suppression] = set()
    for v in all_violations:
        match = next((s for s in suppressions if s.matches(v)), None)
        if match is None:
            report.violations.append(v)
        else:
            used.add(match)
            report.suppressed.append((v, match))
    report.stale_suppressions = [s for s in suppressions if s not in used]
    report.violations.sort(key=lambda v: (v.path, v.line, v.rule))
    return report
