"""jaxlint: repo-specific static analysis for the PDHG serving stack.

Every rule is seeded by a real bug this repo shipped and later fixed —
the linter turns each one-off review catch into a mechanical check
(TDO-CIM's argument: compiler-level detection scales, hand-auditing
does not).  Pure stdlib ``ast`` — no third-party dependencies, so the
CI lint job needs no JAX install.

Rules
-----
R1  cache-key completeness.  A module defining a ``*Options`` dataclass
    together with an ``opts_static`` builder must account for EVERY
    option field: either the field is consumed by ``opts_static`` (and
    therefore part of every compiled-executable cache key) or it is
    listed in an explicit module-level ``DYNAMIC_FIELDS`` allowlist.
    Seeded by: ``sparse_kernel``, ``megakernel`` and ``restart`` each
    shipped without an ``opts_static`` entry, so executables compiled
    for one backend could be served to another.

R2  PRNG discipline.  (a) ``jax.random.PRNGKey(<const>)`` outside
    allowlisted test/example trees — a hardcoded key silently
    correlates every stream drawn from it.  (b) The same key variable
    feeding two random draws without an intervening
    ``split``/``fold_in`` rebinding.  Seeded by: ``_solve_jit_core``
    ignoring its caller key in favour of ``PRNGKey(0)``, and the host
    restart check reusing k3/k4 for the averaged-iterate MVMs.

R3  non-monotonic timing.  ``time.time()`` feeding a duration
    subtraction — wall-clock time is not monotonic (NTP steps make
    durations negative or garbage); durations must use
    ``time.perf_counter()``.  Seeded by: the PR 6 benchmark-timing
    sweep that fixed ``stream_throughput.py`` but missed four other
    files.

R4  tracer-hostile control flow.  Python ``if``/``while`` whose test
    contains a ``jnp``-rooted expression inside a function that is
    jit/vmap/shard_map-traced — under tracing this either raises a
    ``TracerBoolConversionError`` or silently bakes in a trace-time
    constant.  Seeded by: the ``restart_beta = 0.0`` encoding whose
    jitted comparison only worked because ``0.0 * inf`` is NaN and NaN
    comparisons are false.

R5  host-sync in hot paths.  ``.item()``, ``numpy.asarray``/``array``,
    or ``float()``/``int()``/``bool()`` over a device expression inside
    a traced function of a designated hot-path file — each is an
    implicit device->host sync that destroys async dispatch (and is
    exactly what the runtime transfer sanitizer traps at run time).

R6  stale pragma.  A ``# jaxlint: disable=RX`` on a line where rule RX
    no longer fires is itself a finding — suppressions must stay
    justified, and a pragma that outlives its finding silently licenses
    the next real instance of the bug.  Pragmas naming unknown rule ids
    are flagged too.  ``disable=R6`` on the same line self-suppresses
    (for the rare pragma that is only conditionally live).

R7  benchmark timing windows.  A ``time.perf_counter()`` start/stop
    pair in ``benchmarks/`` must contain a ``block_until_ready`` call
    (method or ``jax.block_until_ready``) before the closing read —
    JAX dispatch is async, so an unsynchronized window times the
    enqueue, not the computation, and the numbers are fiction.

Pragmas: append ``# jaxlint: disable=R2`` (comma-separate for several
rules) to a line to suppress findings anchored there — every pragma in
this repo must carry a one-line justification.  R6 keeps the pragma
inventory honest: a suppression whose rule no longer fires must be
deleted, not carried.
"""
from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path
from typing import Iterable, List, Optional, Sequence

RULE_IDS = ("R1", "R2", "R3", "R4", "R5", "R6", "R7")

RULE_SUMMARIES = {
    "R1": "cache-key completeness (Options fields vs opts_static + "
          "DYNAMIC_FIELDS)",
    "R2": "PRNG discipline (hardcoded PRNGKey / key reuse without split)",
    "R3": "non-monotonic timing (time.time() in a duration subtraction)",
    "R4": "tracer-hostile control flow (Python if/while on jnp inside "
          "traced code)",
    "R5": "host-sync in hot paths (.item()/np.asarray/float() under "
          "tracing)",
    "R6": "stale pragma (disable= for a rule that no longer fires here)",
    "R7": "benchmark timing window without block_until_ready before the "
          "closing perf_counter read",
}

_PRAGMA_RE = re.compile(r"#\s*jaxlint:\s*disable=([A-Z0-9,\s]+)")

# jax.random draws that consume a key as their first positional argument
_DRAW_FNS = frozenset({
    "normal", "uniform", "randint", "bernoulli", "beta", "cauchy",
    "choice", "dirichlet", "exponential", "gamma", "gumbel", "laplace",
    "logistic", "maxwell", "multivariate_normal", "orthogonal", "pareto",
    "permutation", "poisson", "rademacher", "categorical",
    "truncated_normal", "t", "shuffle", "bits",
})
# key-deriving calls: rebinding a name from these REFRESHES it
_REFRESH_FNS = frozenset({"split", "fold_in", "PRNGKey", "key", "clone"})

# transforms whose function argument (or decorated function) is traced
_TRACING_TRANSFORMS = frozenset({
    "jit", "vmap", "pmap", "grad", "value_and_grad", "shard_map",
    "checkpoint", "remat", "scan", "while_loop", "fori_loop", "cond",
    "switch", "custom_vjp", "custom_jvp", "pallas_call",
})

# host-sync calls R5 traps inside traced hot-path code
_NUMPY_SYNC_FNS = frozenset({"asarray", "array", "copy"})
_BUILTIN_SYNC_FNS = frozenset({"float", "int", "bool"})


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


@dataclasses.dataclass(frozen=True)
class Config:
    """Repo-specific knobs; the defaults ARE this repo's policy."""

    # R5 applies only inside these path fragments (posix, substring match)
    hot_paths: Sequence[str] = (
        "repro/core/engine.py",
        "repro/kernels/",
        "repro/runtime/batch.py",
    )
    # R2(a) hardcoded-key allowlist: test/example trees may pin seeds
    prng_allow: Sequence[str] = ("tests/", "examples/", "conftest.py")
    # R7 applies only inside these path fragments (posix, substring match)
    bench_paths: Sequence[str] = ("benchmarks/",)
    # extra jit-entry functions per path fragment (cross-module jit
    # targets the per-module decorator scan cannot see, e.g.
    # ``jax.jit(engine.solve_core, ...)`` living in core/pdhg.py)
    jit_entry_points: Sequence[tuple] = (
        ("repro/core/engine.py",
         ("solve_core", "pdhg_loop", "pdhg_step", "init_state",
          "draw_init", "adaptive_omega_init", "adaptive_shrink",
          "adaptive_omega_update")),
        ("repro/runtime/batch.py",
         ("_single_solve", "_prep_one", "_prep_one_sparse",
          "_prep_one_ell", "_coo_matvec", "_row_reduce",
          "make_bucket_pipeline", "make_sparse_bucket_pipeline",
          "make_ell_bucket_pipeline")),
        ("repro/core/lanczos.py",
         ("lanczos_svd_jit_mv", "lanczos_svd_jit", "power_iteration",
          "power_iteration_mv")),
        ("repro/kernels/ops.py",
         ("crossbar_mvm", "primal_update", "dual_update")),
        ("repro/kernels/sparse_mvm.py", ("ell_matvec", "ell_to_dense")),
        ("repro/kernels/pdhg_megakernel.py",
         ("fused_dense_steps", "_run_steps")),
        ("repro/kernels/ref.py",
         ("crossbar_mvm_ref", "primal_update_ref", "dual_update_ref")),
        ("repro/crossbar/solver.py", ("make_crossbar_bucket_pipeline",)),
        ("repro/distributed/pdhg_dist.py", ("make_dist_step",)),
    )
    select: Optional[frozenset] = None          # None = all rules

    def rule_enabled(self, rule: str) -> bool:
        return self.select is None or rule in self.select

    def is_hot_path(self, path: str) -> bool:
        return any(frag in path for frag in self.hot_paths)

    def prng_allowed(self, path: str) -> bool:
        return any(frag in path for frag in self.prng_allow)

    def is_bench_path(self, path: str) -> bool:
        return any(frag in path for frag in self.bench_paths)

    def entry_points_for(self, path: str) -> frozenset:
        names: set = set()
        for frag, fns in self.jit_entry_points:
            if frag in path:
                names.update(fns)
        return frozenset(names)


DEFAULT_CONFIG = Config()


# ------------------------------------------------------------- helpers ---

def _attr_chain(node: ast.AST) -> Optional[str]:
    """Dotted name of a Name/Attribute chain ('jax.random.PRNGKey'),
    or None when the chain roots in something dynamic."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_chain(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Call):
        return _attr_chain(node.func)
    return None


def _is_prngkey_call(node: ast.AST) -> bool:
    chain = _call_chain(node)
    return chain is not None and chain.split(".")[-1] == "PRNGKey"


def _contains_jnp(node: ast.AST) -> bool:
    """True when the expression tree references ``jnp.*`` (or
    ``jax.numpy.*`` / ``jax.lax.*``) — a device-value expression."""
    for sub in ast.walk(node):
        chain = _attr_chain(sub) if isinstance(sub, ast.Attribute) else None
        if chain and (chain.startswith("jnp.")
                      or chain.startswith("jax.numpy.")
                      or chain.startswith("jax.lax.")):
            return True
    return False


def _pragma_lines(source: str) -> dict:
    """line number -> set of disabled rule ids.

    Tokenize-based: only REAL comments count, so a pragma spelled inside
    a string literal (fixture sources, docstring examples) neither
    suppresses anything nor registers as stale for R6.  Falls back to a
    line scan when the file does not tokenize (lint_source has already
    bailed on syntax errors by then, so this is belt-and-braces)."""
    out = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type != tokenize.COMMENT:
                continue
            mt = _PRAGMA_RE.search(tok.string)
            if mt:
                out[tok.start[0]] = {
                    r.strip() for r in mt.group(1).split(",") if r.strip()}
    except (tokenize.TokenError, IndentationError):
        for i, text in enumerate(source.splitlines(), start=1):
            mt = _PRAGMA_RE.search(text)
            if mt:
                out[i] = {r.strip() for r in mt.group(1).split(",")
                          if r.strip()}
    return out


def _functions(tree: ast.AST) -> Iterable[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


# ------------------------------------------------- R1: cache-key audit ---

def _dataclass_fields(cls: ast.ClassDef) -> dict:
    """Annotated field name -> line, for a dataclass body."""
    fields = {}
    for stmt in cls.body:
        if isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target,
                                                          ast.Name):
            if isinstance(stmt.annotation, ast.Name) and \
                    stmt.annotation.id == "ClassVar":
                continue
            fields[stmt.target.id] = stmt.lineno
    return fields


def _is_dataclass(cls: ast.ClassDef) -> bool:
    for dec in cls.decorator_list:
        chain = _attr_chain(dec.func if isinstance(dec, ast.Call) else dec)
        if chain and chain.split(".")[-1] == "dataclass":
            return True
    return False


def rule_r1(tree: ast.Module, path: str) -> List[Finding]:
    opts_cls = next(
        (n for n in tree.body
         if isinstance(n, ast.ClassDef) and n.name.endswith("Options")
         and _is_dataclass(n)), None)
    static_fn = next(
        (n for n in tree.body
         if isinstance(n, ast.FunctionDef) and n.name == "opts_static"),
        None)
    if opts_cls is None or static_fn is None:
        return []        # rule only binds where both halves live together

    fields = _dataclass_fields(opts_cls)
    opts_arg = static_fn.args.args[0].arg if static_fn.args.args else "opts"
    consumed = set()
    for node in ast.walk(static_fn):
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == opts_arg:
            consumed.add(node.attr)

    dynamic = None
    dynamic_line = static_fn.lineno
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "DYNAMIC_FIELDS":
                    dynamic_line = node.lineno
                    if isinstance(node.value, (ast.Tuple, ast.List,
                                               ast.Set)):
                        dynamic = {
                            el.value for el in node.value.elts
                            if isinstance(el, ast.Constant)
                            and isinstance(el.value, str)}

    findings = []
    if dynamic is None:
        return [Finding(
            path, static_fn.lineno, "R1",
            f"{opts_cls.name} + opts_static found but no module-level "
            "DYNAMIC_FIELDS allowlist: every option field must be "
            "consumed by opts_static or explicitly declared dynamic")]
    for name, line in fields.items():
        in_static = name in consumed
        in_dynamic = name in dynamic
        if not in_static and not in_dynamic:
            findings.append(Finding(
                path, line, "R1",
                f"{opts_cls.name}.{name} is neither consumed by "
                "opts_static (executable cache key) nor listed in "
                "DYNAMIC_FIELDS — decide its cache-key fate"))
        elif in_static and in_dynamic:
            findings.append(Finding(
                path, line, "R1",
                f"{opts_cls.name}.{name} is consumed by opts_static AND "
                "listed in DYNAMIC_FIELDS — remove it from the "
                "allowlist"))
    for name in sorted(dynamic - set(fields)):
        findings.append(Finding(
            path, dynamic_line, "R1",
            f"DYNAMIC_FIELDS entry {name!r} is not a field of "
            f"{opts_cls.name} — stale allowlist"))
    return findings


# --------------------------------------------------- R2: PRNG discipline ---

def rule_r2(tree: ast.Module, path: str, cfg: Config) -> List[Finding]:
    findings = []

    # (a) hardcoded PRNGKey(<const>)
    if not cfg.prng_allowed(path):
        for node in ast.walk(tree):
            if _is_prngkey_call(node) and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, int):
                findings.append(Finding(
                    path, node.lineno, "R2",
                    f"hardcoded jax.random.PRNGKey({node.args[0].value}) "
                    "— thread a caller key/seed, or pragma with a "
                    "justification if the determinism is deliberate"))

    # (b) same key feeding two draws without an intervening split
    for fn in _functions(tree):
        findings.extend(_scan_key_reuse(fn, path))
    return findings


# callables whose ``key=`` kwarg is a comparator, not a PRNG key
_KEY_KWARG_EXEMPT = frozenset({
    "sorted", "min", "max", "sort", "nlargest", "nsmallest", "groupby",
})


def _key_uses(call: ast.Call) -> List[str]:
    """Key variable names this call CONSUMES (draw semantics)."""
    chain = _call_chain(call) or ""
    leaf = chain.split(".")[-1]
    used = []
    if ".random." in f".{chain}." and leaf in _DRAW_FNS and call.args and \
            isinstance(call.args[0], ast.Name):
        used.append(call.args[0].id)
    if leaf not in _KEY_KWARG_EXEMPT:
        for kw in call.keywords:
            if kw.arg == "key" and isinstance(kw.value, ast.Name):
                used.append(kw.value.id)
    return used


def _scan_key_reuse(fn, path: str) -> List[Finding]:
    """Branch-aware scan of one function body (nested defs get their own
    scan): a name consumed by two draws along one execution path with no
    refreshing rebinding in between is a reused key.  ``if``/``else``
    arms fork the used-set and merge as a union; draws in mutually
    exclusive branches never fire."""
    findings = []

    def scan_expr(node: ast.AST, used: set) -> None:
        """Record draws inside one expression/simple statement, in
        source order, skipping nested function/lambda bodies."""
        nested = {
            id(sub)
            for n in ast.walk(node)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda))
            for sub in ast.walk(n)}
        comp_targets: set = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.comprehension):
                comp_targets.update(_target_names(sub.target))
        ordered = sorted(
            (s for s in ast.walk(node) if id(s) not in nested),
            key=lambda s: (getattr(s, "lineno", 0),
                           getattr(s, "col_offset", 0)))
        for sub in ordered:
            if not isinstance(sub, ast.Call):
                continue
            for name in _key_uses(sub):
                if name in comp_targets:
                    continue        # fresh binding per comprehension iter
                if name in used:
                    findings.append(Finding(
                        path, sub.lineno, "R2",
                        f"key {name!r} feeds a second random draw "
                        "without an intervening split/fold_in — reused "
                        "keys correlate the two streams"))
                used.add(name)

    def refresh(stmt: ast.AST, used: set) -> None:
        if isinstance(stmt, ast.Assign):
            for tgt in stmt.targets:
                for name in _target_names(tgt):
                    used.discard(name)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) and \
                isinstance(stmt.target, ast.Name):
            used.discard(stmt.target.id)

    def scan_block(stmts, used: set) -> set:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue        # scanned as their own scope
            if isinstance(stmt, ast.If):
                scan_expr(stmt.test, used)
                u_then = scan_block(stmt.body, set(used))
                u_else = scan_block(stmt.orelse, set(used))
                used = u_then | u_else
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                scan_expr(stmt.iter, used)
                for name in _target_names(stmt.target):
                    used.discard(name)
                # body scanned once: reuse WITHIN an iteration fires;
                # cross-iteration reuse is left to the loop author
                u_body = scan_block(stmt.body, set(used))
                used = u_body | scan_block(stmt.orelse, set(used))
            elif isinstance(stmt, ast.While):
                scan_expr(stmt.test, used)
                u_body = scan_block(stmt.body, set(used))
                used = u_body | scan_block(stmt.orelse, set(used))
            elif isinstance(stmt, ast.Try):
                merged = scan_block(stmt.body, set(used))
                for handler in stmt.handlers:
                    merged |= scan_block(handler.body, set(used))
                merged = scan_block(stmt.orelse, merged)
                used = scan_block(stmt.finalbody, merged)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    scan_expr(item.context_expr, used)
                used = scan_block(stmt.body, used)
            else:
                scan_expr(stmt, used)
                refresh(stmt, used)
        return used

    scan_block(fn.body, set())
    return findings


def _target_names(tgt: ast.AST) -> List[str]:
    if isinstance(tgt, ast.Name):
        return [tgt.id]
    if isinstance(tgt, (ast.Tuple, ast.List)):
        out = []
        for el in tgt.elts:
            out.extend(_target_names(el))
        return out
    return []


# ------------------------------------------------ R3: duration timing ---

def rule_r3(tree: ast.Module, path: str) -> List[Finding]:
    findings = []
    for scope in [tree, *list(_functions(tree))]:
        nested = set()
        if not isinstance(scope, ast.Module):
            nested = {
                id(sub)
                for n in ast.walk(scope)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n is not scope
                for sub in ast.walk(n)}
        else:
            nested = {
                id(sub)
                for n in ast.walk(scope)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                for sub in ast.walk(n)}
        own = [n for n in ast.walk(scope) if id(n) not in nested]
        walltime_names = set()
        for node in own:
            if isinstance(node, ast.Assign) and \
                    _call_chain(node.value) in ("time.time",):
                for tgt in node.targets:
                    walltime_names.update(_target_names(tgt))
        for node in own:
            if isinstance(node, ast.BinOp) and \
                    isinstance(node.op, ast.Sub):
                operands = (node.left, node.right)
                direct = any(_call_chain(op) == "time.time"
                             for op in operands)
                via_name = any(isinstance(op, ast.Name)
                               and op.id in walltime_names
                               for op in operands)
                if direct or via_name:
                    findings.append(Finding(
                        path, node.lineno, "R3",
                        "duration computed from time.time() — wall-clock "
                        "time is not monotonic; use "
                        "time.perf_counter()"))
    return findings


# --------------------------------------- R4/R5: traced-code reachability ---

def _traced_functions(tree: ast.Module, path: str, cfg: Config) -> set:
    """ids of FunctionDef nodes that execute under a JAX trace.

    Seeds: functions decorated with a tracing transform, functions
    passed by (local) name to a tracing transform, and the configured
    cross-module entry points.  Closure: a function called by name from
    a traced function, and every nested def of a traced function (all
    code inside a traced function runs at trace time).
    """
    by_name: dict = {}
    for fn in _functions(tree):
        by_name.setdefault(fn.name, []).append(fn)

    entry_names = cfg.entry_points_for(path)
    traced: set = set()

    def is_tracing_transform(node: ast.AST) -> bool:
        chain = _attr_chain(node)
        if chain is None:
            return False
        leaf = chain.split(".")[-1]
        if leaf not in _TRACING_TRANSFORMS:
            return False
        # functools.partial(jax.jit, ...) handled by caller
        return True

    for fn in _functions(tree):
        if fn.name in entry_names:
            traced.add(id(fn))
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if is_tracing_transform(target):
                traced.add(id(fn))
            # functools.partial(jax.jit, static_argnames=...)
            if isinstance(dec, ast.Call) and \
                    (_attr_chain(dec.func) or "").endswith("partial") and \
                    dec.args and is_tracing_transform(dec.args[0]):
                traced.add(id(fn))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                is_tracing_transform(node.func):
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    for fn in by_name.get(arg.id, []):
                        traced.add(id(fn))

    # closure: by-name calls from traced bodies + nested defs
    changed = True
    while changed:
        changed = False
        for fn in _functions(tree):
            if id(fn) not in traced:
                continue
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        node is not fn and id(node) not in traced:
                    traced.add(id(node))
                    changed = True
                if isinstance(node, ast.Call):
                    callee = None
                    if isinstance(node.func, ast.Name):
                        callee = node.func.id
                    for cand in by_name.get(callee, []):
                        if id(cand) not in traced:
                            traced.add(id(cand))
                            changed = True
                # function names passed around inside traced code
                # (e.g. fori_loop bodies) are caught by the global
                # transform scan above
    return traced


def rule_r4(tree: ast.Module, path: str, cfg: Config) -> List[Finding]:
    traced = _traced_functions(tree, path, cfg)
    findings = []
    for fn in _functions(tree):
        if id(fn) not in traced:
            continue
        nested = {
            id(sub)
            for n in ast.walk(fn)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n is not fn
            for sub in ast.walk(n)}
        for node in ast.walk(fn):
            if id(node) in nested:
                continue
            if isinstance(node, (ast.If, ast.While)) and \
                    _contains_jnp(node.test):
                kind = "while" if isinstance(node, ast.While) else "if"
                findings.append(Finding(
                    path, node.lineno, "R4",
                    f"Python `{kind}` on a jnp expression inside traced "
                    f"function {fn.name!r} — use lax.cond/while_loop or "
                    "jnp.where; under jit this either raises or bakes "
                    "in a trace-time constant"))
    return findings


def rule_r5(tree: ast.Module, path: str, cfg: Config) -> List[Finding]:
    if not cfg.is_hot_path(path):
        return []
    traced = _traced_functions(tree, path, cfg)
    findings = []
    for fn in _functions(tree):
        if id(fn) not in traced:
            continue
        nested = {
            id(sub)
            for n in ast.walk(fn)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n is not fn
            for sub in ast.walk(n)}
        for node in ast.walk(fn):
            if id(node) in nested or not isinstance(node, ast.Call):
                continue
            chain = _call_chain(node) or ""
            leaf = chain.split(".")[-1]
            msg = None
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                msg = ".item() forces a device->host sync"
            elif chain.split(".")[0] in ("np", "numpy") and \
                    leaf in _NUMPY_SYNC_FNS:
                msg = (f"{chain}() materializes a host copy of a device "
                       "value")
            elif chain in _BUILTIN_SYNC_FNS and node.args and not \
                    isinstance(node.args[0], (ast.Name, ast.Constant)):
                msg = (f"{chain}() on a computed value forces a "
                       "device->host sync")
            if msg:
                findings.append(Finding(
                    path, node.lineno, "R5",
                    f"{msg} inside traced hot-path function "
                    f"{fn.name!r} — keep the value on device (the "
                    "runtime transfer sanitizer traps this at run "
                    "time)"))
    return findings


# ----------------------------------------- R7: benchmark timing windows ---

def _scope_own_nodes(scope, is_module: bool):
    """Nodes belonging to ``scope`` but not to any nested function."""
    nested = {
        id(sub)
        for n in ast.walk(scope)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and (is_module or n is not scope)
        for sub in ast.walk(n)}
    return [n for n in ast.walk(scope) if id(n) not in nested]


def _is_perf_counter(node: ast.AST) -> bool:
    return _call_chain(node) in ("time.perf_counter", "perf_counter")


def rule_r7(tree: ast.Module, path: str, cfg: Config) -> List[Finding]:
    """Every perf_counter start->stop subtraction in a benchmark must
    bracket a ``block_until_ready`` call, else async dispatch means the
    window times the enqueue, not the work."""
    if not cfg.is_bench_path(path):
        return []
    findings = []
    for scope in [tree, *list(_functions(tree))]:
        own = _scope_own_nodes(scope, isinstance(scope, ast.Module))
        perf_assigns: dict = {}       # name -> sorted assign lines
        sync_lines = []
        windows = []                  # (start_line, end_line)
        for node in own:
            if isinstance(node, ast.Assign) and _is_perf_counter(node.value):
                for tgt in node.targets:
                    for name in _target_names(tgt):
                        perf_assigns.setdefault(name, []).append(node.lineno)
            if isinstance(node, ast.Call):
                chain = _call_chain(node) or ""
                if chain.split(".")[-1] == "block_until_ready":
                    sync_lines.append(node.lineno)
        for node in own:
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)):
                continue
            starts = []
            for op in (node.left, node.right):
                if isinstance(op, ast.Name) and op.id in perf_assigns:
                    # timer names get reused across windows in one scope:
                    # this read closes the LATEST assignment before it
                    prior = [ln for ln in perf_assigns[op.id]
                             if ln <= node.lineno]
                    if prior:
                        starts.append(max(prior))
            if starts:
                windows.append((min(starts), node.lineno))
        for start, end in windows:
            if not any(start <= ln <= end for ln in sync_lines):
                findings.append(Finding(
                    path, end, "R7",
                    "perf_counter timing window (opened at line "
                    f"{start}) closes without a block_until_ready — "
                    "async dispatch makes this time the enqueue, not "
                    "the computation"))
    return findings


# ------------------------------------------------- R6: stale pragmas ---

def rule_r6(findings: List[Finding], pragmas: dict, path: str,
            cfg: Config) -> List[Finding]:
    """A pragma entry whose rule did not fire on that line is stale.

    Runs AFTER the other rules so it can see what actually fired.
    ``R6`` entries themselves are exempt (they exist to self-suppress
    this rule); disabled rules are exempt too (a partial ``--select``
    run cannot judge pragmas for rules it never executed)."""
    fired = {(f.line, f.rule) for f in findings}
    out = []
    for line, rules in sorted(pragmas.items()):
        for rid in sorted(rules):
            if rid == "R6":
                continue
            if rid not in RULE_IDS:
                out.append(Finding(
                    path, line, "R6",
                    f"pragma disables unknown rule {rid!r} — typo or "
                    "removed rule; delete the entry"))
            elif cfg.rule_enabled(rid) and (line, rid) not in fired:
                out.append(Finding(
                    path, line, "R6",
                    f"stale pragma: {rid} does not fire on this line "
                    "any more — delete the suppression (or the whole "
                    "pragma) so it cannot silently license the next "
                    "real instance"))
    return out


# ------------------------------------------------------------- driver ---

def lint_source(source: str, path: str,
                cfg: Config = DEFAULT_CONFIG) -> List[Finding]:
    """Lint one file's source text; ``path`` drives per-path policy."""
    path = Path(path).as_posix()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [Finding(path, exc.lineno or 1, "E0",
                        f"syntax error: {exc.msg}")]
    findings: List[Finding] = []
    if cfg.rule_enabled("R1"):
        findings.extend(rule_r1(tree, path))
    if cfg.rule_enabled("R2"):
        findings.extend(rule_r2(tree, path, cfg))
    if cfg.rule_enabled("R3"):
        findings.extend(rule_r3(tree, path))
    if cfg.rule_enabled("R4"):
        findings.extend(rule_r4(tree, path, cfg))
    if cfg.rule_enabled("R5"):
        findings.extend(rule_r5(tree, path, cfg))
    if cfg.rule_enabled("R7"):
        findings.extend(rule_r7(tree, path, cfg))
    pragmas = _pragma_lines(source)
    if cfg.rule_enabled("R6"):
        findings.extend(rule_r6(findings, pragmas, path, cfg))
    kept = [f for f in findings
            if f.rule not in pragmas.get(f.line, set())]
    return sorted(kept, key=lambda f: (f.path, f.line, f.rule))


def lint_file(path, cfg: Config = DEFAULT_CONFIG) -> List[Finding]:
    p = Path(path)
    return lint_source(p.read_text(), p.as_posix(), cfg)


def iter_python_files(paths: Iterable) -> List[Path]:
    out: List[Path] = []
    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            out.extend(sorted(
                f for f in p.rglob("*.py")
                if not any(part.startswith(".") for part in f.parts)))
        elif p.suffix == ".py":
            out.append(p)
    return out


def lint_paths(paths: Iterable,
               cfg: Config = DEFAULT_CONFIG) -> List[Finding]:
    findings: List[Finding] = []
    for f in iter_python_files(paths):
        findings.extend(lint_file(f, cfg))
    return findings
