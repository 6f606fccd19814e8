"""traceaudit: static analysis over TRACED computations.

``tools/jaxlint`` reads source; this tool reads what the source
actually becomes.  It traces every supported solver path combo
(operator backend x update kernel x step_rule x sparse_kernel x
megakernel on/off, plus the mixed-precision refinement shells) via
``jax.make_jaxpr`` on tiny shapes, then runs
four analyzers over each jaxpr:

budget       The primitive-budget checker walks the jaxpr into
             ``while``/``scan``/``cond``/``pjit``/``shard_map``/
             ``pallas_call`` bodies (trip-count scaling shared with
             ``launch/hlo.py``'s HLO walker), counts MVM-bearing
             primitives (rank>=2 ``dot_general``, ``bcoo_dot_general``,
             the ELL row-gather), and asserts the count per check
             window equals ``core.engine.mvm_window_budget`` — and that
             NOTHING MVM-shaped runs outside the loop.  This is the
             energy ledger's formula re-derived from the actual trace:
             the ledger lied twice before (the ``2*it`` undercount, the
             noisy-check charge) and ``step_rule="adaptive"``'s "zero
             extra MVMs" claim was prose until now.

dtype        Flags silent float narrowing (``convert_element_type``
             f64 -> f32 anywhere in the trace — the paths are traced in
             f64, so every narrowing is a demotion someone wrote) and
             mixed-precision accumulation (a dot whose output dtype is
             narrower than its widest float operand).

effects      No host callbacks or device transfers inside the hot loop:
             ``pure_callback``/``debug_callback``/``io_callback``/
             ``infeed``/``outfeed``/``device_put`` under a ``while``
             body would synchronize every iteration.

fingerprint  Canonicalizes each path's jaxpr (structural rendering with
             no variable names), hashes it, and diffs against the
             committed ``TRACE_BASELINE.json``.  Unexplained drift
             fails CI with a primitive-histogram diff; PRs that
             intentionally change traced structure rerun with
             ``--update-baseline`` and commit the new file.  Hash drift
             is only a hard failure when ``jax.__version__`` matches
             the baseline's (lowering details move between releases);
             budget/dtype/effects gate regardless of version.

Run as ``python -m tools.traceaudit`` (CPU-only: the module forces
``JAX_PLATFORMS=cpu`` and x64 before tracing).  No module-level jax
import — the CLI must win the import race to pin the platform.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
BASELINE_PATH = Path(__file__).resolve().parent / "TRACE_BASELINE.json"
BASELINE_SCHEMA = "traceaudit/v1"

# tiny trace shapes: structure is shape-independent (the walker never
# reads dimension VALUES except loop trips), so the cheapest legal
# shapes trace fastest.  K has zeros so the ELL pattern is non-trivial.
TRACE_M, TRACE_N = 4, 3
CHECK_EVERY = 4
MAX_ITERS = 8
GAMMA_SC = 0.1          # strongly_convex requires gamma > 0

ANALYZERS = ("budget", "dtype", "effects", "fingerprint")


def _ensure_import_paths() -> None:
    for p in (str(REPO_ROOT / "src"), str(REPO_ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


# ------------------------------------------------------- path registry ---

@dataclasses.dataclass(frozen=True)
class PathSpec:
    """One supported solver path combo; ``name`` is the stable id used
    in TRACE_BASELINE.json and in findings."""
    backend: str          # dense | ell | bcoo | crossbar | sharded
    kernel: str           # jnp | pallas  (update kernel)
    step_rule: str        # fixed | adaptive | strongly_convex
    megakernel: bool
    restart: bool
    refine: int = 0       # iterative-refinement rounds (0 = plain solve)

    @property
    def name(self) -> str:
        base = (f"{self.backend}/{self.kernel}/{self.step_rule}"
                f"/mega{int(self.megakernel)}/restart{int(self.restart)}")
        # suffix only when nonzero so pre-refinement baseline names are
        # stable across the matrix extension
        return base + (f"/refine{self.refine}" if self.refine else "")

    @property
    def gamma(self) -> float:
        return GAMMA_SC if self.step_rule == "strongly_convex" else 0.0


def supported_paths() -> List[PathSpec]:
    """The full combo matrix.  Constraints mirror the engine's:
    megakernel exists for dense only (the one fusable operand layout),
    the distributed path always uses jnp updates, and the restart=False
    variant is audited once per backend on the canonical combo (restart
    is orthogonal to kernel/step_rule in the trace — it only toggles
    the averaged-iterate check block)."""
    paths: List[PathSpec] = []
    for backend in ("dense", "ell", "bcoo", "crossbar", "sharded"):
        kernels = ("jnp",) if backend == "sharded" else ("jnp", "pallas")
        megas = (False, True) if backend == "dense" else (False,)
        for kernel in kernels:
            for rule in ("fixed", "adaptive", "strongly_convex"):
                for mega in megas:
                    paths.append(PathSpec(backend, kernel, rule, mega,
                                          True))
        paths.append(PathSpec(backend, "jnp", "fixed", False, False))
    # mixed-precision refinement shells (crossbar.refine.refined_core):
    # the analog-operator mount the batched pipeline uses, plus the dense
    # self-mount solve_crossbar_refined runs — each inner solve is one
    # more while loop on the SAME operator, so budgets scale by
    # engine.refine_window_factor and the digital residual MVMs land
    # outside the loops (engine.refine_digital_mvms)
    for refine in (1, 2):
        paths.append(PathSpec("crossbar", "jnp", "fixed", False, True,
                              refine))
    paths.append(PathSpec("dense", "jnp", "fixed", False, True, 1))
    return paths


# ------------------------------------------------------------- tracing ---

_TRACE_CACHE: Dict[str, object] = {}


def _problem(jnp):
    import numpy as np
    Kd = np.array([[1.0, 0.0, 2.0],
                   [0.0, 3.0, 0.0],
                   [4.0, 0.0, 5.0],
                   [0.0, 6.0, 7.0]])
    assert Kd.shape == (TRACE_M, TRACE_N)
    dt = jnp.float64
    m, n = TRACE_M, TRACE_N
    return dict(
        Kd=Kd, K=jnp.asarray(Kd, dt),
        b=jnp.ones(m, dt), c=jnp.ones(n, dt),
        lb=jnp.zeros(n, dt), ub=jnp.ones(n, dt),
        T=jnp.ones(n, dt), Sigma=jnp.ones(m, dt),
        rho=jnp.asarray(2.0, dt), dt=dt)


def _make_operator(spec: PathSpec, prob, engine):
    """Mount the operator exactly the way the serving paths do."""
    import jax.numpy as jnp
    Kd, dt = prob["Kd"], prob["dt"]
    m, n = TRACE_M, TRACE_N
    if spec.backend == "ell":
        import numpy as np
        from repro.kernels.sparse_mvm import ell_from_coo
        rows, cols = np.nonzero(Kd)
        vals = Kd[rows, cols]
        df, cf = ell_from_coo(vals, rows, cols, (m, n))
        da, ca = ell_from_coo(vals, cols, rows, (n, m))
        df, da = jnp.asarray(df, dt), jnp.asarray(da, dt)
        cf, ca = jnp.asarray(cf), jnp.asarray(ca)
        return engine.sparse_ell_operator(df, cf, da, ca)
    if spec.backend == "crossbar":
        gp = jnp.maximum(prob["K"], 0.0)
        gn = jnp.maximum(-prob["K"], 0.0)
        R = C = m + n
        gpf = jnp.zeros((R, C), dt).at[:m, m:].set(gp)
        gnf = jnp.zeros((R, C), dt).at[:m, m:].set(gn)
        return engine.crossbar_operator(gpf, gnf, jnp.asarray(1.0, dt),
                                        m, n)
    return None                  # dense / bcoo: solve_core self-mounts


def _static_tuple(spec: PathSpec):
    from repro.core.pdhg import PDHGOptions, opts_static
    opts = PDHGOptions(
        max_iters=MAX_ITERS, check_every=CHECK_EVERY,
        kernel=spec.kernel, step_rule=spec.step_rule,
        megakernel=spec.megakernel, restart=spec.restart,
        gamma=spec.gamma, refine_rounds=spec.refine,
        sparse_kernel="bcoo" if spec.backend == "bcoo" else "ell")
    return opts_static(opts)


def _trace_refined(spec: PathSpec, prob, engine, operator_override=None):
    """Refined paths: ``crossbar.refine.refined_core`` — digital exact
    operator blocks for the residual MVMs, the backend's analog mount for
    every inner solve (crossbar paths mount ``crossbar_operator`` the way
    the batched pipeline does; dense self-mounts like the eager
    ``solve_crossbar_refined`` driver)."""
    import functools

    import jax

    from repro.crossbar.refine import refined_core

    static = _static_tuple(spec)
    key = jax.random.PRNGKey(0)
    operator = (operator_override if operator_override is not None
                else _make_operator(spec, prob, engine))
    fn = (refined_core if operator is None else
          functools.partial(refined_core, operator=operator))
    K = prob["K"]
    return jax.make_jaxpr(fn, static_argnums=(12,))(
        K, K.T, K, K.T, prob["b"], prob["c"], prob["lb"], prob["ub"],
        prob["T"], prob["Sigma"], prob["rho"], key, static)


def _trace_sharded(spec: PathSpec, prob):
    """The distributed path: ``pdhg_loop`` under ``shard_map`` on a
    1-device ("data", "model") mesh with psum reduction hooks, the
    structure ``distributed/pdhg_dist.solve_dist`` runs per pod."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import engine
    from repro.distributed.sharding import col_axes, row_axes

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    rax, cax = row_axes(mesh), col_axes(mesh)
    key = jax.random.PRNGKey(0)

    def local_solve(K, b, c, lb, ub, T, Sigma):
        op = engine.sharded_operator(K, rax, cax)
        k2, x0, y0 = engine.draw_init(key, b.shape[0], c.shape[0],
                                      lb, ub, b.dtype)
        xsum = lambda v: jax.lax.psum(jnp.sum(v), cax)   # noqa: E731
        ysum = lambda v: jax.lax.psum(jnp.sum(v), rax)   # noqa: E731
        return engine.pdhg_loop(
            op, engine.JNP_UPDATES, b, c, lb, ub, T, Sigma, x0, y0,
            0.1, 0.1, k2, max_iters=MAX_ITERS, tol=1e-6,
            gamma=spec.gamma, check_every=CHECK_EVERY, restart_beta=0.5,
            restart=spec.restart, step_rule=spec.step_rule,
            xsum_fn=xsum, ysum_fn=ysum)

    fn = jax.shard_map(
        local_solve, mesh=mesh,
        in_specs=(P(rax, cax), P(rax), P(cax), P(cax), P(cax), P(cax),
                  P(rax)),
        out_specs=(P(cax), P(rax), P(), P()), check_vma=False)
    return jax.make_jaxpr(fn)(prob["K"], prob["b"], prob["c"],
                              prob["lb"], prob["ub"], prob["T"],
                              prob["Sigma"])


def trace_path(spec: PathSpec, operator_override=None):
    """Trace one path combo to a ClosedJaxpr (cached per name unless an
    override operator is injected — the test hook for seeded lies)."""
    _ensure_import_paths()
    if operator_override is None and spec.name in _TRACE_CACHE:
        return _TRACE_CACHE[spec.name]

    import functools

    import jax
    import jax.numpy as jnp

    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        from repro.core import engine
        prob = _problem(jnp)
        if spec.backend == "sharded":
            jaxpr = _trace_sharded(spec, prob)
        elif spec.refine > 0:
            jaxpr = _trace_refined(spec, prob, engine, operator_override)
        else:
            static = _static_tuple(spec)
            key = jax.random.PRNGKey(0)
            operator = (operator_override if operator_override is not None
                        else _make_operator(spec, prob, engine))
            if spec.backend == "bcoo" and operator_override is None:
                from jax.experimental import sparse as jsparse
                K_fwd, K_adj = jsparse.BCOO.fromdense(prob["K"]), None
            elif operator is None:
                K_fwd, K_adj = prob["K"], prob["K"].T
            else:
                K_fwd = K_adj = None
            fn = (engine.solve_core if operator is None else
                  functools.partial(engine.solve_core, operator=operator))
            jaxpr = jax.make_jaxpr(fn, static_argnums=(10,))(
                K_fwd, K_adj, prob["b"], prob["c"], prob["lb"],
                prob["ub"], prob["T"], prob["Sigma"], prob["rho"],
                key, static)
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
    if operator_override is None:
        _TRACE_CACHE[spec.name] = jaxpr
    return jaxpr


# ------------------------------------------------------- jaxpr walking ---

def _subjaxprs(eqn):
    """(param_name, jaxpr) pairs for every sub-jaxpr an eqn carries —
    pjit/scan/while/cond bodies, shard_map/pallas_call kernels."""
    for pname in sorted(eqn.params):
        val = eqn.params[pname]
        vals = val if isinstance(val, (list, tuple)) else [val]
        for i, sub in enumerate(vals):
            if hasattr(sub, "eqns"):
                yield f"{pname}{i}", sub
            elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                yield f"{pname}{i}", sub.jaxpr


def build_regions(jaxpr) -> Tuple[Dict[str, dict], List[tuple]]:
    """Flatten a jaxpr into loop-nesting regions.

    Returns ``(regions, edges)``: ``regions[rid]`` holds the region's
    eqns and whether it executes under a ``while`` body (the hot-loop
    "window"); ``edges`` are ``(parent, child, trip)`` triples feeding
    ``launch.hlo.propagate_multipliers`` — a ``scan`` body's trip is its
    static ``length``, everything else is 1 (a ``while`` trip is
    unknowable statically, which is exactly why budgets are PER WINDOW).
    """
    regions: Dict[str, dict] = {}
    edges: List[tuple] = []

    def visit(jx, rid: str, window: bool) -> None:
        regions[rid] = {"eqns": list(jx.eqns), "window": window}
        for i, eqn in enumerate(jx.eqns):
            name = eqn.primitive.name
            trip = 1.0
            if name == "scan":
                trip = float(eqn.params.get("length", 1))
            child_window = window or name == "while"
            for pname, sub in _subjaxprs(eqn):
                crid = f"{rid}/{i}.{name}.{pname}"
                edges.append((rid, crid, trip))
                visit(sub, crid, child_window)

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, "root", False)
    return regions, edges


def _region_multipliers(regions, edges):
    from repro.launch.hlo import propagate_multipliers
    return propagate_multipliers(regions, edges)


def classify_mvm(eqn) -> Optional[str]:
    """MVM-bearing primitive classes (None for everything else):

    dot     ``dot_general`` with an operand of rank >= 2 (rank-1 pairs
            are the residual/merit vdots — NOT operator applications)
    bcoo    any ``bcoo_dot_general`` variant (BCOO SpMV)
    gather  the ELL row gather: rank-1 source indexed to a rank-2
            (rows x width) block — ``ell_matvec``'s take expression
    """
    name = eqn.primitive.name
    if name == "dot_general":
        if max(v.aval.ndim for v in eqn.invars) >= 2:
            return "dot"
    if name.startswith("bcoo_dot_general"):
        return "bcoo"
    if name == "gather":
        if (eqn.invars[0].aval.ndim == 1
                and eqn.outvars[0].aval.ndim == 2):
            return "gather"
    return None


def count_mvms(jaxpr) -> Dict[str, float]:
    """Trip-scaled MVM counts split into ``outside`` (per solve) and
    ``per_window`` (per while-body execution)."""
    _ensure_import_paths()
    regions, edges = build_regions(jaxpr)
    mults = _region_multipliers(regions, edges)
    out = {"outside": 0.0, "per_window": 0.0}
    for rid, reg in regions.items():
        n = sum(1 for e in reg["eqns"] if classify_mvm(e))
        if not n:
            continue
        bucket = "per_window" if reg["window"] else "outside"
        out[bucket] += n * mults[rid]
    return out


def primitive_histogram(jaxpr) -> Dict[str, float]:
    """Trip-scaled primitive counts across all regions (the
    human-readable axis of the structural fingerprint diff)."""
    _ensure_import_paths()
    regions, edges = build_regions(jaxpr)
    mults = _region_multipliers(regions, edges)
    hist: Dict[str, float] = {}
    for rid, reg in regions.items():
        for eqn in reg["eqns"]:
            name = eqn.primitive.name
            hist[name] = hist.get(name, 0.0) + mults[rid]
    return hist


# --------------------------------------------------------- analyzers ---

@dataclasses.dataclass(frozen=True)
class Finding:
    path: str            # solver path name (the audit's "file")
    analyzer: str        # budget | dtype | effects | fingerprint
    message: str

    def __str__(self) -> str:
        return f"{self.path}: {self.analyzer} {self.message}"


def check_budget(spec: PathSpec, counts: Dict[str, float],
                 check_every: int = CHECK_EVERY) -> List[Finding]:
    """The ledger cross-check: traced per-window MVMs must equal
    ``engine.mvm_window_budget`` and nothing MVM-shaped may run outside
    the loop (norm estimation is ledgered separately and is not part of
    ``solve_core``)."""
    _ensure_import_paths()
    from repro.core import engine
    findings = []
    window_factor = engine.refine_window_factor(spec.refine)
    expected = (window_factor
                * engine.mvm_window_budget(check_every, spec.restart))
    got = counts["per_window"]
    if got != expected:
        findings.append(Finding(
            spec.name, "budget",
            f"per-window MVM count {got:g} != "
            f"{window_factor}*mvm_window_budget "
            f"{expected} (= {window_factor} analog solve(s) x "
            f"({engine.MVMS_PER_ITERATION}*{check_every} iterations + "
            f"{engine.mvms_per_check(spec.restart)} check)) "
            "— the energy ledger and the traced computation disagree"))
    expected_outside = engine.refine_digital_mvms(spec.refine)
    if counts["outside"] != expected_outside:
        findings.append(Finding(
            spec.name, "budget",
            f"{counts['outside']:g} MVM-bearing primitive(s) outside "
            f"the while loops, expected {expected_outside} "
            "(refine_digital_mvms: the refinement shell's exact residual"
            "/candidate MVMs run digitally outside the analog loops; "
            "anything beyond that is an unledgered device read)"))
    return findings


def check_adaptive_delta(records) -> List[Finding]:
    """PR 8's claim, made mechanical: for every (backend, kernel,
    megakernel, restart) family, ``adaptive`` must trace to EXACTLY the
    fixed rule's per-window MVM count."""
    by_family: Dict[tuple, dict] = {}
    for rec in records:
        s = rec.spec
        fam = (s.backend, s.kernel, s.megakernel, s.restart, s.refine)
        by_family.setdefault(fam, {})[s.step_rule] = rec
    findings = []
    for fam, rules in by_family.items():
        if "fixed" not in rules or "adaptive" not in rules:
            continue
        fx = rules["fixed"].counts["per_window"]
        ad = rules["adaptive"].counts["per_window"]
        if fx != ad:
            findings.append(Finding(
                rules["adaptive"].spec.name, "budget",
                f"adaptive step rule adds {ad - fx:+g} MVMs per window "
                f"vs fixed ({ad:g} vs {fx:g}) — the rule is specified "
                "to rebalance from already-computed quantities at zero "
                "extra MVM cost"))
    return findings


def _float_itemsize(dtype) -> Optional[int]:
    import numpy as np
    d = np.dtype(dtype)
    return d.itemsize if np.issubdtype(d, np.floating) else None


def check_dtype(spec_name: str, jaxpr) -> List[Finding]:
    """Silent float narrowing + mixed-precision accumulation.  Paths
    are traced in f64, so ANY float-narrowing convert is a demotion
    written in code (weak-type promotion never narrows)."""
    _ensure_import_paths()
    regions, _ = build_regions(jaxpr)
    findings = []
    for rid, reg in regions.items():
        for eqn in reg["eqns"]:
            name = eqn.primitive.name
            if name == "convert_element_type":
                src = _float_itemsize(eqn.invars[0].aval.dtype)
                dst = _float_itemsize(eqn.outvars[0].aval.dtype)
                if src is not None and dst is not None and dst < src:
                    findings.append(Finding(
                        spec_name, "dtype",
                        f"silent float narrowing "
                        f"{eqn.invars[0].aval.dtype} -> "
                        f"{eqn.outvars[0].aval.dtype} in {rid}"))
            elif name == "dot_general":
                ins = [_float_itemsize(v.aval.dtype) for v in eqn.invars]
                ins = [i for i in ins if i is not None]
                out_sz = _float_itemsize(eqn.outvars[0].aval.dtype)
                if ins and out_sz is not None and out_sz < max(ins):
                    findings.append(Finding(
                        spec_name, "dtype",
                        f"mixed-precision accumulation: dot output "
                        f"{eqn.outvars[0].aval.dtype} narrower than its "
                        f"operands in {rid}"))
    return findings


# host-sync / host-callback primitives that must never run per-iteration
EFFECT_DENYLIST = frozenset({
    "pure_callback", "debug_callback", "io_callback", "infeed",
    "outfeed", "device_put", "copy_to_host_async", "debug_print",
})


def check_effects(spec_name: str, jaxpr) -> List[Finding]:
    _ensure_import_paths()
    regions, _ = build_regions(jaxpr)
    findings = []
    for rid, reg in regions.items():
        if not reg["window"]:
            continue
        for eqn in reg["eqns"]:
            if eqn.primitive.name in EFFECT_DENYLIST:
                findings.append(Finding(
                    spec_name, "effects",
                    f"{eqn.primitive.name} inside the hot loop ({rid}) "
                    "— host round-trips per iteration serialize the "
                    "solve"))
    return findings


# ------------------------------------------------ structural fingerprint ---

def _canon_value(v) -> Optional[str]:
    """Deterministic, machine-independent rendering of a param value;
    None when the value may embed paths/object identity (those params
    are named but not valued in the canonical form)."""
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return repr(v)
    if isinstance(v, (list, tuple)):
        parts = [_canon_value(x) for x in v]
        if any(p is None for p in parts):
            return None
        return "(" + ",".join(parts) + ")"
    import numpy as np
    try:
        if isinstance(v, np.dtype) or (isinstance(v, type)
                                       and issubclass(v, np.generic)):
            return str(np.dtype(v))
        if isinstance(v, np.generic):
            return repr(v.item())
    except Exception:
        pass
    return None


def canonical_render(jaxpr) -> str:
    """Structural dump with NO variable names: each eqn renders as
    ``prim[params] in_avals -> out_avals`` with sub-jaxprs indented
    beneath it.  Stable under alpha-renaming by construction."""
    lines: List[str] = []

    def aval_str(v):
        s = str(v.aval)
        if hasattr(v, "val"):         # Literal: the value is structure
            return f"{s}={v.val!r}"
        return s

    def visit(jx, depth):
        pad = "  " * depth
        for eqn in jx.eqns:
            params = []
            for k in sorted(eqn.params):
                if any(True for _ in _subjaxprs_of_value(eqn.params[k])):
                    params.append(f"{k}=<jaxpr>")
                    continue
                cv = _canon_value(eqn.params[k])
                params.append(f"{k}={cv}" if cv is not None
                              else f"{k}=<{type(eqn.params[k]).__name__}>")
            ins = ",".join(aval_str(v) for v in eqn.invars)
            outs = ",".join(str(v.aval) for v in eqn.outvars)
            lines.append(f"{pad}{eqn.primitive.name}"
                         f"[{';'.join(params)}] {ins} -> {outs}")
            for pname, sub in _subjaxprs(eqn):
                lines.append(f"{pad} <{pname}>")
                visit(sub, depth + 1)

    def _subjaxprs_of_value(val):
        vals = val if isinstance(val, (list, tuple)) else [val]
        for sub in vals:
            if hasattr(sub, "eqns") or (hasattr(sub, "jaxpr")
                                        and hasattr(sub.jaxpr, "eqns")):
                yield sub

    visit(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, 0)
    return "\n".join(lines)


def fingerprint(jaxpr) -> str:
    return hashlib.sha256(canonical_render(jaxpr).encode()).hexdigest()


# ------------------------------------------------------------ baseline ---

def load_baseline(path=BASELINE_PATH) -> Optional[dict]:
    p = Path(path)
    if not p.exists():
        return None
    return json.loads(p.read_text())


def histogram_diff(old: Dict[str, float], new: Dict[str, float]) -> str:
    lines = []
    for prim in sorted(set(old) | set(new)):
        a, b = old.get(prim, 0.0), new.get(prim, 0.0)
        if a != b:
            lines.append(f"    {prim}: {a:g} -> {b:g} ({b - a:+g})")
    if not lines:
        return ("    primitive histogram identical — drift is at the "
                "param/dtype/ordering level")
    return "\n".join(lines)


@dataclasses.dataclass
class PathRecord:
    spec: PathSpec
    counts: Dict[str, float]
    histogram: Dict[str, float]
    fingerprint: str


def analyze_path(spec: PathSpec, jaxpr) -> PathRecord:
    return PathRecord(spec=spec, counts=count_mvms(jaxpr),
                      histogram=primitive_histogram(jaxpr),
                      fingerprint=fingerprint(jaxpr))


def compare_to_baseline(records: List[PathRecord], baseline: dict,
                        full_matrix: bool) -> Tuple[List[Finding],
                                                    List[str]]:
    """Findings (hard failures) + notes (version-skew soft warnings).

    ``full_matrix`` gates the missing/stale-entry checks: a filtered
    run cannot judge baseline completeness."""
    import jax
    findings: List[Finding] = []
    notes: List[str] = []
    same_version = baseline.get("jax_version") == jax.__version__
    if not same_version:
        notes.append(
            f"baseline traced under jax {baseline.get('jax_version')}, "
            f"running {jax.__version__}: fingerprint drift reported as "
            "notes, not failures (budget/dtype/effects still gate)")
    base_paths = baseline.get("paths", {})
    for rec in records:
        base = base_paths.get(rec.spec.name)
        if base is None:
            findings.append(Finding(
                rec.spec.name, "fingerprint",
                "path missing from TRACE_BASELINE.json — new path? "
                "rerun with --update-baseline and commit the result"))
            continue
        if base["fingerprint"] != rec.fingerprint:
            diff = histogram_diff(base.get("primitives", {}),
                                  rec.histogram)
            msg = ("traced structure drifted from baseline; "
                   "primitive-level diff:\n" + diff +
                   "\n    intentional? rerun with --update-baseline "
                   "and commit the new TRACE_BASELINE.json")
            if same_version:
                findings.append(Finding(rec.spec.name, "fingerprint",
                                        msg))
            else:
                notes.append(f"{rec.spec.name}: {msg}")
    if full_matrix:
        audited = {r.spec.name for r in records}
        for name in sorted(set(base_paths) - audited):
            findings.append(Finding(
                name, "fingerprint",
                "baseline entry no longer matches any supported path — "
                "stale; rerun with --update-baseline"))
    return findings, notes


def make_baseline(records: List[PathRecord]) -> dict:
    import jax
    return {
        "schema": BASELINE_SCHEMA,
        "jax_version": jax.__version__,
        "trace_shape": [TRACE_M, TRACE_N],
        "check_every": CHECK_EVERY,
        "paths": {
            rec.spec.name: {
                "fingerprint": rec.fingerprint,
                "mvms": rec.counts,
                "primitives": {k: rec.histogram[k]
                               for k in sorted(rec.histogram)},
            } for rec in sorted(records, key=lambda r: r.spec.name)
        },
    }


def save_baseline(records: List[PathRecord],
                  path=BASELINE_PATH) -> None:
    Path(path).write_text(json.dumps(make_baseline(records), indent=1)
                          + "\n")


# --------------------------------------------------------------- audit ---

def audit_paths(specs: List[PathSpec],
                baseline: Optional[dict] = None,
                full_matrix: bool = False):
    """Trace + analyze each spec.  Returns (records, findings, notes)."""
    records: List[PathRecord] = []
    findings: List[Finding] = []
    for spec in specs:
        jaxpr = trace_path(spec)
        rec = analyze_path(spec, jaxpr)
        records.append(rec)
        findings.extend(check_budget(spec, rec.counts))
        findings.extend(check_dtype(spec.name, jaxpr))
        findings.extend(check_effects(spec.name, jaxpr))
    findings.extend(check_adaptive_delta(records))
    notes: List[str] = []
    if baseline is not None:
        bf, notes = compare_to_baseline(records, baseline, full_matrix)
        findings.extend(bf)
    return records, findings, notes
