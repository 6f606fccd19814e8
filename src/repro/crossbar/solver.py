"""Fast (jitted) crossbar PDHG: device physics + analytic energy ledger.

The host-loop path (``core.pdhg.solve`` + ``crossbar_accel_factory``)
simulates every MVM through the tile model — maximal fidelity, but eager
per-call overhead makes 40k-iteration benchmark sweeps slow on one CPU
core.  This module runs the SAME device physics inside the jitted solver:

  1. Encode M = [[0,K],[K^T,0]] once (quantization + residual programming
     error; the K and K^T blocks are physically distinct cells and carry
     independent error) — ledgered as WRITE.
  2. Decode the two programmed blocks K_fwd (≈K) and K_adj (≈K^T) and run
     ``core.pdhg.solve_jit`` with per-MVM multiplicative read noise.
  3. Charge READ energy/latency analytically from the iteration count
     (2 MVMs per PDHG iteration + residual checks + Lanczos), identical
     cost constants to the host path.

Stream serving is DEVICE-TILE-AWARE and batched: ``CrossbarBatchSolver``
(a ``runtime.batch.BatchSolver`` subclass) buckets instances to multiples
of the physical crossbar tile, then encodes AND solves each bucket
through one vmapped compiled pipeline — programming a stacked (B, R, C)
operator array and solving all B instances in a single dispatch, with the
compiled executable cached per (bucket, batch, dtype, options, device)
signature.  Per-instance encode statistics come back from the pipeline so
each report's energy ledger is accumulated vectorized, with logical vs.
padding cells ledgered separately.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import engine
from ..core import pdhg as pdhg_mod
from ..core.pdhg import PDHGOptions, PDHGResult
from ..core.lanczos import lanczos_svd_jit
from ..core.residuals import kkt_residuals
from ..core.symblock import build_sym_block
from ..lp.problem import StandardLP
from ..runtime.batch import BatchSolver, _ceil_to, opts_static, prep_scale
from .device import DeviceModel, EPIRAM
from .encode import charge_write, encode_core, encode_matrix
from .energy import Ledger


@dataclasses.dataclass
class CrossbarSolveReport:
    result: PDHGResult
    ledger: Ledger
    device: DeviceModel
    lanczos_mvms: int
    pdhg_mvms: int
    # iterations the hardware actually EXECUTED (and the ledger charged).
    # On the batched path a vmapped while_loop runs every lane until the
    # slowest lane's check window completes, so an early-converged
    # instance executes (and pays for) more windows than
    # ``result.iterations`` reports; on single-instance paths the two
    # coincide.  With refinement this sums the executed windows of every
    # round.
    executed_iterations: int = 0
    # exact full-precision residual MVMs issued by the digital refinement
    # shell (``engine.refine_digital_mvms``) — digital co-processor work,
    # deliberately NOT charged to the analog read ledger
    digital_mvms: int = 0


def _charge_reads(ledger: Ledger, device: DeviceModel, n_mvms: int,
                  active_cells: float):
    """Charge ``n_mvms`` analog reads: each draws read energy from every
    active cell (``2 * pairs * fill * ecc``) and takes one read latency
    (tiles fire in parallel)."""
    ledger.read_energy_j += (n_mvms * active_cells
                             * device.read_energy_per_cell_j)
    ledger.read_latency_s += n_mvms * device.read_latency_s
    ledger.mvm_count += n_mvms


def answer_status(merit: float, opts: PDHGOptions) -> str:
    """Status of a crossbar answer from its final merit.  With
    refinement on and a ``refine_tol`` set, that is the answer's target
    and ``merit`` is the exact digital KKT merit; ``tol`` is then only
    the inner stop of each analog solve, which sits at the read-noise
    floor.  Otherwise the target is ``tol``."""
    refined = opts.refine_rounds > 0 and opts.refine_tol > 0.0
    target = opts.refine_tol if refined else opts.tol
    if not np.isfinite(merit):
        return "diverged"       # NaN merit: blow-up, not a limit
    return "optimal" if merit <= target else "iteration_limit"


def solve_crossbar_jit(
    lp: StandardLP,
    opts: PDHGOptions = PDHGOptions(),
    device: DeviceModel = EPIRAM,
    key: Optional[jax.Array] = None,
    ledger: Optional[Ledger] = None,
) -> CrossbarSolveReport:
    if opts.refine_rounds > 0:
        # digital iterative-refinement shell: same encode-once contract,
        # extra analog read windows per round, zero extra writes
        from . import refine as refine_mod
        return refine_mod.solve_crossbar_refined(lp, opts, device, key,
                                                 ledger)
    if key is None:
        key = jax.random.PRNGKey(opts.seed)
    ledger = ledger if ledger is not None else Ledger()

    # Ruiz-scale on host first (Algorithm 4 step 0), then program M once.
    scaled, _T, _Sigma = pdhg_mod.prepare(lp, opts)
    m, n = scaled.K.shape
    M = build_sym_block(scaled.K)
    enc = encode_matrix(M, device, key, ledger=ledger)
    M_prog = enc.decode()
    K_fwd = M_prog[:m, m:]          # programmed K block
    K_adj = M_prog[m:, :m]          # programmed K^T block (distinct cells)

    result = pdhg_mod.solve_jit(
        lp, opts, K_fwd=K_fwd, K_adj=K_adj, sigma_read=device.sigma_read
    )
    # READ accounting: ``result.mvm_calls`` already counts Lanczos
    # (1 MVM/iter, ``result.lanczos_iters``) + PDHG (2/iter) + residual
    # checks (4 per check: x/y pair for current and averaged iterates) —
    # charge it wholesale.
    lanczos_mvms = result.lanczos_iters
    pdhg_mvms = result.mvm_calls - lanczos_mvms
    _charge_reads(ledger, device, result.mvm_calls, enc.active_cells)
    return CrossbarSolveReport(
        result=result, ledger=ledger, device=device,
        lanczos_mvms=lanczos_mvms, pdhg_mvms=pdhg_mvms,
        executed_iterations=result.iterations,
    )


# ------------------------------------------------- batched stream serving ---

def _array_dims(mb: int, nb: int, device: DeviceModel) -> Tuple[int, int]:
    """Physical array shape of the programmed symmetric block M for a
    (mb, nb) bucket: (mb+nb) rounded up to whole tiles.  With square
    tiles (the shipped devices) this is the identity, but rectangular
    tiles leave (mb+nb) mid-tile in one dimension."""
    d = mb + nb
    return (_ceil_to(d, device.crossbar_rows),
            _ceil_to(d, device.crossbar_cols))


def read_tiles(mb: int, nb: int, device: DeviceModel) -> Tuple[int, int]:
    """Kernel tiles that one ``fwd`` and one ``adj`` read of an (mb, nb)
    bucket visit together (``crossbar_mvm.read_windows``), and the tiles
    of the whole tile-padded array, which one read of the whole pair
    visits."""
    from ..kernels import crossbar_mvm as xbar  # deferred, as in engine

    R, C = _array_dims(mb, nb, device)
    pair = sum(rows[1] * cols[1]
               for rows, cols in xbar.read_windows(mb, nb))
    whole = (xbar.tiles(0, R, xbar.TILE_R)[1]
             * xbar.tiles(0, C, xbar.TILE_C)[1])
    return pair, whole


def make_crossbar_bucket_pipeline(opts: PDHGOptions, device: DeviceModel):
    """vmapped prep + encode + solve over a stacked (B, m, n) bucket.

    Per instance: Ruiz/diagonal preconditioning, differential-pair
    programming of M (independent error on the K and K^T blocks), Lanczos
    on the PROGRAMMED operator (or ``opts.norm_override``), then the
    engine loop with the device's read noise.  ``opts.kernel`` selects
    the backends: ``"jnp"`` decodes the programmed blocks and runs the
    dense operator; ``"pallas"`` keeps the conductance pair ON DEVICE and
    issues every solve MVM through the tiled differential-pair kernel
    (``engine.crossbar_operator`` -> ``kernels.ops.crossbar_mvm``) with
    the fused update kernels.  Returns unscaled (xs, ys, its,
    merits, rhos, nz) — ``nz`` is the per-instance count of programmed
    differential pairs feeding the vectorized write ledger, and ``its``
    is the per-round iteration-count vector (length
    ``opts.refine_rounds + 1``; one entry per analog solve).

    With ``opts.refine_rounds > 0`` each lane runs the digital
    iterative-refinement shell (``crossbar.refine.refined_core``) around
    the same encode: the programmed conductance stack is reused by every
    round (zero extra writes), the exact scaled K feeds the digital
    residual MVMs, and the analog correction solves ride the same
    operator backend selection.
    """
    from .refine import refined_core   # deferred: refine imports solver

    static = opts_static(opts, device.sigma_read)

    def one(K, b, c, lb, ub, key):
        (Ks, bs, cs, lbs, ubs, T, Sigma, D1, D2) = prep_scale(
            K, b, c, lb, ub, opts)
        enc_key, solve_key = jax.random.split(key)
        m, n = K.shape
        with jax.named_scope(engine.ENCODE_SCOPE):
            M = build_sym_block(Ks)
            R, C = _array_dims(m, n, device)
            Mp = jnp.zeros((R, C), M.dtype).at[:m + n, :m + n].set(M)
            g_pos, g_neg, scale, nz = encode_core(
                Mp, enc_key, device.g_levels, device.sigma_program,
                ecc=device.ecc, ecc_decode=device.ecc_decode,
                stuck_rate=device.stuck_rate, drift=device.drift)
            M_prog = (g_pos - g_neg) * scale
            K_fwd = M_prog[:m, m:m + n]
            K_adj = M_prog[m:m + n, :m]
        if opts.norm_override is not None:
            rho = jnp.asarray(opts.norm_override, K.dtype)
        else:
            # operator norm of the operator actually executed (Lemma 2
            # margin widened for the noisy estimate, as in solve_jit)
            with jax.named_scope(engine.NORM_SCOPE):
                Keff = (jnp.sqrt(Sigma)[:, None] * K_fwd
                        * jnp.sqrt(T)[None, :])
                rho = engine.lemma2_margin(
                    lanczos_svd_jit(build_sym_block(Keff),
                                    k_max=opts.lanczos_iters),
                    device.sigma_read)
        op = (engine.crossbar_operator(g_pos, g_neg, scale, m, n,
                                       sigma_read=device.sigma_read)
              if opts.kernel == "pallas" else None)   # None -> dense decode
        if opts.refine_rounds > 0:
            x, y, its, merit = refined_core(
                Ks, Ks.T, K_fwd, K_adj, bs, cs, lbs, ubs, T, Sigma, rho,
                solve_key, static, operator=op)
        else:
            x, y, it, merit = engine.solve_core(
                K_fwd, K_adj, bs, cs, lbs, ubs, T, Sigma, rho, solve_key,
                static, operator=op)
            its = jnp.reshape(it, (1,))
        return D2 * x, D1 * y, its, merit, rho, nz

    def pipeline(Ks, bs, cs, lbs, ubs, keys):
        return jax.vmap(one)(Ks, bs, cs, lbs, ubs, keys)

    return pipeline


class CrossbarBatchSolver(BatchSolver):
    """Device-tile-aware bucketing scheduler for crossbar-simulated LPs.

    Buckets snap to multiples of ``device.crossbar_rows/cols`` (whole
    physical tiles), each bucket is encoded + solved by one vmapped
    compiled executable, and the cache key carries the device model, so
    traffic mixing devices or shapes compiles at most once per
    (bucket, batch, device) signature.  ``solve_stream`` returns
    ``CrossbarSolveReport`` objects (per-instance energy ledger included;
    residuals reported in ORIGINAL coordinates).

    Sparse instances densify on entry (``supports_sparse = False``): a
    crossbar programs every physical cell of its tiles regardless of the
    operator's sparsity, so there is no memory to save device-side.

    ``last_stream_stats`` adds the call's ledger totals, summed over its
    reports: ``analog_mvms`` (charged analog reads), ``digital_mvms``
    (the refinement shell's exact products), ``cells_written``,
    ``write_energy_j`` and ``read_energy_j``; and the 128x128 kernel
    tiles of the PDHG products (the ledger's ``pdhg_mvms``, half of
    them ``fwd``, half ``adj``): ``xbar_tiles_read``, those the windowed
    reads visit, and ``xbar_tiles_array``, those reads of the whole
    array would visit.  The norm estimate runs on the decoded blocks,
    not through the read, so its reads are in neither.
    """

    supports_sparse = False
    dense_operator = "crossbar"

    def __init__(self, opts: PDHGOptions = PDHGOptions(), *,
                 device: DeviceModel = EPIRAM, mesh=None,
                 batch_axes: Tuple[str, ...] = ("data",),
                 kernel: Optional[str] = None):
        super().__init__(
            opts, mesh=mesh, batch_axes=batch_axes,
            sigma_read=device.sigma_read,
            tile=(device.crossbar_rows, device.crossbar_cols),
            kernel=kernel)
        self.device = device

    def _device_signature(self):
        return self.device           # frozen dataclass -> hashable

    def solve_stream(self, lps: Sequence[StandardLP]
                     ) -> List[CrossbarSolveReport]:
        reports = super().solve_stream(lps)
        tiles_read = tiles_array = 0
        for lp, r in zip(lps, reports):
            pair, whole = read_tiles(*self._bucket(*lp.K.shape),
                                     self.device)
            tiles_read += r.pdhg_mvms // 2 * pair
            tiles_array += r.pdhg_mvms * whole
        self.last_stream_stats.update(
            xbar_tiles_read=tiles_read, xbar_tiles_array=tiles_array,
            analog_mvms=sum(r.ledger.mvm_count for r in reports),
            digital_mvms=sum(r.digital_mvms for r in reports),
            cells_written=sum(r.ledger.cells_written for r in reports),
            write_energy_j=sum(r.ledger.write_energy_j for r in reports),
            read_energy_j=sum(r.ledger.read_energy_j for r in reports))
        return reports

    def _make_pipeline(self):
        return make_crossbar_bucket_pipeline(self.opts, self.device)

    def _collect(self, out, bucket, idxs, lps, results) -> None:
        xs, ys, its, merits, rhos, nzs = (np.asarray(a) for a in out)
        mb, nb = bucket
        R, C = _array_dims(mb, nb, self.device)
        pairs_total = R * C                # tile-padded physical array
        lanczos_mvms = (0 if self.opts.norm_override is not None
                        else self.opts.lanczos_iters)
        # The vmapped while_loop physically executes EVERY lane (filler
        # lanes included) until the slowest lane's check window
        # completes, so the hardware runs — and the ledger must charge —
        # the bucket-max iteration count per analog solve, not each
        # instance's own early-exit count.  ``its`` is (B, rounds + 1):
        # one column per refinement round's analog solve; iteration
        # counts advance by ``check_every`` per window, so the column max
        # is already window-quantized.
        executed = its.max(axis=0)                  # per-round, all lanes
        executed_total = int(executed.sum())
        pdhg_mvms = int(sum(
            engine.mvm_accounting(int(e), self.opts.check_every, 0,
                                  restart=self.opts.restart)
            for e in executed))
        digital_mvms = engine.refine_digital_mvms(self.opts.refine_rounds)
        for k, i in enumerate(idxs):
            lp = lps[i]
            m, n = lp.K.shape
            x, y = xs[k, :n], ys[k, :m]
            it = int(its[k].sum())
            merit = float(merits[k])
            ledger = Ledger()
            fill = charge_write(ledger, self.device, float(nzs[k]),
                                pairs_logical=(m + n) ** 2,
                                pairs_total=pairs_total)
            active_cells = (2.0 * pairs_total * fill
                            * max(1, self.device.ecc))
            _charge_reads(ledger, self.device, lanczos_mvms + pdhg_mvms,
                          active_cells)
            res = kkt_residuals(
                jnp.asarray(x), jnp.asarray(x), jnp.asarray(y),
                jnp.asarray(lp.c), jnp.asarray(lp.b),
                jnp.asarray(lp.K @ x), jnp.asarray(lp.K.T @ y),
                lb=jnp.asarray(lp.lb), ub=jnp.asarray(lp.ub))
            result = PDHGResult(
                status=answer_status(merit, self.opts),
                x=x, y=y, obj=float(lp.c @ x), iterations=it,
                residuals=res, sigma_max=float(rhos[k]),
                lanczos_iters=lanczos_mvms,
                mvm_calls=lanczos_mvms + pdhg_mvms,
                merit=merit,
            )
            results[i] = CrossbarSolveReport(
                result=result, ledger=ledger, device=self.device,
                lanczos_mvms=lanczos_mvms, pdhg_mvms=pdhg_mvms,
                executed_iterations=executed_total,
                digital_mvms=digital_mvms,
            )


def solve_crossbar_stream(
    lps: Sequence[StandardLP],
    opts: PDHGOptions = PDHGOptions(),
    device: DeviceModel = EPIRAM,
    *,
    mesh=None,
    solver: Optional[CrossbarBatchSolver] = None,
) -> List[CrossbarSolveReport]:
    """Serve a heterogeneous LP stream on one simulated crossbar tier.

    Instances bucket to whole physical tiles and every bucket runs
    encode -> solve as ONE vmapped compiled call (see
    ``CrossbarBatchSolver``).  Pass ``solver`` to keep the compiled
    executables warm across streams.
    """
    if solver is None:
        solver = CrossbarBatchSolver(opts, device=device, mesh=mesh)
    return solver.solve_stream(lps)
