"""analog_reads_per_instance.stream: analog reads of the programmed
array an answer's solves take (norm estimate, the windows its bucket ran
in lockstep, the checks, over every refinement round), averaged over the
window's answers.  The count is the plain reference's
(``bench/xbar_reference.reads``) from the iterations the bucket ran
(``executed_iterations``), not the program's own ledger, so a ledger
that charges fewer reads cannot read as a gain.  Nothing when the entry
reports no executed iterations."""
from bench.xbar_reference import reads


def read(run):
    executed = [a.get("executed_iterations") for a in run.answers]
    if not executed or any(e is None for e in executed):
        return None
    cfg = run.cell.config
    every = int(cfg["check_every"])
    lanczos = int(cfg["lanczos_iters"])
    return sum(reads(e, every, lanczos) for e in executed) / len(executed)
