"""ell_gnnz_per_s.stream: logical nonzeros multiplied per second of
device time, in G nonzeros/s.  Each instance's iterations multiply by K
and K^T once each, and every ``check_every`` iterations four more times,
over its logical (not padded) nonzeros; the time is the device busy
time inside the traced rounds.  Counting the iterations each instance
needs and its logical nonzeros makes any implementation read the same
work, so less padding, fewer lockstep iterations and a faster gather
all show as a gain."""
from bench.kernels import sparse_iteration_nnz


def read(run):
    busy = run.device_busy_s()
    if busy is None:
        return None
    every = int(run.cell.config["check_every"])
    work = sum(sparse_iteration_nnz(inst.nnz, every) * a["iterations"]
               for c in run.traced_calls
               for inst, a in zip(c.instances, c.answers))
    return work / busy / 1e9
