"""xbar_hbm_share.stream: the least bytes of conductances the traced
rounds' crossbar reads must move (``bench/xbar_work.py``: each answer's
iterations, over its logical m x n), over the device busy time inside
those rounds, as a share of the chip's HBM bandwidth, in percent.
Padding to tiles, reading the whole programmed M and lockstep lanes are
work beyond that least count, so they show as a lower share.

The profiler keeps only the first few million device events, and a
cycle of this cell makes more, so the later rounds of a traced cycle can
be cut short or missing from the trace.  Only the rounds the trace holds
whole count, in bytes and in busy time alike: those after which a kept
device event starts.  Nothing when no round is whole."""
import numpy as np

from bench.xbar_work import read_bytes


def whole_rounds(run):
    """The traced rounds whose device events the trace holds in full, as
    ``(span start, span end, call)``."""
    if run.trace is None or not run.trace.device_ops:
        return []
    spans = run.trace.spans_named(run.span)
    calls = run.traced_calls
    if len(spans) != len(calls):
        return []
    # the events kept are a prefix in time on each device
    last = min(max((s for s, _, _ in ops), default=0)
               for ops in run.trace.device_ops.values())
    return [(lo, hi, c) for (lo, hi, _), c in zip(spans, calls) if last > hi]


def read(run):
    rounds = whole_rounds(run)
    busy = sum(run.trace.busy_ns(lo, hi) for lo, hi, _ in rounds) * 1e-9
    if busy <= 0:
        return None
    cfg = run.cell.config
    every = int(cfg["check_every"])
    itemsize = np.dtype(cfg["dtype"]).itemsize
    moved = sum(read_bytes(*inst.shape, a["iterations"], every, itemsize)
                for _, _, c in rounds
                for inst, a in zip(c.instances, c.answers))
    return 100.0 * moved / busy / float(run.peaks["hbm_bytes_per_s"])
