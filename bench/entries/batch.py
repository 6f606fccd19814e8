"""Entry: a list of instances per call through
``repro.runtime.batch.BatchSolver.solve_stream`` (shape buckets, batch
padding, dense stacks or COO->ELL for sparse instances, one vmapped
executable per bucket; the ``--backend batch [--sparse]`` path).  One
solver serves every call, so its executable cache stays warm."""
from __future__ import annotations

import collections
import sys

import numpy as np

from bench import program


class Entry:
    def __init__(self, config: dict):
        from repro.runtime.batch import BatchSolver

        self.solver = BatchSolver(program.options(config))
        self.dtype = np.dtype(config["dtype"])
        # the lanes a bucket of k instances rides in; the scheduler keeps
        # its padding rule private, so name it once, and loudly
        self.padded_batch = getattr(self.solver, "_padded_batch", None)
        if self.padded_batch is None:
            print("bench: BatchSolver has no _padded_batch; "
                  "lane_useful_share.stream cannot be read",
                  file=sys.stderr)

    def prepare(self, call):
        return [program.to_program(inst, self.dtype) for inst in call]

    def programs(self):
        """HLO text of every compiled program the solver has run."""
        return self.solver.hlo_texts()

    def _lanes(self, count: int):
        return None if self.padded_batch is None else \
            int(self.padded_batch(count))

    def solve(self, payload):
        results = self.solver.solve_stream(payload)
        per_bucket = collections.Counter(
            (tuple(r.bucket), r.sparse) for r in results)
        return [{"status": r.status, "x": r.x, "y": r.y,
                 "iterations": int(r.iterations), "merit": float(r.merit),
                 "bucket": tuple(int(v) for v in r.bucket),
                 "lanes": self._lanes(per_bucket[(tuple(r.bucket),
                                                  r.sparse)])}
                for r in results]


def make(config: dict) -> Entry:
    return Entry(config)
