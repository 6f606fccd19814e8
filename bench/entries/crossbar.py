"""Entry: a list of instances per call through
``repro.crossbar.solver.CrossbarBatchSolver.solve_stream`` (buckets of
whole crossbar tiles, one vmapped executable per bucket that programs
M = [[0,K],[K^T,0]] once and solves on noisy analog reads of it, with
digital refinement; the ``--backend batch --device ...`` path).  One
solver serves every call, so its executable cache stays warm."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import program


class Entry:
    def __init__(self, config: dict):
        from repro.crossbar import DEVICES
        from repro.crossbar.solver import CrossbarBatchSolver

        device = DEVICES[config["device"]]
        # the plain reference reads the device's constants from the
        # configuration; refuse a program whose device says otherwise
        differ = {k: (getattr(device, k), v)
                  for k, v in config["device_model"].items()
                  if getattr(device, k) != v}
        if differ:
            raise ValueError(f"{config['device']} in the program differs "
                             f"from the configuration: {differ}")
        opts = dataclasses.replace(
            program.options(config), kernel=config["kernel"],
            refine_rounds=int(config["refine_rounds"]),
            refine_tol=float(config["refine_tol"]),
            lanczos_iters=int(config["lanczos_iters"]))
        self.solver = CrossbarBatchSolver(opts, device=device)
        self.dtype = np.dtype(config["dtype"])

    def prepare(self, call):
        return [program.to_program(inst, self.dtype) for inst in call]

    def programs(self):
        """HLO text of every compiled program the solver has run."""
        return self.solver.hlo_texts()

    def solve(self, payload):
        reports = self.solver.solve_stream(payload)
        return [{"status": r.result.status, "x": r.result.x,
                 "y": r.result.y, "iterations": int(r.result.iterations),
                 "merit": float(r.result.merit),
                 "analog_reads": int(r.ledger.mvm_count),
                 "executed_iterations": int(r.executed_iterations),
                 "ledger": dataclasses.asdict(r.ledger)}
                for r in reports]


def make(config: dict) -> Entry:
    return Entry(config)
