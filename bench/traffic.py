"""The one traffic generator: a mix file (``bench/mixes/<name>.json``)
and a configuration in, the instances of every distinct call out.

A configuration lists its instances (``instances``: name, rows ``m``,
columns ``n`` and ``density`` of each, before standard form) and the
family that draws their data.  A mix holds:

* ``why`` and ``source``: what the mix stands for, and where its set of
  instances per call comes from;
* ``entry``: the kind of call (``"stream"``: a list of instances per
  call); the configuration maps it to an entry;
* ``storage``: how a call hands K to the program, ``"dense"`` (an
  array) or ``"coo"`` (its nonzeros, which the program turns into ELL);
* ``distinct_calls``: how many different call payloads make up the
  pool.  Each call holds every instance of the configuration once, with
  data of its own.  The closed loop runs whole cycles through the pool,
  in an order drawn from the run's seed;
* ``pool_seed``: the seed of the pool's data.  Every run seed solves the
  same pool, so a seed changes the order and not the amount of work:
  iterations to tolerance differ from one draw of the data to the next,
  and a pool drawn from each run seed would make run-to-run spread
  mostly seed choice.

Instance j of pool call i is drawn from
``numpy.random.default_rng([pool_seed, i, j])``.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.gen import lp

STORAGE = {"dense": lambda inst: inst, "coo": lp.as_coo}


def make_calls(mix: dict, config: dict, seed: int) -> List[List[lp.Instance]]:
    """The pool's call payloads, in the order ``seed`` draws."""
    family = lp.FAMILIES[config["family"]]
    store = STORAGE[mix["storage"]]
    dtype = np.dtype(config["dtype"])
    pool = int(mix["pool_seed"])
    n_calls = int(mix["distinct_calls"])
    order = np.random.default_rng(int(seed) % 2 ** 63).permutation(n_calls)
    return [[store(family(f"{spec['name']}.{i}", int(spec["m"]),
                          int(spec["n"]), float(spec["density"]),
                          float(config["box"]),
                          np.random.default_rng([pool, int(i), j]),
                          dtype=dtype))
             for j, spec in enumerate(config["instances"])]
            for i in order]
