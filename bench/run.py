"""Run one benchmark cell once on the chip; see ``bench/harness.py``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no accelerator or
fewer chips than the cell needs, or when the program's sources are not
in the checkout.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# run as a script, sys.path[0] is bench/; import the benchmark as the
# package ``bench`` from the checkout root instead
sys.path[0] = ROOT
# the TPU runtime logs under /tmp by default; keep them in the checkout
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = os.path.join(ROOT, "bench", "out", "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
