# Pallas TPU kernels for the paper's compute hot-spots:
#   crossbar_mvm  — the analog MVM (the operation the paper accelerates),
#                   as a tiled differential-pair MXU matmul.
#   pdhg_update   — fused primal/dual vector updates (single VMEM pass).
# Validated in interpret=True mode on CPU against ref.py oracles and
# compiled for a described v5e in tests/test_tpu_compile.py; every entry
# point auto-detects interpret mode through kernels.interpret, and
# compiled kernels take f32 only (kernels.interpret.check_kernel_dtype).
# Solvers reach these through core.engine's operator/update backends
# (PDHGOptions.kernel = "pallas").
from . import crossbar_mvm, interpret, ops, pdhg_update, ref
from .interpret import interpret_default

__all__ = ["crossbar_mvm", "interpret", "interpret_default", "ops",
           "pdhg_update", "ref"]
