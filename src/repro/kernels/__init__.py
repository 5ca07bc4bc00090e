"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel module pairs with a pure-jnp oracle in ``ref.py``; the public
entry points live in ``ops.py`` and route through the
dispatch layer in ``backend.py`` (fused XLA vs Pallas tile vs interpret
mode, selected by the active ``repro.core.policy.KernelPolicy`` — per
call via ``policy=``/``path=``, or process-wide; the stable façade is
``repro.ops``):

  backend.py          compiler params + capability probes + pallas_op dispatch
  tcu_reduce.py       matmul-form segmented reduction   (paper §4)
  tcu_scan.py         matmul-form segmented scan        (paper §5)
  matmul_scan.py      log-depth MatMulScan: carry-free local kernels +
                      O(log) tree combine (``tile_logdepth``; beyond-paper)
  fused_rmsnorm.py    RMSNorm with MXU Σx²              (paper §8 future work)
  ssd_scan.py         Mamba-2 SSD = weighted tile scan  (beyond-paper)
  flash_attention.py  blocked attention, matmul-form ℓ  (beyond-paper)
  layout.py           shared padding/fold glue + the ONLY home of kernel
                      geometry numbers (TuneSpec defaults / sweep
                      candidates; grep-guard enforced)
  triton/             Pallas-Triton (GPU) twins of all five kernels,
                      registered as the ``tile_gpu`` entries
"""
from repro.kernels import backend
from repro.kernels.backend import (
    available_ops,
    compiler_params,
    pallas_op,
)
from repro.kernels.ops import (
    attention,
    rmsnorm,
    segmented_reduce,
    segmented_scan,
    ssd_scan,
    weighted_scan,
)

__all__ = [
    "attention",
    "available_ops",
    "backend",
    "compiler_params",
    "pallas_op",
    "rmsnorm",
    "segmented_reduce",
    "segmented_scan",
    "ssd_scan",
    "weighted_scan",
]
