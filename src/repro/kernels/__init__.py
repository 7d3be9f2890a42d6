"""Pallas TPU kernels for the perf-critical compute hot spots.

Each kernel ships as ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling), with ``ops.py`` as the jit'd public wrapper and ``ref.py`` as the
pure-jnp oracle.  Whether a kernel runs under the Pallas interpreter is
decided in one place, :func:`interpret_on`, from the platform of the devices
it runs on: the CPU backend interprets, a TPU compiles it with Mosaic.

Kernels:
  * fused_select_agg   — single-pass select+project+aggregate (TPC-H Q6 pipeline)
  * grouped_select_agg — fused select + dense-bucket grouped aggregation
                         (TPC-H Q1 pipeline: vec.GroupAggDirect under kernels)
  * segsum             — segment reduction as one-hot MXU matmul (GroupBy)
  * kmeans_step      — fused assign+accumulate k-means iteration
  * flash_attention  — causal/windowed GQA online-softmax attention
"""

from . import ops, ref  # noqa: F401


def interpret_on(platform: str) -> bool:
    """Pallas interpret mode for kernels run on ``platform``: on only for the
    CPU backend, which has no Mosaic; every accelerator compiles them."""
    return platform == "cpu"
