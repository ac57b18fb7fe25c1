"""Pallas TPU kernels for the paper's compute hot-spots.

  dtw_band  — batched early-abandoning pruned DTW (the paper's core loop,
              TPU-tiled: query/candidate-parallel grid x sequential
              row-blocks, flattened (Q x K) lanes with a per-lane ub vector,
              banded columns with a window-following offset, VMEM DP carry,
              SMEM abandon flag, optional rows/cells pruning counters)
  lb_keogh  — LB_Kim + LB_Keogh for every window of a reference in one pass

``ops.py`` holds the jitted wrappers (Mosaic by default; ``interpret=True``
runs the kernel body in Python, the CPU test path):
``dtw_ea_multi`` is the multi-query launch, ``dtw_ea`` its Q = 1 form, and
``dtw_ea_persistent`` the one-launch-per-search persistent form (sequential
candidate grid dimension, incumbent carried in SMEM scratch);
``ref.py`` the pure-jnp oracles the tests sweep against.
"""
from repro.kernels.ops import (
    dtw_ea,
    dtw_ea_multi,
    dtw_ea_persistent,
    lb_keogh_all_windows,
)

__all__ = ["dtw_ea", "dtw_ea_multi", "dtw_ea_persistent", "lb_keogh_all_windows"]
