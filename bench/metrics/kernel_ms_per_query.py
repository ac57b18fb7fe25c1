"""kernel_ms_per_query: device time of the DTW Pallas kernels per query.

Summed durations of the trace's ``XLA Ops`` events that are one of the
search path's Pallas kernels, mean over the chips used, over the queries of
the traced slice of the window. On the search path the only Pallas kernels
are the DTW ones (``kernels/dtw_band.py`` through ``kernels/ops.py``).

On the v5e the trace names an op by its whole HLO instruction,
``%dtw_ea_multi_fused.3 = f32[...] custom-call(...), custom_call_target=
"tpu_custom_call", ...``: the instruction's own name is the first word,
without its ``%``. The compiler names a Pallas kernel's custom call after
the jitted wrapper of ``kernels/ops.py`` that holds it, and only Mosaic
kernels have the target ``tpu_custom_call``, in the name or in the op's
stats.
"""
from benchkit import trace as tr

KERNEL_NAMES = ("dtw_ea_multi_fused", "dtw_ea_persistent_fused",
                "dtw_ea_multi", "dtw_ea_persistent")
MOSAIC_TARGET = "tpu_custom_call"


def op_name(ev) -> str:
    """The HLO instruction's own name: ``dtw_ea_multi_fused.3``."""
    words = ev.name.split()
    return words[0].lstrip("%") if words else ""


def is_kernel(ev) -> bool:
    return (op_name(ev).split(".")[0] in KERNEL_NAMES
            or MOSAIC_TARGET in ev.name or MOSAIC_TARGET in ev.meta)


def read(run):
    if run.trace is None or not run.traced:
        return None
    ns = tr.matching_ns(run.trace, is_kernel)
    if ns == 0:
        return None
    return ns / 1e6 / len(run.traced)
