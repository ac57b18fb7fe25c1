"""xla_ms_per_query: device busy time outside the DTW kernels, per query.

The union of all op events on a chip, less the union of its DTW kernel
events (``kernel_ms_per_query.KERNEL_NAMES``): an op that encloses the
kernel, such as the round loop's ``while``, is not counted twice. Mean
over the chips used,
over the queries of the traced slice: window statistics, quarantine, the LB
cascade, the argsort and the round bookkeeping that XLA runs.
"""
from pathlib import Path

from benchkit import trace as tr
from benchkit.spec import metric_module

_kernel = metric_module(
    "kernel_ms_per_query", Path(__file__).resolve().parents[2]).is_kernel


def read(run):
    if run.trace is None or not run.trace.devices or not run.traced:
        return None
    other = tr.per_device_mean(
        run.trace, lambda ops: tr.covered_ns(ops) - tr.covered_ns(
            [e for e in ops if _kernel(e)]))
    return other / 1e6 / len(run.traced)
