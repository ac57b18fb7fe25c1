"""Backend dispatch for the batched EAPrunedDTW hot path.

One question, answered in one place: *which implementation evaluates a batch
of candidates?* Two real backends exist:

  ``pallas`` — the TPU kernel (``kernels.ops.dtw_ea``): a banded
      ``(candidate_blocks, row_blocks)`` grid with the DP carry in VMEM and a
      block-level early-exit flag. Rows advance in lockstep across the lanes
      of a block, so abandon granularity is the block — coarser than the JAX
      path but with none of vmap's per-lane while_loop degradation. It
      lowers through Mosaic and exists only on the TPU: asking for it on
      another platform raises instead of quietly running the interpreter.

  ``jax`` — ``core.ea_pruned_dtw.ea_pruned_dtw_banded`` under ``vmap``: a
      per-lane banded ``lax.while_loop``. Under vmap every lane steps until
      the slowest lane of the whole batch finishes, with per-lane
      dynamic-slice realignment each row. This is the portable CPU/GPU
      fallback and the float64 reference (the kernel is float32).

Selection order:

  1. explicit ``backend=`` argument (``"pallas"``, ``"pallas_interpret"``,
     ``"jax"``, ``"auto"``),
  2. the ``REPRO_DTW_BACKEND`` environment variable (same values) when the
     argument is ``None`` / ``"auto"`` is passed through it,
  3. platform default: ``pallas`` on TPU, ``jax`` elsewhere.

``pallas_interpret`` runs the same kernel in interpret mode (Python
execution of the kernel body) on any platform — the CI path that exercises
the kernel's exact program on CPU. It runs only when asked for. Multivariate queries
(``query.ndim > 1``) always take the ``jax`` backend; the kernel is
univariate (the paper's workload).

Every public entry point (``ea_pruned_dtw_batch``, ``ea_search_round``,
``subsequence_search``, ``multi_query_search``) resolves the environment
variable in its un-jitted wrapper, so the resolved name becomes the static
``backend`` argument of the jitted program: changing ``REPRO_DTW_BACKEND``
between calls correctly retraces. Only ``make_distributed_search`` /
``make_distributed_multi_search`` pin the backend once, at closure-build
time.
"""
from __future__ import annotations

import os

import jax

BACKENDS = ("auto", "pallas", "pallas_interpret", "jax")
ENV_VAR = "REPRO_DTW_BACKEND"


def resolve_backend(backend: str | None = None) -> str:
    """Resolve a backend request to a concrete backend name.

    ``None`` defers to ``$REPRO_DTW_BACKEND`` (default ``auto``); ``auto``
    picks ``pallas`` on TPU and ``jax`` elsewhere. Returns one of
    ``("pallas", "pallas_interpret", "jax")``. Raises ``ValueError`` for an
    unknown name, and for ``pallas`` off the TPU.
    """
    b = backend if backend is not None else os.environ.get(ENV_VAR, "auto")
    if b not in BACKENDS:
        raise ValueError(f"backend {b!r} not in {BACKENDS}")
    on_tpu = jax.default_backend() == "tpu"
    if b == "auto":
        return "pallas" if on_tpu else "jax"
    if b == "pallas" and not on_tpu:
        raise ValueError(
            f"backend 'pallas' lowers through Mosaic and needs a TPU (this "
            f"platform is {jax.default_backend()!r}); use 'pallas_interpret' "
            f"to run the kernel in interpret mode, or 'jax'"
        )
    return b
