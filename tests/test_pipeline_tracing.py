"""The search pipeline names its stages, and the mesh program counts lanes.

Each stage of ``search/pipeline.py`` traces under a ``jax.named_scope``
(``dtw.prepare``, ``dtw.cascade``, ``dtw.execute``; the mesh program's
collectives under ``dtw.reconcile`` inside ``dtw.execute``), which lands in
the compiled HLO's ``op_name`` metadata and so in a device profile. These
tests read the compiled programs at a tiny size on the CPU. The sharded
ones run in a subprocess with forced host devices, as
``test_multi_query.py`` does.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.search import pipeline

STAGES = ("dtw.prepare", "dtw.cascade", "dtw.execute")
SCOPES = STAGES + ("dtw.reconcile", "dtw.sort")
_OP = re.compile(r'%\S+ = (\S+) ([a-z-]+)\(.*op_name="([^"]*)"')
N_REF, LENGTH = 500, 32
N_WIN = N_REF - LENGTH + 1


def hlo_shaped_ops(hlo: str) -> list[tuple[str, str, str]]:
    """``(shape, opcode, op_name)`` of every instruction with an op_name."""
    return [m.groups() for m in map(_OP.search, hlo.splitlines()) if m]


def hlo_ops(hlo: str) -> list[tuple[str, str]]:
    """``(opcode, op_name)`` of every instruction that has an op_name."""
    return [(op, name) for _, op, name in hlo_shaped_ops(hlo)]


def _scenario():
    rng = np.random.default_rng(5)
    ref = jnp.asarray(np.cumsum(rng.normal(size=N_REF)), jnp.float32)
    return ref, ref[None, 100:100 + LENGTH]


def _compiled_hlo(kind: str) -> str:
    ref, q = _scenario()
    if kind == "baseline":
        plan = pipeline.make_plan(length=LENGTH, window=3, batch=16, chunk=64,
                                  variant="full", backend="jax")
        low = pipeline._baseline_search_impl.lower(
            ref, q[0], plan=plan, with_info=False)
    else:
        plan = pipeline.make_plan(length=LENGTH, window=3, batch=16, chunk=64,
                                  rounds=kind, backend="jax")
        low = pipeline._offline_search_impl.lower(
            ref, q, jnp.full((1,), jnp.inf, jnp.float32), plan=plan,
            with_info=False)
    return low.compile().as_text()


def _compiled_ops(kind: str) -> list[tuple[str, str]]:
    return hlo_ops(_compiled_hlo(kind))


@pytest.mark.parametrize("kind", ["host", "persistent", "baseline"])
def test_compiled_search_carries_the_stage_scopes(kind):
    shaped = hlo_shaped_ops(_compiled_hlo(kind))
    ops = [(op, name) for _, op, name in shaped]
    for stage in STAGES:
        assert any(stage in name.split("/") for _, name in ops), stage
    # Only the named scopes, and the outermost is always a stage.
    for _, name in ops:
        mine = [s for s in name.split("/") if s.startswith("dtw.")]
        assert set(mine) <= set(SCOPES), name
        assert not mine or mine[0] in STAGES, name
    # The cascade slices no single window out of the reference: its loop
    # over query offsets takes one slice across every window start.
    cascade = [(shape, op, name) for shape, op, name in shaped
               if "dtw.cascade/" in name
               and op in ("gather", "dynamic-slice", "slice")]
    assert not [c for c in cascade
                if re.match(rf"f32\[(\d+,)*{LENGTH}\]", c[0])], cascade
    assert any(shape.startswith(f"f32[{N_WIN}]") and op == "dynamic-slice"
               and "/while/body/" in name for shape, op, name in cascade)
    # The argsort sits in its sub-scope.
    assert any("dtw.cascade/dtw.sort/" in name for _, name in ops)


def test_round_body_fold_is_in_the_execute_scope():
    # ``fold_min`` in the host round driver's loop body: its argmin (a
    # variadic ``reduce``) and the pick of each query's minimum.
    body = [(op, name.split("dtw.execute/while/body/", 1)[1])
            for op, name in _compiled_ops("host")
            if "dtw.execute/while/body/" in name]
    assert any(name == "reduce" for _, name in body)
    assert any(op in ("gather", "dynamic-slice")
               and name == "jit(take_along_axis)/gather" for op, name in body)


def _run(code: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=420,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )


_MESH_HEAD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
import numpy as np, jax, jax.numpy as jnp
rng = np.random.default_rng(11)
# 1,001 - 96 + 1 = 906 windows: not a multiple of 4, so the mesh pads.
ref = jnp.asarray(np.cumsum(rng.normal(size=1001)), jnp.float32)
queries = jnp.asarray(np.cumsum(rng.normal(size=(2, 96)), axis=1),
                      jnp.float32)
mesh = jax.make_mesh((4,), ("d",))
"""


def test_sharded_collectives_carry_the_reconcile_scope():
    code = _MESH_HEAD + r"""
from repro.search import pipeline
from test_pipeline_tracing import hlo_ops
plan = pipeline.make_plan(length=96, window=9, batch=32, backend="jax")
fn = pipeline.make_sharded_search(mesh, ("d",), plan)
ops = hlo_ops(fn.lower(ref, queries).compile().as_text())
reduce = [name for op, name in ops if op.startswith("all-reduce")]
assert reduce, "no all-reduce in the sharded program"
for name in reduce:
    assert "/dtw.reconcile/" in name + "/", name
    assert "/dtw.execute/" in name, name
for stage in ("dtw.prepare", "dtw.cascade", "dtw.execute"):
    assert any(stage in name.split("/") for _, name in ops), stage
print("RECONCILE OK", len(reduce))
"""
    out = _run(code)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RECONCILE OK" in out.stdout


def test_sharded_lane_counters_cover_every_window_once():
    code = _MESH_HEAD + r"""
from repro.search import (make_distributed_multi_search,
                          make_distributed_search, subsequence_search)
from repro.search.pipeline import ShardedExecutor, initial_state, make_plan
n_win = ref.shape[0] - 96 + 1
poisoned = ref.at[400].set(jnp.nan)  # quarantines 96 windows
for series in (ref, poisoned):
    res = make_distributed_multi_search(
        mesh, ("d",), length=96, window=9, batch=32, backend="jax"
    )(series, queries)
    lanes, pruned = np.asarray(res.lanes), np.asarray(res.lb_pruned)
    assert lanes.shape == (2,) and (lanes > 0).all(), lanes
    assert (lanes + pruned == n_win).all(), (lanes, pruned)
    for q in range(2):
        one = subsequence_search(series, queries[q], length=96, window=9,
                                 batch=32, backend="jax")
        assert int(res.best_start[q]) == int(one.best_start)
        np.testing.assert_allclose(float(res.best_dist[q]),
                                   float(one.best_dist), rtol=1e-4)
        s = make_distributed_search(
            mesh, ("d",), length=96, window=9, batch=32, backend="jax"
        )(series, queries[q])
        assert int(s.lanes) == lanes[q] and int(s.lb_pruned) == pruned[q]
        assert int(s.best_start) == int(one.best_start)
# The executor seam reports the same counts.
plan = make_plan(length=96, window=9, batch=32, backend="jax")
rr = ShardedExecutor(mesh, ("d",), ref, queries).run_range(
    plan, initial_state(2, jnp.float32, None), 0, n_win)
assert (np.asarray(rr.stats.lanes) + np.asarray(rr.stats.lb_pruned)
        == n_win).all()
print("COUNTERS OK")
"""
    out = _run(code)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "COUNTERS OK" in out.stdout
