"""``collective_ms_per_query`` on synthetic traces."""
from __future__ import annotations

import pytest

from bench_fixtures import REPO
from benchkit import spec
from benchkit import trace as tr
from benchkit.harness import Run
from benchkit.trace import Ev

coll = spec.metric_module("collective_ms_per_query", REPO)

# Ops as the v5e trace names them: the whole HLO instruction.
PMIN = ("%pmin.27 = f32[1]{0:T(128)} all-reduce(%min.71), channel_id=1, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_13.15")
PMAX = ("%pmax.31 = u32[]{:T(128)} all-reduce(%bitcast.153), channel_id=1, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_14.16.clone")
TUPLE = ("%all-reduce.15 = (s32[]{:T(128)}, s32[1]{0:T(128)}) all-reduce("
         "%convert_reduce_fusion.1, %min.51), channel_id=1")
KERNEL = ("%dtw_ea_multi_fused.6 = f32[256,1]{1,0:T(8,128)S(1)} custom-call("
          "f32[256,1]{1,0} %copy.28), custom_call_target=\"tpu_custom_call\"")
WHILE = ("%while.41 = (s32[]{:T(128)}, f32[1]{0:T(128)}) while((s32[], "
         "f32[1]) %tuple.86), condition=%c, body=%b")
FUSION = ("%fusion.6 = f32[1]{0:T(128)} fusion(f32[1]{0} %x), kind=kLoop, "
          "calls=%fused_computation.all-reduce.1")
START = "%all-reduce-start.2 = f32[1]{0} all-reduce-start(f32[1]{0} %x)"
DONE = "%all-reduce-done.2 = f32[1]{0} all-reduce-done(f32[1]{0} %s)"


def run_of(devices: dict, traced: int = 2) -> Run:
    """A run of four chips whose traced window is [0, 10,000] ns."""
    spans = [Ev("bench.window", 0, 10_000)]
    t = tr.build(devices=devices, spans=spans, used=(0, 1, 2, 3))
    return Run(cell=None, n_windows=1, queries=[object()] * 7,
               window_s=1.0, setup_s=0.0, trace=t, traced=[object()] * traced)


def four_chips() -> dict:
    """Each chip: a round loop around the kernel and two reconcile
    all-reduces, then the final tuple all-reduce; chip 3 waits longest at
    the first one (its shard finished early)."""
    devs = {}
    for i, wait in enumerate((100, 100, 100, 700)):
        devs[i] = [Ev(WHILE, 0, 5_000), Ev(KERNEL, 100, 3_000),
                   Ev(FUSION, 3_100, 50), Ev(PMIN, 3_200, wait),
                   Ev(PMAX, 4_000, 200), Ev(TUPLE, 6_000, 300)]
    return devs


def test_counts_all_reduces_only_mean_over_chips_per_traced_query():
    # chips 0-2: 100 + 200 + 300; chip 3: 700 + 200 + 300; not the kernel,
    # the while loop around them, nor a fusion named after a collective.
    got = coll.read(run_of(four_chips(), traced=2))
    assert got == pytest.approx((3 * 600 + 1200) / 4 / 1e6 / 2)


def test_async_pair_and_bare_names_are_all_reduces():
    assert coll.is_collective(Ev(START, 0, 1))
    assert coll.is_collective(Ev(DONE, 0, 1))
    assert coll.is_collective(Ev("all-reduce.3", 0, 1))
    assert coll.is_collective(Ev("pmin.27", 0, 1, meta=PMIN))
    # The opcode is cached by name and stats together, not by name alone.
    assert not coll.is_collective(Ev("pmin.27", 0, 1, meta="pmin"))
    for name in (KERNEL, WHILE, FUSION, "fusion.1", "while.2"):
        assert not coll.is_collective(Ev(name, 0, 1)), name
    devs = {i: [Ev(START, 1_000, 10), Ev(DONE, 1_500, 90)] for i in range(4)}
    assert coll.read(run_of(devs, traced=1)) == pytest.approx(100 / 1e6)


def test_only_the_window_counts():
    devs = four_chips()
    for ops in devs.values():
        ops.append(Ev(PMIN, 9_900, 500))  # 100 ns of it inside the window
    got = coll.read(run_of(devs, traced=2))
    assert got == pytest.approx((3 * 700 + 1300) / 4 / 1e6 / 2)


def test_nothing_to_read_gives_none():
    assert coll.read(Run(cell=None, n_windows=1, queries=[], window_s=1.0,
                         setup_s=0.0)) is None
    one_chip = {i: [Ev(KERNEL, 0, 100), Ev(FUSION, 200, 10)]
                for i in range(4)}
    assert coll.read(run_of(one_chip)) is None
    assert coll.read(run_of(four_chips(), traced=0)) is None
