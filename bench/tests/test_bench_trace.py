"""The trace reduction on small synthetic and recorded traces."""
from __future__ import annotations

import pytest

from bench_fixtures import REPO
from benchkit import spec
from benchkit import trace as tr
from benchkit.harness import Run
from benchkit.trace import Ev

KERNEL = "dtw_ea_multi_fused.3"


def synthetic(used=(0, 1)) -> tr.Trace:
    """Two chips used, a window [100, 1100] ns, host spans for one query.

    chip 0: a fusion [50, 250] (half before the window), the kernel
            [300, 700] with a while op [280, 800] around it, an all-reduce
            [900, 950];
    chip 1: the kernel [300, 500] and an all-reduce [600, 700];
    chip 2: on the same host, idle, not used by the cell.
    """
    dev0 = [Ev("fusion.1", 50, 200), Ev("while.2", 280, 520),
            Ev(KERNEL, 300, 400), Ev("all-reduce.3", 900, 50)]
    dev1 = [Ev(KERNEL, 300, 200), Ev("all-reduce.3", 600, 100)]
    spans = [Ev("bench.window", 100, 1000), Ev("bench.next", 100, 20),
             Ev("bench.dispatch", 120, 100), Ev("bench.wait", 220, 730),
             Ev("bench.fetch", 950, 150)]
    return tr.build(devices={0: dev0, 1: dev1, 2: []}, spans=spans,
                    used=used)


def test_busy_union_clips_to_window_and_merges_nesting():
    t = synthetic()
    # chip 0: [100, 250] + [280, 800] + [900, 950] = 150 + 520 + 50
    # chip 1: [300, 500] + [600, 700] = 300
    assert tr.busy_ns(t) == pytest.approx((720 + 300) / 2)
    assert t.window_ns == 1000


def test_kernel_matching_and_device_readers():
    t = synthetic()
    kern = spec.metric_module("kernel_ms_per_query", REPO)
    assert tr.matching_ns(t, kern.is_kernel) == pytest.approx((400 + 200) / 2)
    # Per query of the traced slice (2), not of the whole window (5).
    run = Run(cell=None, n_windows=10, queries=[object()] * 5, window_s=1.0,
              setup_s=0.0, trace=t, traced=[object()] * 2)
    assert kern.read(run) == pytest.approx(300 / 1e6 / 2)
    xla = spec.metric_module("xla_ms_per_query", REPO)
    # busy outside the kernel: chip 0 720 - 400, chip 1 300 - 200
    assert xla.read(run) == pytest.approx(210 / 1e6 / 2)
    idle = spec.metric_module("device_idle_share", REPO)
    assert idle.read(run) == pytest.approx(100 * (1 - 510 / 1000))


def test_unused_idle_chip_is_left_out():
    # One chip used of three on the host: chip 1's and chip 2's planes do
    # not dilute chip 0's numbers.
    t = synthetic(used=(0,))
    assert len(t.devices) == 1
    assert tr.busy_ns(t) == pytest.approx(720)
    run = Run(cell=None, n_windows=10, queries=[object()] * 2, window_s=1.0,
              setup_s=0.0, trace=t, traced=[object()] * 2)
    idle = spec.metric_module("device_idle_share", REPO)
    assert idle.read(run) == pytest.approx(100 * (1 - 720 / 1000))
    kern = spec.metric_module("kernel_ms_per_query", REPO)
    assert kern.read(run) == pytest.approx(400 / 1e6 / 2)
    # A used chip with no plane in the trace gives no plane.
    assert len(synthetic(used=(0, 5)).devices) == 1


def test_kernel_is_found_by_wrapper_name_or_mosaic_target():
    kern = spec.metric_module("kernel_ms_per_query", REPO)
    assert kern.is_kernel(Ev("dtw_ea_persistent_fused", 0, 1))
    assert kern.is_kernel(Ev("custom-call.9", 0, 1,
                             'custom_call_target="tpu_custom_call"'))
    assert not kern.is_kernel(Ev("fusion.12", 0, 1, "jit(search)/sort"))
    assert not kern.is_kernel(Ev("dtw_ea_multi_fusedx.1", 0, 1))


@pytest.mark.parametrize("name", [
    # The v5e trace names an op by its whole HLO instruction.
    "%dtw_ea_multi_fused.3 = f32[256]{0:T(256)} custom-call(f32[65536]{0} "
    "%p.1), custom_call_target=\"tpu_custom_call\"",
    "%custom-call.12 = f32[256]{0} custom-call(f32[65536]{0} %p.1), "
    "custom_call_target=\"tpu_custom_call\"",
    "%dtw_ea_persistent.2 = f32[8]{0} custom-call()",
])
def test_kernel_is_found_by_hlo_text_name(name):
    kern = spec.metric_module("kernel_ms_per_query", REPO)
    assert kern.is_kernel(Ev(name, 0, 1))


@pytest.mark.parametrize("name", [
    "%while.26 = (s32[]{:T(128)}, f32[65536]{0}) while(%tuple.85), "
    "condition=%wide.while_cond, body=%wide.while_body.sunk",
    "%sort.0 = (f32[1,64513]{1,0}) sort(f32[1,64513]{1,0} %g.266)",
    "wide.region_7.30.sunk",
])
def test_other_hlo_text_ops_are_not_the_kernel(name):
    kern = spec.metric_module("kernel_ms_per_query", REPO)
    assert not kern.is_kernel(Ev(name, 0, 1))


def test_idle_gaps_are_labelled_by_host_span():
    t = synthetic()
    # A gap goes to the span covering its middle.
    # chip 0 gaps: [250, 280] (wait), [800, 900] (wait), [950, 1100] (fetch)
    # chip 1 gaps: [100, 300] (dispatch), [500, 600] (wait),
    #              [700, 1100] (wait, middle 900)
    assert tr.gaps(t.devices[0], *t.window) == [(250, 280), (800, 900),
                                                (950, 1100)]
    got = dict((k, v * 1e9) for k, v in tr.idle_by_host_span(t))
    assert got == pytest.approx({"dispatch": 200 / 2,
                                 "wait": (30 + 100 + 100 + 400) / 2,
                                 "fetch": 150 / 2})
    assert tr.label((2000, 2100), t.spans) == "outside_spans"


def test_top_ops_orders_by_device_time():
    top = tr.top_ops(synthetic(), n=2)
    assert [name for name, _ in top] == [KERNEL, "while.2"]
    assert top[0][1] == pytest.approx(300 / 1e9)


@pytest.mark.parametrize("name,short", [
    ("%dynamic-update-slice.8 = f32[4096,128]{1,0:T(8,128)S(1)} "
     "dynamic-update-slice(f32[4096,128]{1,0:T(8,128)S(1)} %g.465, "
     "f32[1,128]{1,0:T(1,128)S(1)} %b.1)",
     "dynamic-update-slice.8 = f32[4096,128] dynamic-update-slice"),
    ("%while.26 = (s32[]{:T(128)}, f32[262144]{0:T(1024)}) while((s32[]"
     "{:T(128)}, f32[262144]{0:T(1024)}) %tuple.86), condition=%c",
     "while.26 = (...) while"),
    ("%dtw_ea_multi_fused.6 = f32[256,1]{1,0:T(8,128)S(1)} custom-call("
     "f32[256,1]{1,0:T(8,128)S(1)} %copy.28), custom_call_target="
     "\"tpu_custom_call\"",
     "dtw_ea_multi_fused.6 = f32[256,1] custom-call"),
    ("wide.region_7.30.sunk", "wide.region_7.30.sunk"),
])
def test_breakdown_names_are_cut_to_name_shape_and_opcode(name, short):
    assert tr.short_name(name) == short


def test_dropped_events_are_measured_inside_the_window():
    t = synthetic()
    assert t.dropped_ns == 0
    spans = [Ev("bench.window", 100, 1000)]
    lost = {0: [Ev(tr.DROPPED, 900, 400)], 1: [Ev(tr.DROPPED, 0, 50)]}
    t = tr.build(devices={0: [], 1: []}, spans=spans, used=(0, 1),
                 dropped=lost)
    assert t.dropped_ns == 200  # chip 0: [900, 1100]; chip 1: before it


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        tr.build(devices={0: []}, spans=[Ev("bench.next", 0, 1)],
                 used=(0,))


def test_recorded_trace_holds_the_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    y = f(x)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = tr.load(str(tmp_path), used=(0,))
    assert t.window_ns > 0
    names = [s.name for s in t.spans]
    assert names.count("bench.dispatch") == 2 and names.count("bench.wait") == 2
    assert all(t.window[0] <= s.start and s.end <= t.window[1]
               for s in t.spans)
