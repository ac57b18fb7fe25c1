"""The DTW kernels compile for a TPU v5e (ahead of time, no chip needed).

Every Pallas kernel on the search path is lowered through Mosaic and
compiled by the installed TPU compiler for one chip of a described
``v5e:2x2`` topology, at the search deployment's widths: l=128 and l=1024,
Sakoe-Chiba windows 12, 102 (the config's r=0.1) and 512 (r=0.5, band =
whole row), Q=2 queries, K=64 lanes, and the fused pair over a
1,000,000-sample reference (the config's) and a 2,000,000-sample one.
Interpret-mode tests cannot see what
this sees: Mosaic refuses unaligned lane slices and over-size VMEM, which
the interpreter runs happily.

Each case asserts that the compiled program holds the kernel
(``tpu_custom_call``), that its scoped VMEM fits the v5e's 16 MiB, and,
for the fused pair, that the kernel takes the reference as a
``memory_space=ANY`` operand: only the per-lane window spans enter its
scoped VMEM, so that allocation does not grow with the reference. Nothing
runs, so nothing here is a result or a time.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and a fixture keeps every pytest
worker's collection identical. ``band_width`` is passed explicitly because
``default_band_width`` sees the CPU here, and x64 is off around each
compile because the search runs 32-bit (``conftest.py`` turns it on for the
float64 oracles).
"""
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

V5E_SCOPED_VMEM = 16 * 1024 * 1024
V5E_HBM = 16 * 1000**3
Q, K = 2, 64

KERNELS = (
    "dtw_ea_multi", "dtw_ea_multi_fused", "dtw_ea_persistent",
    "dtw_ea_persistent_fused",
)
FUSED = ("dtw_ea_multi_fused", "dtw_ea_persistent_fused")

# (kernel, l, w, reference samples for the fused pair)
CASES = (
    [(k, 128, 12, 1_000_000) for k in KERNELS]
    + [(k, 1024, 102, 1_000_000) for k in KERNELS]
    + [(k, 1024, 512, 1_000_000) for k in FUSED]
    + [(k, 1024, 102, 2_000_000) for k in FUSED]
)


def _band(l, w):
    return min(l, -(-(2 * w + 1) // 128) * 128)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _call(kernel, l, w, n_ref, spec):
    """The kernel as a function of shapes, plus its argument shapes."""
    kw = dict(window=w, band_width=_band(l, w))
    f32 = lambda *shape: spec(shape)
    i32 = lambda *shape: spec(shape, jnp.int32)
    if kernel == "dtw_ea_multi":
        fn = lambda q, c, ub, cb: ops.dtw_ea_multi(q, c, ub, cb=cb, **kw)
        return fn, (f32(Q, l), f32(Q, K, l), f32(Q, K), f32(Q, K, l))
    if kernel == "dtw_ea_persistent":
        fn = lambda q, c, lb, st, ub0, u, lo: ops.dtw_ea_persistent(
            q, c, lb, st, ub0, u=u, low=lo, use_cb=True, **kw
        )
        return fn, (f32(Q, l), f32(Q, K, l), f32(Q, K), i32(Q, K), f32(Q),
                    f32(Q, l), f32(Q, l))
    if kernel == "dtw_ea_multi_fused":
        fn = lambda q, r, st, mu, sg, ub, u, lo: ops.dtw_ea_multi_fused(
            q, r, st, mu, sg, ub, length=l, u=u, low=lo, use_cb=True, **kw
        )
        return fn, (f32(Q, l), f32(n_ref), i32(Q, K), f32(Q, K), f32(Q, K),
                    f32(Q, K), f32(Q, l), f32(Q, l))
    fn = lambda q, r, lb, st, mu, sg, ub0, u, lo: ops.dtw_ea_persistent_fused(
        q, r, lb, st, mu, sg, ub0, length=l, u=u, low=lo, use_cb=True, **kw
    )
    return fn, (f32(Q, l), f32(n_ref), f32(Q, K), i32(Q, K), f32(Q, K),
                f32(Q, K), f32(Q), f32(Q, l), f32(Q, l))


def _kernel_scoped_vmem(hlo: str) -> int:
    """Bytes of scoped VMEM (memory space 1) the compiled kernels use."""
    total = 0
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        cfg = re.search(r'"used_scoped_memory_configs":(\[[^\]]*\])', line)
        for entry in json.loads(cfg.group(1)):
            if entry["memory_space"] == "1":
                total += int(entry["size"])
    return total


@pytest.mark.parametrize(
    "kernel,l,w,n_ref", CASES,
    ids=[f"{k}-l{l}-w{w}-N{n}" for k, l, w, n in CASES],
)
def test_kernel_compiles_for_v5e(spec, kernel, l, w, n_ref):
    fn, args = _call(kernel, l, w, n_ref, spec)
    with jax.enable_x64(False):  # the search runs 32-bit; conftest turns x64 on
        compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo

    scoped = _kernel_scoped_vmem(hlo)
    assert 0 < scoped <= V5E_SCOPED_VMEM, scoped
    if kernel in FUSED:
        n_pad = ops.padded_ref_len(n_ref, l)
        assert f"f32[1,{n_pad}]" in hlo
        assert scoped < n_pad * 4, scoped  # the reference is not scoped VMEM

    mem = compiled.memory_analysis()
    hbm = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes)
    assert hbm <= V5E_HBM, hbm
