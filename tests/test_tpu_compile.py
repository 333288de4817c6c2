"""Compile the device wave program for a described TPU v5e (no chip needed).

The TPU compiler ships with jaxlib, so a topology that is described but not
attached takes the Pallas kernel through Mosaic exactly as the chip would:
block-shape rules, unimplemented primitives and VMEM limits all surface
here.  The shapes are the chip smoke's own (``chip_smoke.py`` at its
default 20M airline rows): a primary segment of D=8 columns padded to 2^25
rows with k=3 grid dims and a sorted dim, the full-dimensional outlier grid,
and a delta segment, at the wave buckets B=4 and B=64.

The topology is described inside a module-scoped fixture — never at import
— so every xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine.device import _wave_program
from repro.kernels.fused_scan import DEFAULT_HIT_CAP, GROUP_ROWS

D = 8
PRIMARY = (1 << 25, 3)        # (padded rows, grid dims): 18.4M primary rows
OUTLIER = (1 << 21, 7)        # 1.6M outlier rows, every dim but the sorted
DELTA_ROWS = GROUP_ROWS       # smallest delta image (holds up to 4096 rows)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _segment(sharding, bp, n_pad, k=0, sort=False):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    lanes = (n_pad // 128, 128)
    seg = {"rows": s((D,) + lanes, jnp.float32),
           "alive": s(lanes, jnp.int32),
           "flo": s((bp, D), jnp.float32), "fhi": s((bp, D), jnp.float32)}
    if k:
        seg.update(coords=s((k,) + lanes, jnp.int32),
                   first=s((bp, k), jnp.int32), last=s((bp, k), jnp.int32))
    if sort:
        seg.update(sv=s(lanes, jnp.float32), tband=s((bp, 2), jnp.float32))
    cfg = (DEFAULT_HIT_CAP, bool(k), sort, True, False, 0)
    return seg, cfg


@pytest.mark.parametrize("bp", [4, 64])
def test_wave_program_compiles_for_v5e(one_chip, no_persistent_cache, bp):
    segs = [_segment(one_chip, bp, *PRIMARY, sort=True),
            _segment(one_chip, bp, *OUTLIER, sort=True),
            _segment(one_chip, bp, DELTA_ROWS)]
    fn = jax.jit(_wave_program, static_argnums=1)
    compiled = fn.lower(tuple(s for s, _ in segs),
                        tuple(c for _, c in segs)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3     # one kernel per segment
    out = jax.eval_shape(fn, tuple(s for s, _ in segs),
                         tuple(c for _, c in segs))
    for counts, hits, scanned in out:
        assert counts.shape == scanned.shape == (bp, 1)
        assert hits.shape == (bp, DEFAULT_HIT_CAP)
