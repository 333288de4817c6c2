"""Compile the device wave program for a described TPU v5e (no chip needed).

The TPU compiler ships with jaxlib, so a topology that is described but not
attached takes the Pallas kernel through Mosaic exactly as the chip would:
block-shape rules, unimplemented primitives and VMEM limits all surface
here.  Three sets of shapes, each at the wave buckets B=4 and B=64:

* the chip smoke's own (``chip_smoke.py`` at its default 20M airline
  rows): a primary segment of D=8 columns padded to 2^25 rows with k=3
  grid dims and a sorted dim, the full-dimensional outlier grid, and a
  delta segment;
* the benchmark's airline table (80M rows x 8): primary 2^27 rows x k=3,
  outlier 2^23 x k=7;
* the benchmark's OSM table (105M rows x 4): primary 2^27 x k=2, outlier
  2^25 x k=3;
* the benchmark's TPC-H ``lineitem`` at SF 10 (60M rows x 8): primary
  2^26 x k=5, outlier 2^20 x k=7.

The drain-time large pass compiles too, at the TPC-H primary's shapes,
where every Q6 answer takes it: the segment's scan returning its hit
words, and one compaction pass at the largest chunk, whose optimized HLO
must hold no scatter or gather with an index per word or per slot.

Every grid segment carries the plan's work list: ``W`` items, ``W`` being
the tile count of the largest image (4,096 at 2^27 rows), so the
scalar-prefetched list must fit SMEM beside the per-query scalars, and
both the listed and the full-scan kernel lower in one program.

The topology is described inside a module-scoped fixture — never at import
— so every xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine.device import LARGE_CHUNK, _wave_program, _words_program
from repro.kernels.fused_scan import (DEFAULT_HIT_CAP, GROUP_ROWS, STEP_ROWS,
                                      WORD_BITS, compact_range)

DELTA_ROWS = GROUP_ROWS       # smallest delta image (holds up to 4096 rows)
# (columns D, (padded rows, grid dims) of the primary, of the outlier grid)
TABLES = {
    "smoke": (8, (1 << 25, 3), (1 << 21, 7)),    # 18.4M / 1.6M rows
    "airline-80m": (8, (1 << 27, 3), (1 << 23, 7)),
    "osm-105m": (4, (1 << 27, 2), (1 << 25, 3)),
    "tpch-sf10": (8, (1 << 26, 5), (1 << 20, 7)),
}


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


def _segment(sharding, bp, d, n_pad, k=0, sort=False, nw=0):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    lanes = (n_pad // 128, 128)
    seg = {"rows": s((d,) + lanes, jnp.float32),
           "alive": s(lanes, jnp.int32),
           "flo": s((bp, d), jnp.float32), "fhi": s((bp, d), jnp.float32)}
    if k:
        seg.update(coords=s((k,) + lanes, jnp.int32),
                   first=s((bp, k), jnp.int32), last=s((bp, k), jnp.int32))
        seg["work"] = s((2 * nw,), jnp.int32)
    if sort:
        seg.update(sv=s(lanes, jnp.float32), tband=s((bp, 2), jnp.float32))
    cfg = (DEFAULT_HIT_CAP, bool(k), sort, True, False, 0, nw if k else 0)
    return seg, cfg


@pytest.mark.parametrize("bp", [4, 64])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_wave_program_compiles_for_v5e(one_chip, no_persistent_cache, bp,
                                       table):
    d, primary, outlier = TABLES[table]
    nw = primary[0] // STEP_ROWS                  # the plan's list width
    segs = [_segment(one_chip, bp, d, *primary, sort=True, nw=nw),
            _segment(one_chip, bp, d, *outlier, sort=True, nw=nw),
            _segment(one_chip, bp, d, DELTA_ROWS)]
    fn = jax.jit(_wave_program, static_argnums=1)
    compiled = fn.lower(tuple(s for s, _ in segs),
                        tuple(c for _, c in segs)).compile()
    text = compiled.as_text()
    # a listed and a full-scan kernel per grid segment, one for the delta
    assert text.count("tpu_custom_call") >= 5
    # only the kernel launches (and the tuple reads of their outputs) carry
    # the kernel's name, so the trace's kernel time counts no enclosing
    # operation, such as the lax.cond around them
    named = [ln for ln in text.splitlines() if "coax_fused_scan" in ln
             and " = " in ln]
    assert named and all("custom-call(" in ln or "get-tuple-element(" in ln
                         for ln in named), named
    out = jax.eval_shape(fn, tuple(s for s, _ in segs),
                         tuple(c for _, c in segs))
    for counts, hits, scanned in out:
        assert counts.shape == scanned.shape == (bp, 1)
        assert hits.shape == (bp, DEFAULT_HIT_CAP)


@pytest.fixture(scope="module")
def tpch_compaction(one_chip, no_persistent_cache):
    """``compact_range`` compiled at the TPC-H primary's shapes: the
    segment's words for a bucket of 4, one pass of ``LARGE_CHUNK`` slots."""
    _, (n_pad, _), _ = TABLES["tpch-sf10"]

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    fn = jax.jit(compact_range, static_argnames=("chunk",))
    args = (s((4, n_pad // WORD_BITS)), s((4,)), s(()))
    out = jax.eval_shape(functools.partial(fn, chunk=LARGE_CHUNK), *args)
    return fn.lower(*args, chunk=LARGE_CHUNK).compile(), out


def test_large_pass_compiles_for_v5e(one_chip, no_persistent_cache,
                                     tpch_compaction):
    d, (n_pad, k), _ = TABLES["tpch-sf10"]
    seg, cfg = _segment(one_chip, 4, d, n_pad, k, sort=True,
                        nw=n_pad // STEP_ROWS)
    words_fn = jax.jit(_words_program, static_argnums=1)
    compiled = words_fn.lower(seg, cfg).compile()
    # the listed and the full-scan kernel, under the list's lax.cond
    assert compiled.as_text().count("tpu_custom_call") >= 2
    words = jax.eval_shape(words_fn, seg, cfg)
    assert words.shape == (4, n_pad // WORD_BITS)
    _, out = tpch_compaction
    assert out.shape == (4, LARGE_CHUNK)


def _indexed_ops(hlo: str):
    """``(op, name, index count)`` of every scatter and gather in an HLO
    module's text: the element count of its second operand, the indices."""
    shapes = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo))
    ops = []
    for name, op, operands in re.findall(
            r"%([\w.\-]+) = \S+ (scatter|gather)\(([^)]*)\)", hlo):
        idx = operands.split(",")[1].strip().lstrip("%")
        dims = re.findall(r"\d+", shapes[idx].split("[", 1)[1])
        ops.append((op, name, math.prod(int(x) for x in dims)))
    return ops


def test_large_pass_compaction_has_no_per_word_scatter(tpch_compaction):
    """The compaction places hits by dense shift rounds: its optimized HLO
    at the TPC-H shapes holds no scatter or gather with an index per word
    or per slot (2^20 or more indices), which a TPU runs one by one."""
    compiled, _ = tpch_compaction
    per_word = [op for op in _indexed_ops(compiled.as_text())
                if op[2] >= 1 << 20]
    assert not per_word, per_word
