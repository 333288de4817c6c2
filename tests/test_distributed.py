"""Distribution-layer tests: sharding rules, sharded-vs-single-device step
equivalence, pipeline parallelism, gradient compression, HLO parsing.

Multi-device cases run in subprocesses with fake XLA devices so the main
test process keeps exactly one device.  Mesh construction goes through
``launch.mesh.make_mesh`` (Auto axis types)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro.configs import SHAPES, get_config
from repro.distributed.compression import (Int8Compressor, TopKCompressor,
                                           make_compressed_train_step)
from repro.distributed.partitioning import logical_to_spec, use_rules
from repro.launch.hloparse import collective_bytes

def test_main_process_single_device():
    assert len(jax.devices()) == 1


# --------------------------- sharding rules ------------------------------ #

def test_rules_divisibility_adaptation():
    code = """
import jax
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_local_mesh
from repro.distributed.sharding import rules_for_arch
from repro.configs import get_config, SHAPES

mesh = make_local_mesh(2, 4)  # tp=4
r = rules_for_arch(get_config("gemma2-27b"), mesh, SHAPES["train_4k"])
assert r["heads"] == ("model",), r
assert r["attn_seq"] is None

r = rules_for_arch(get_config("qwen2-vl-2b"), mesh, SHAPES["train_4k"])
assert r["heads"] == ("model",)  # 12 % 4 == 0 on tp=4

mesh8 = make_local_mesh(1, 8)
r = rules_for_arch(get_config("qwen2-vl-2b"), mesh8, SHAPES["train_4k"])
assert r["heads"] is None and r["attn_seq"] == ("model",)  # 12 % 8 != 0

r = rules_for_arch(get_config("phi3.5-moe-42b-a6.6b"), mesh8, SHAPES["train_4k"])
assert r["experts"] == ("model",)  # 16 % 8 == 0 -> EP
r = rules_for_arch(get_config("mixtral-8x7b"), mesh8, SHAPES["train_4k"])
assert r["experts"] is None and r["expert_ff"] == ("model",)  # 8 % 8... wait
print("RULES_OK")
"""
    # mixtral E=8 divides tp=8 — adjust expectation inside subprocess
    code = code.replace(
        'assert r["experts"] is None and r["expert_ff"] == ("model",)  # 8 % 8... wait',
        'assert r["experts"] == ("model",)  # 8 % 8 == 0 -> EP on tp=8')
    out = run_in_subprocess(code, devices=8)
    assert "RULES_OK" in out


def test_tiny_batch_falls_back_to_context_parallel_decode():
    code = """
from repro.launch.mesh import make_local_mesh
from repro.distributed.sharding import rules_for_arch
from repro.configs import get_config, SHAPES
mesh = make_local_mesh(4, 2)
r = rules_for_arch(get_config("h2o-danube-3-4b"), mesh, SHAPES["long_500k"])
assert r["batch"] is None          # batch=1 cannot shard over data=4
assert r["kv_len"] == ("data",)    # cache length shards instead
print("CP_OK")
"""
    assert "CP_OK" in run_in_subprocess(code, devices=8)


def test_sharded_step_matches_single_device():
    """The same train step on a 2x2 mesh must produce the same loss as on a
    single device — GSPMD must not change the math.  ``in_shardings`` take
    explicit ``NamedSharding``s under a ``with mesh:`` scope, which both
    jax 0.4.x (no ``jax.set_mesh``) and current jax accept."""
    code = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
import jax.tree_util as jtu
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import build_model
from repro.models.common import axes_to_pspecs
from repro.distributed.partitioning import use_rules
from repro.distributed.sharding import rules_for_arch, input_pspecs
from repro.launch.mesh import make_local_mesh
from repro.optim import AdamWConfig, adamw_init
from repro.runtime.steps import make_train_step

cfg = get_config("h2o-danube-3-4b")
cfg = dataclasses.replace(cfg, n_layers=2, d_model=32, d_ff=64, vocab_size=256,
                          n_heads=4, n_kv_heads=2, head_dim=8, window=8)
model = build_model(cfg)
params, axes = model.init(jax.random.key(0))
opt = adamw_init(params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 200, (4, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, 200, (4, 16)), jnp.int32)}
step = make_train_step(model, AdamWConfig(lr=1e-3))

# single device reference
_, _, m_ref = jax.jit(step)(params, opt, batch)

mesh = make_local_mesh(2, 2)
rules = rules_for_arch(cfg, mesh)
with mesh, use_rules(rules):
    pspecs = jtu.tree_map(lambda s: NamedSharding(mesh, s),
                          axes_to_pspecs(axes, rules))
    bspecs = {"tokens": NamedSharding(mesh, P("data")),
              "labels": NamedSharding(mesh, P("data"))}
    f = jax.jit(step, in_shardings=(pspecs, None, bspecs))
    _, _, m_sh = f(params, opt, batch)
d = abs(float(m_ref["loss"]) - float(m_sh["loss"]))
assert d < 5e-3, (float(m_ref["loss"]), float(m_sh["loss"]))
print("SHARDED_OK", d)
"""
    assert "SHARDED_OK" in run_in_subprocess(code, devices=4)


def test_pipeline_parallel_forward_matches_sequential():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_forward, bubble_fraction
from repro.launch.mesh import make_mesh

assert abs(bubble_fraction(4, 12) - 3/15) < 1e-12

def stage_fn(w, x):
    return jnp.tanh(x @ w)

n_stages, d = 4, 16
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.normal(0, 0.5, (n_stages, d, d)), jnp.float32)
x = jnp.asarray(rng.normal(0, 1, (8, d)), jnp.float32)

ref = x
for s in range(n_stages):
    ref = stage_fn(ws[s], ref)

mesh = make_mesh((n_stages,), ("stage",))
out = pipeline_forward(stage_fn, ws, x, mesh, n_microbatches=4)
err = float(jnp.abs(out - ref).max())
assert err < 1e-5, err
print("PIPELINE_OK", err)
"""
    assert "PIPELINE_OK" in run_in_subprocess(code, devices=4)


def test_compressed_psum_close_to_exact():
    code = """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("data",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(0, 1, (4, 256)), jnp.float32)

@partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
def exact(x):
    return jax.lax.psum(x, "data")

@partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
def compressed(x):
    return compressed_psum(x, "data")

a, b = exact(x), compressed(x)
rel = float(jnp.abs(a - b).max() / jnp.abs(a).max())
assert rel < 0.05, rel
print("PSUM_OK", rel)
"""
    assert "PSUM_OK" in run_in_subprocess(code, devices=4)


# --------------------------- compression --------------------------------- #

def test_int8_error_feedback_reduces_bias():
    comp = Int8Compressor()
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1, 512), jnp.float32)}
    err = comp.init(g)
    acc_true = jnp.zeros(512)
    acc_comp = jnp.zeros(512)
    for _ in range(50):
        deq, err = comp.compress(g, err)
        acc_true += g["w"]
        acc_comp += deq["w"]
    # error feedback keeps the long-run sums together
    rel = float(jnp.abs(acc_true - acc_comp).max() / jnp.abs(acc_true).max())
    assert rel < 0.01
    assert comp.wire_bytes_ratio() == 0.25


def test_topk_compressor_sparsity():
    comp = TopKCompressor(frac=0.1)
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(0, 1, 1000), jnp.float32)}
    err = comp.init(g)
    kept, err = comp.compress(g, err)
    nz = float((kept["w"] != 0).mean())
    assert 0.05 <= nz <= 0.15


def test_compressed_train_step_trains():
    import dataclasses
    from conftest import make_batch, tiny_config
    from repro.models import build_model
    from repro.optim import AdamWConfig, adamw_init
    cfg = tiny_config(get_config("mamba2-130m"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    comp = Int8Compressor()
    step = jax.jit(make_compressed_train_step(model, AdamWConfig(lr=3e-3), comp))
    opt = adamw_init(params)
    ef = comp.init(params)
    losses = []
    for i in range(12):
        batch = make_batch(cfg, batch=2, seq=16, seed=i % 3)
        params, opt, ef, m = step(params, opt, ef, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


# --------------------------- HLO parsing --------------------------------- #

def test_collective_bytes_parser():
    hlo = """
  %x = bf16[16,4096]{1,0} all-reduce(bf16[16,4096]{1,0} %a), replica_groups={}
  %y = f32[8,128]{1,0} all-gather(f32[8,32]{1,0} %b), dimensions={1}
  %z = (f32[4,4]{1,0}, f32[4,4]{1,0}) reduce-scatter(f32[16,4]{1,0} %c, f32[16,4]{1,0} %d)
  %w = f32[64]{0} all-reduce-start(f32[64]{0} %e)
  %w2 = f32[64]{0} all-reduce-done(f32[64]{0} %w)
  %n = f32[2,2]{1,0} add(f32[2,2]{1,0} %p, f32[2,2]{1,0} %q)
"""
    total, per = collective_bytes(hlo)
    assert per["all-reduce"] == 16 * 4096 * 2 + 64 * 4
    assert per["all-gather"] == 8 * 128 * 4
    assert per["reduce-scatter"] == 2 * 16 * 4
    assert total == sum(per.values())


def test_logical_to_spec_rules():
    from jax.sharding import PartitionSpec as P
    rules = {"batch": ("pod", "data"), "heads": ("model",), "seq": None}
    with use_rules(rules):
        assert logical_to_spec(("batch", "seq", "heads")) == P(("pod", "data"), None, "model")
    assert logical_to_spec(("batch",), None) == P()  # no rules -> replicated
