"""Distribution tests (subprocess: needs multi host-device XLA_FLAGS)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # These tests shard over FAKE host devices (XLA_FLAGS in HEADER) — pin
    # the platform: on a host with libtpu installed but no chip attached,
    # JAX would spend a minute probing for a TPU per subprocess, and on a
    # TPU host it would take one real device and make the 8-device mesh
    # impossible.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return out.stdout


HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
"""


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_local():
    """(2,4) mesh train step == single-device step (same grads/params)."""
    _run(HEADER + """
from repro import configs
from repro.models import transformer as tf
from repro.optim.adamw import AdamW
from repro.train.step import init_train_state, make_train_step
from repro.parallel.context import ParallelContext, local_context
from repro.data.synthetic import make_batch
from repro.launch.mesh import make_test_mesh, make_context

cfg = configs.get_config("internlm2-1.8b").smoke()
opt = AdamW(learning_rate=1e-3)
policy = tf.build_policy(cfg)
batch = make_batch(0, 0, 8, 128, cfg.vocab)

state_l = init_train_state(cfg, opt, jax.random.PRNGKey(0), policy)
step_l = jax.jit(make_train_step(cfg, local_context(), opt))
nl, ml = step_l(state_l, batch)

mesh = make_test_mesh(2, 4)
ctx = make_context(mesh)
state_s = init_train_state(cfg, opt, jax.random.PRNGKey(0), policy)
with jax.set_mesh(mesh):
    step_s = jax.jit(make_train_step(cfg, ctx, opt))
    ns, ms = step_s(state_s, batch)
np.testing.assert_allclose(float(ml["loss"]), float(ms["loss"]), rtol=1e-4)
for a, b in zip(jax.tree.leaves(nl.params), jax.tree.leaves(ns.params)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-3,
                               atol=2e-4)
print("OK")
""")


@pytest.mark.slow
def test_moe_shard_map_matches_local():
    """EP-as-TP MoE under a real mesh == local (single-shard) MoE."""
    _run(HEADER + """
from repro import configs
from repro.models import mlp
from repro.parallel.context import ParallelContext, local_context
from repro.launch.mesh import make_test_mesh, make_context

cfg = configs.get_config("dbrx-132b").smoke()
p = mlp.init_moe(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(4, 32, cfg.d_model)) * 0.3, jnp.float32)
bits = {"moe_router": jnp.float32(8.0),
        "moe_gateup": jnp.full((cfg.n_experts,), 4.0, jnp.float32),
        "moe_down": jnp.full((cfg.n_experts,), 4.0, jnp.float32)}

y_local, aux_l = mlp.moe_apply(p, x, bits, cfg, local_context())

mesh = make_test_mesh(2, 4)
ctx = make_context(mesh)
with jax.set_mesh(mesh):
    y_shard, aux_s = jax.jit(
        lambda p, x: mlp.moe_apply(p, x, bits, cfg, ctx))(p, x)
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_shard),
                           rtol=3e-3, atol=3e-3)
print("OK")
""")


@pytest.mark.slow
def test_int8_grad_compression_close_to_exact():
    _run(HEADER + """
from repro import configs
from repro.models import transformer as tf
from repro.optim.adamw import AdamW
from repro.train.step import init_train_state, make_train_step
from repro.data.synthetic import make_batch
from repro.launch.mesh import make_test_mesh, make_context

cfg = configs.get_config("olmo-1b").smoke()
opt = AdamW(learning_rate=1e-3)
policy = tf.build_policy(cfg)
mesh = make_mesh((8,), ("data",))
from repro.parallel.context import ParallelContext
ctx = ParallelContext(mesh=mesh, batch_axes=("data",))
batch = make_batch(0, 0, 8, 128, cfg.vocab)

s0 = init_train_state(cfg, opt, jax.random.PRNGKey(0), policy)
with jax.set_mesh(mesh):
    exact = jax.jit(make_train_step(cfg, ctx, opt))
    comp = jax.jit(make_train_step(cfg, ctx, opt, grad_compression="int8"))
    ne, _ = exact(s0, batch)
    s1 = init_train_state(cfg, opt, jax.random.PRNGKey(0), policy)
    nc, _ = comp(s1, batch)
errs = []
for a, b in zip(jax.tree.leaves(ne.params), jax.tree.leaves(nc.params)):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    denom = np.abs(a).max() + 1e-9
    errs.append(np.abs(a - b).max() / denom)
assert max(errs) < 0.1, max(errs)
print("OK")
""")


@pytest.mark.slow
def test_sharded_serving_bit_exact_with_single_device():
    """Tensor-parallel packed serving (EngineSpec(mesh=...), 8 host
    devices, model=4) is token-for-token BIT-EXACT with single-device
    decode for >=16 greedy tokens on olmo-1b smoke — packed weights over
    the full-dtype cache AND the int8 / packed-int4 quantized caches —
    and the cache's per-device resident bytes shard exactly n_shards
    ways."""
    _run(HEADER + """
from repro import configs
from repro.models import transformer as tf
from repro.parallel.context import local_context
from repro.serve import EngineSpec, ServeEngine, pack_params

cfg = configs.get_config("olmo-1b").smoke()
ctx = local_context()
params = tf.init_params(cfg, jax.random.PRNGKey(0))
policy = tf.build_policy(cfg)
arrays = policy.as_arrays()
pa = jax.tree.map(jnp.asarray, arrays)
rng = np.random.default_rng(2)
prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
mesh = make_mesh((2, 4), ("data", "model"))     # all 8 host devices
for cache, bits in (("full", 8), ("quantized", 8), ("quantized", 4)):
    e1 = ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg), policy_arrays=pa, ctx=ctx, max_seq=64, spec=EngineSpec(weights="packed", cache=cache, cache_bits=bits))
    eS = ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg), policy_arrays=pa, ctx=ctx, max_seq=64, spec=EngineSpec(weights="packed", cache=cache, cache_bits=bits, mesh=mesh))
    want = np.asarray(e1.generate(prompt, n_new=16))
    got = np.asarray(eS.generate(prompt, n_new=16))
    np.testing.assert_array_equal(got, want)
    rep = eS.residency(eS.new_cache(2))
    assert rep["per_device_kv_bytes"] * 4 == rep["resident_kv_bytes"], rep
    assert rep["per_device_weight_bytes"] < rep["resident_weight_bytes"]
print("OK")
""")


@pytest.mark.slow
def test_sharded_paged_serving_bit_exact_parity_ladder():
    """Tensor-parallel PAGED serving composes: EngineSpec(mesh=...,
    cache_layout="paged") is token-for-token BIT-EXACT with BOTH
    single-device paged decode AND contiguous+mesh decode — full-dtype,
    int8 and packed-int4 caches — and the physical page pools shard
    exactly n_shards ways on the KV-head axis (the per-device paged
    residency columns) while the block table stays replicated."""
    _run(HEADER + """
from repro import configs
from repro.models import transformer as tf
from repro.parallel.context import local_context
from repro.serve import EngineSpec, ServeEngine, pack_params

cfg = configs.get_config("olmo-1b").smoke()
ctx = local_context()
params = tf.init_params(cfg, jax.random.PRNGKey(0))
policy = tf.build_policy(cfg)
arrays = policy.as_arrays()
pa = jax.tree.map(jnp.asarray, arrays)
rng = np.random.default_rng(7)
prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
mesh = make_mesh((2, 4), ("data", "model"))     # all 8 host devices

def mk(cache, bits, layout, m, **kw):
    if layout == "paged":
        kw.update(cache_layout="paged", page_size=16)
    return ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg),
                       policy_arrays=pa, ctx=ctx, max_seq=64,
                       spec=EngineSpec(weights="packed", cache=cache,
                                       cache_bits=bits, mesh=m, **kw))

for cache, bits in (("full", 8), ("quantized", 8), ("quantized", 4)):
    solo_p = mk(cache, bits, "paged", None)
    mesh_c = mk(cache, bits, "contiguous", mesh)
    mesh_p = mk(cache, bits, "paged", mesh)
    want = np.asarray(solo_p.generate(prompt, n_new=16))
    np.testing.assert_array_equal(
        np.asarray(mesh_c.generate(prompt, n_new=16)), want)
    np.testing.assert_array_equal(
        np.asarray(mesh_p.generate(prompt, n_new=16)), want)
    # page pools shard n_shards ways; block table + lengths replicate
    rep = mesh_p.residency(mesh_p.new_cache(2))
    assert rep["per_device_paged_page_bytes"] * 4 == \
        rep["paged_page_bytes"], rep
    assert rep["per_device_paged_slot_bytes"] * 4 == \
        rep["paged_slot_bytes"] or rep["paged_slot_bytes"] == 0, rep
    assert rep["per_device_kv_bytes"] * 4 == rep["resident_kv_bytes"], rep
print("OK")
""")


@pytest.mark.slow
def test_sharded_paged_scheduler_evict_readmit_recycled_pages():
    """The continuous-batching scheduler drives a SHARDED paged engine
    unchanged: 3 requests through 1 slot on a deliberately TIGHT page
    pool, so every later admission lands on RECYCLED physical pages —
    eviction, re-admission and page reuse under the mesh stay
    token-for-token equal to solo paged decode."""
    _run(HEADER + """
from repro import configs
from repro.models import transformer as tf
from repro.parallel.context import local_context
from repro.serve import EngineSpec, Request, ServeEngine, pack_params, serve_all

cfg = configs.get_config("olmo-1b").smoke()
ctx = local_context()
params = tf.init_params(cfg, jax.random.PRNGKey(0))
policy = tf.build_policy(cfg)
arrays = policy.as_arrays()
pa = jax.tree.map(jnp.asarray, arrays)
mesh = make_mesh((4,), ("model",))

def mk(m, **kw):
    return ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg),
                       policy_arrays=pa, ctx=ctx, max_seq=64,
                       spec=EngineSpec(weights="packed", cache="quantized",
                                       cache_bits=8, cache_layout="paged",
                                       page_size=16, mesh=m, **kw))

rng = np.random.default_rng(11)
prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (9, 14, 7)]
reqs = [Request(uid=f"r{i}", prompt=p, max_new_tokens=6)
        for i, p in enumerate(prompts)]
# 1 slot needs ceil((16+6)/16) = 2 pages -> a 3-page pool forces r1/r2
# onto pages recycled from evicted predecessors
eQ = mk(mesh, n_pages=3)
res = serve_all(eQ, reqs, n_slots=1)
solo = mk(None)                          # capacity-parity fresh pool
for i, p in enumerate(prompts):
    want = np.asarray(solo.generate(jnp.asarray([p], jnp.int32), n_new=6))
    assert res[f"r{i}"].tokens == want[0].tolist(), f"r{i}"
print("OK")
""")


@pytest.mark.slow
def test_sharded_serving_scheduler_and_mixed_policy():
    """The continuous-batching scheduler drives a SHARDED engine with zero
    changes (admit/evict/re-admit == solo), and a REAL mixed 4/2-bit
    knapsack policy (per-layer packed shapes, row-repacked shards) stays
    bit-exact with its single-device run."""
    _run(HEADER + """
from repro import configs
from repro.core import knapsack
from repro.models import transformer as tf
from repro.parallel.context import local_context
from repro.serve import EngineSpec, Request, ServeEngine, pack_params, serve_all

cfg = configs.get_config("olmo-1b").smoke()
ctx = local_context()
params = tf.init_params(cfg, jax.random.PRNGKey(0))
policy = tf.build_policy(cfg)
mixed = policy.apply_selection(knapsack.select_for_budget(
    policy, knapsack.synthetic_gains(policy), budget_frac=0.7).take)
arrays = mixed.as_arrays()
pa = jax.tree.map(jnp.asarray, arrays)
mesh = make_mesh((4,), ("model",))
rng = np.random.default_rng(3)
prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, 12)), jnp.int32)
e1 = ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg), policy_arrays=pa, ctx=ctx, max_seq=64, spec=EngineSpec(weights="packed"))
eS = ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg), policy_arrays=pa, ctx=ctx, max_seq=64, spec=EngineSpec(weights="packed", mesh=mesh))
np.testing.assert_array_equal(np.asarray(eS.generate(prompt, n_new=16)),
                              np.asarray(e1.generate(prompt, n_new=16)))
# scheduler (UNCHANGED) over the sharded engine: 2 requests, 1 slot ->
# eviction + re-admission into the freed slot, quantized cache re-grid
eQ = ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg), policy_arrays=pa, ctx=ctx, max_seq=64, spec=EngineSpec(weights="packed", cache="quantized", cache_bits=8, mesh=mesh))
prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (9, 14)]
reqs = [Request(uid=f"r{i}", prompt=p, max_new_tokens=6)
        for i, p in enumerate(prompts)]
res = serve_all(eQ, reqs, n_slots=1)
for i, p in enumerate(prompts):
    solo = np.asarray(eQ.generate(jnp.asarray([p], jnp.int32), n_new=6))
    assert res[f"r{i}"].tokens == solo[0].tolist(), f"r{i}"
print("OK")
""")


@pytest.mark.slow
def test_sharded_serving_moe_expert_ffn():
    """Sharded packed serving of an MoE config (every expert's gate/up
    column- and down row-parallel over d_ff; the MoE combine is linear in
    the expert partials so ONE psum completes the whole block) ==
    single-device, bit-exact."""
    _run(HEADER + """
from repro import configs
from repro.core import knapsack
from repro.models import transformer as tf
from repro.parallel.context import local_context
from repro.serve import EngineSpec, ServeEngine, pack_params

# dbrx smoke is MQA (1 KV head -> nothing to shard the cache on); serve a
# GQA variant of the same MoE architecture.
cfg = configs.get_config("dbrx-132b").smoke().replace(n_kv_heads=2)
ctx = local_context()
params = tf.init_params(cfg, jax.random.PRNGKey(1))
policy = tf.build_policy(cfg)
mixed = policy.apply_selection(knapsack.select_for_budget(
    policy, knapsack.synthetic_gains(policy), budget_frac=0.6).take)
arrays = mixed.as_arrays()
pa = jax.tree.map(jnp.asarray, arrays)
rng = np.random.default_rng(19)
prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, 10)), jnp.int32)
e1 = ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg), policy_arrays=pa, ctx=ctx, max_seq=40, spec=EngineSpec(weights="packed"))
eS = ServeEngine(cfg=cfg, params=pack_params(params, arrays, cfg), policy_arrays=pa, ctx=ctx, max_seq=40, spec=EngineSpec(weights="packed", mesh=make_mesh((2,), ("model",))))
np.testing.assert_array_equal(np.asarray(eS.generate(prompt, n_new=8)),
                              np.asarray(e1.generate(prompt, n_new=8)))
print("OK")
""")


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    _run(HEADER + """
from repro.parallel.pp import pipeline_apply
mesh = make_mesh((4, 2), ("pod", "model"))
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.normal(size=(4, 16, 16)) * 0.3, jnp.float32)
xs = jnp.asarray(rng.normal(size=(8, 2, 16)), jnp.float32)
block = lambda w, x: jnp.tanh(x @ w)
out = pipeline_apply(block, ws, xs, mesh=mesh, axis="pod")
ref = xs
for s in range(4):
    ref = jax.vmap(lambda x: block(ws[s], x))(ref)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                           atol=1e-5)
print("OK")
""")


@pytest.mark.slow
def test_elastic_replan_and_reshard(tmp_path):
    """Train on 8 devices, checkpoint, reload re-sharded for 4 devices."""
    _run(HEADER + f"""
from repro import configs
from repro.models import transformer as tf
from repro.optim.adamw import AdamW
from repro.train.step import init_train_state, make_train_step
from repro.data.synthetic import make_batch
from repro.checkpoint.manager import CheckpointManager
from repro.launch import elastic
from repro.launch.mesh import make_context

cfg = configs.get_config("olmo-1b").smoke()
opt = AdamW(learning_rate=1e-3)
policy = tf.build_policy(cfg)

plan8 = elastic.plan_mesh(8, model_degree=4, global_batch=8)
mesh8, ctx8 = elastic.build(plan8)
state = init_train_state(cfg, opt, jax.random.PRNGKey(0), policy)
with jax.set_mesh(mesh8):
    step8 = jax.jit(make_train_step(cfg, ctx8, opt))
    state, _ = step8(state, make_batch(0, 0, 8, 64, cfg.vocab))
mgr = CheckpointManager({str(tmp_path)!r}, async_save=False)
mgr.save(1, state)

# "lose" half the fleet -> replan on 4 devices, keep TP degree
plan4 = elastic.plan_mesh(4, model_degree=4, global_batch=8)
assert plan4.mesh_shape == (1, 4)
mesh4, ctx4 = elastic.build(plan4)
_, restored = mgr.restore_latest(state)
with jax.set_mesh(mesh4):
    step4 = jax.jit(make_train_step(cfg, ctx4, opt))
    out, m = step4(restored, make_batch(0, 1, 8, 64, cfg.vocab))
assert np.isfinite(float(m["loss"]))
print("OK")
""")
