"""SPMD tests under a forced multi-device host platform (subprocess).

The main test process sees 1 CPU device (per the dry-run contract, the
512-device override lives ONLY in dryrun.py).  These tests spawn fresh
interpreters with XLA_FLAGS to validate multi-device semantics:
sharded-MoE ≡ GSPMD oracle, distributed train-step equivalence, and
elastic re-meshing.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_spmd(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_moe_matches_gspmd_oracle():
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.models.moe import init_moe, moe_apply, make_sharded_moe
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
E, D, F, k = 4, 32, 64, 2
p = init_moe(jax.random.PRNGKey(0), 1, D, F, E)
r, wi, wg, wo = p["router"][0], p["wi"][0], p["wg"][0], p["wo"][0]
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, D))
y_ref, _ = moe_apply(x, r, wi, wg, wo, top_k=k, capacity_factor=8.0)
with jax.set_mesh(mesh):
    moe = make_sharded_moe(mesh, top_k=k, capacity_factor=8.0,
                           n_experts=E, dp_axes=("data",))
    y, _ = jax.jit(moe)(x, r, wi, wg, wo)
assert np.allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
print("OK")
""")


def test_distributed_train_step_matches_single_device():
    """One jitted train step on a (2,2) mesh must equal the unsharded
    step (same data, same init) — the sharding is semantics-preserving."""
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.steps import jit_train_step, make_train_step, \
    mesh_hinted_config, input_specs
from repro.optim import AdamWConfig, init_opt_state
from repro.models.registry import get_api
from repro.data.tokens import TokenPipelineConfig, batch_at

cfg0 = get_config("qwen3-0.6b", smoke=True)
opt_cfg = AdamWConfig()
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
pipe = TokenPipelineConfig(vocab=cfg0.vocab, seq_len=16, global_batch=4)
batch = batch_at(pipe, 0)

api = get_api(cfg0)
params = api.init(jax.random.PRNGKey(0), cfg0)
opt = init_opt_state(params)
ref_step = make_train_step(cfg0, opt_cfg)
p_ref, o_ref, m_ref = jax.jit(ref_step)(params, opt, batch)

with jax.set_mesh(mesh):
    jitted, _, _, cfg2 = jit_train_step(cfg0, mesh, opt_cfg, 16, 4)
    p_sh, o_sh, m_sh = jitted(params, opt, batch)
assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 2e-2, (
    float(m_ref["loss"]), float(m_sh["loss"]))
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=0.05, atol=0.05)
print("OK")
""")


def test_elastic_remesh_under_devices():
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import make_elastic_mesh, shrink_mesh, remesh_train_state
devs = jax.devices()
mesh = make_elastic_mesh(devs)
new_mesh = shrink_mesh(mesh, {devs[-1].id, devs[-2].id})
assert new_mesh.devices.size <= len(devs) - 2
params = {"w": jnp.arange(64.0).reshape(8, 8)}
opt = {"mu": {"w": jnp.zeros((8, 8))}, "nu": {"w": jnp.zeros((8, 8))},
       "count": jnp.int32(3)}
p2, o2 = remesh_train_state(params, opt, new_mesh)
np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(params["w"]))
assert int(o2["count"]) == 3
print("OK")
""")


def test_sharded_serving_pool_matches_single_device():
    """ROADMAP open item: run the serving engine under a 2-device mesh
    with the slot axis sharded over 'data' (engine ``slot_axis``).  The
    pool rounds execute data-parallel over the slots; admission scatters
    stay slot-local; every request must retire with the same prediction
    and verdict as the unsharded engine."""
    run_spmd("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.launch.serve import make_sar_stream
from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
from repro.serving import SarServingEngine, TriagePolicy

cfg = SarCnnConfig()
params = init_sar_cnn(jax.random.PRNGKey(3), cfg)
policy = TriagePolicy(conf_threshold=0.6, mi_threshold=0.05,
                      r_min=4, r_max=12)

def run(slot_axis, mesh):
    eng = SarServingEngine(params, cfg, n_slots=4, policy=policy,
                           adaptive_mode=True, slot_axis=slot_axis)
    for r in make_sar_stream(10, batch=8):
        eng.submit(r)
    eng.run()
    return {r.rid: (r.prediction, r.verdict, r.n_samples)
            for r in eng.metrics.records}

ref = run(None, None)
mesh = make_mesh((2, 1), ("data", "model"))
with jax.set_mesh(mesh):
    got = run("data", mesh)
assert set(ref) == set(got) == set(range(10))
for rid in ref:
    assert ref[rid] == got[rid], (rid, ref[rid], got[rid])
print("OK")
""", devices=2)


def test_shard_map_fused_kernel_bit_identical():
    """ISSUE gate (shard_map-native decision kernel): on a fixed
    192-request SARD stream the sharded fused engine must produce
    verdicts BIT-FOR-BIT identical to the single-device fused engine
    (confidence/MI floats included — the hash3 read-noise/GRNG streams
    are keyed on global sample index, so shard-local execution draws
    the same noise), and verdict-identical to the materializing jnp
    path.  Ideal die AND a severity-2.5 chip instance (the chip path
    exercises the global-row ``rows`` operand of
    kernels.decision_kernel.decision_stats_sharded).  Host-sync counts
    and the compiled round's largest live intermediate must not grow."""
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.hlo_analysis import largest_intermediate_bytes
from repro.launch.mesh import make_mesh
from repro.launch.serve import make_sar_stream
from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
from repro.serving import SarServingEngine, TriagePolicy
from repro.serving import adaptive as ad

cfg = SarCnnConfig()
params = init_sar_cnn(jax.random.PRNGKey(3), cfg)
policy = TriagePolicy(conf_threshold=0.7, mi_threshold=0.05,
                      r_min=4, r_max=20)

def chip_head():
    from repro.core.bayes_layer import sigma_of
    from repro.core.sampling import BayesHeadConfig
    from repro.hw import VariationSpec, prepare_instance_head, \\
        sample_instances
    chip = sample_instances(0, 1, VariationSpec().scaled(2.5))[0]
    base = BayesHeadConfig(num_samples=policy.r_max, mode="rank16",
                           grng=cfg.grng, compute_dtype=jnp.float32,
                           hoist_basis=True)
    head, hcfg = prepare_instance_head(
        params["head"]["mu"], sigma_of(params["head"]), base, chip,
        calibrated=True)
    return dict(chip=chip, head=head, hcfg=hcfg)

def run(slot_axis, mesh, fused, extra):
    eng = SarServingEngine(params, cfg, n_slots=32, policy=policy,
                           adaptive_mode=True, slot_axis=slot_axis,
                           mesh=mesh, fused=fused, telemetry=False,
                           **extra)
    for r in make_sar_stream(192, corrupt_frac=0.25):
        eng.submit(r)
    eng.run()
    recs = {r.rid: (int(r.prediction), r.verdict, int(r.n_samples),
                    float(r.confidence), float(r.mutual_information))
            for r in eng.metrics.records}
    return recs, eng

def round_peak(eng):
    b, n = 32, cfg.n_classes
    pool = jax.tree.map(lambda x: jnp.zeros_like(x), eng.pool)
    txt = eng._round.lower(pool, ad.init_stats(b, n),
                           jnp.zeros((b,), jnp.uint32),
                           jnp.ones((b,), bool)).compile().as_text()
    return largest_intermediate_bytes(txt)

mesh = make_mesh((2, 1), ("data", "model"))
for tag, extra in (("ideal", {}), ("chip2.5", chip_head())):
    ref, eng_ref = run(None, None, True, extra)
    jnp_ref, _ = run(None, None, False, extra)
    with jax.set_mesh(mesh):
        got, eng_sh = run("data", mesh, True, extra)
    assert eng_sh._mesh is not None, tag   # shard_map-native path taken
    assert set(ref) == set(got) == set(range(192)), tag
    for rid in ref:
        assert ref[rid] == got[rid], (tag, rid, ref[rid], got[rid])
        assert ref[rid][:3] == jnp_ref[rid][:3], (tag, rid)
    assert eng_ref.host_syncs == eng_sh.host_syncs, (
        tag, eng_ref.host_syncs, eng_sh.host_syncs)
    peak_ref = round_peak(eng_ref)
    with jax.set_mesh(mesh):
        peak_sh = round_peak(eng_sh)
    assert peak_sh <= peak_ref * 1.01, (tag, peak_sh, peak_ref)
    print(tag, "OK", eng_ref.host_syncs, peak_ref, peak_sh)
print("OK")
""", devices=2)


def test_fleet_gang_matches_standalone_pools():
    """ISSUE gate (mesh-of-pools fleet): the ONE-gang-dispatch-per-tick
    fleet over a 4-device ("pool",) mesh must produce bit-for-bit the
    verdicts of the sequential fallback — which dispatches each pool
    through its OWN engine round, i.e. standalone pools fed the same
    admission sequences (the router is deterministic)."""
    run_spmd("""
import jax
from repro.launch.serve import serve_sar_fleet
from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn

cfg = SarCnnConfig()
params = init_sar_cnn(jax.random.PRNGKey(3), cfg)
kw = dict(n_requests=256, n_pools=4, slots_per_pool=16,
          corrupt_frac=0.25, params=params, cfg=cfg)
a = serve_sar_fleet(gang=True, **kw)
b = serve_sar_fleet(gang=False, **kw)
assert a["gang"] is True and b["gang"] is False
assert a["decisions"] == b["decisions"] == 256
assert a["routed_per_pool"] == b["routed_per_pool"]
assert a["verdicts"] == b["verdicts"]   # bitwise: floats + pool ids
# the gang folds P pools into one sync per tick: strictly fewer host
# syncs than one-dispatch-per-pool, at the same decision count
assert a["host_syncs"] < b["host_syncs"]
print("OK")
""", devices=8)


def test_fleet_gang_keeps_each_pool_on_its_device():
    """With the gang, pool p's parameters, slot pool, statistics and
    telemetry live on mesh device p only, across ticks and after the
    drain — nothing is replicated or parked on device 0."""
    run_spmd("""
import jax
from repro.launch.serve import make_sar_stream
from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
from repro.serving import SarServingFleet

cfg = SarCnnConfig()
params = init_sar_cnn(jax.random.PRNGKey(3), cfg)
fleet = SarServingFleet(params, cfg, n_pools=4, slots_per_pool=8,
                        gang=True)
for r in make_sar_stream(64, corrupt_frac=0.25):
    fleet.submit(r)
out = fleet.run()
assert out["gang"] and out["decisions"] == 64
devices = list(fleet.mesh.devices.flat)
for p, eng in enumerate(fleet.engines):
    leaves = jax.tree.leaves((eng._params, eng._head, eng.pool, eng.stats,
                              eng._telem))
    placed = set().union(*(x.devices() for x in leaves))
    assert placed == {devices[p]}, (p, placed)
print("OK")
""", devices=4)


def test_microbatched_step_matches_full_batch():
    run_spmd("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.steps import jit_train_step
from repro.optim import AdamWConfig, init_opt_state
from repro.models.registry import get_api
from repro.data.tokens import TokenPipelineConfig, batch_at

cfg0 = get_config("qwen3-1.7b", smoke=True)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
pipe = TokenPipelineConfig(vocab=cfg0.vocab, seq_len=16, global_batch=8)
batch = batch_at(pipe, 0)
api = get_api(cfg0)

def fresh():
    # the jitted step DONATES params/opt — fresh, uncommitted copies
    # per call (created OUTSIDE the mesh context so jit may reshard)
    p = api.init(jax.random.PRNGKey(0), cfg0)
    return p, init_opt_state(p)

params, opt = fresh()
with jax.set_mesh(mesh):
    j1, _, _, _ = jit_train_step(cfg0, mesh, AdamWConfig(), 16, 8)
    p1, o1, m1 = j1(params, opt, batch)
params, opt = fresh()
with jax.set_mesh(mesh):
    j4, _, _, _ = jit_train_step(cfg0, mesh, AdamWConfig(), 16, 8,
                                 microbatches=4)
    p4, o4, m4 = j4(params, opt, batch)
# NOTE: microbatch CE is averaged over chunks — losses should be close;
# grads differ only by accumulation order (and the per-step CLT draw is
# shared since step index is equal).
assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-2
print("OK")
""")
