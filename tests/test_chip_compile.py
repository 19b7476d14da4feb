"""Compiles for a described TPU v5e: the serving path's kernels and its
whole escalation round, lowered and compiled by the TPU compiler with no
chip attached.

Interpret-mode tests cannot see what the chip's compiler refuses
(unsupported casts in a kernel, misaligned blocks, VMEM overruns); these
can.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scope fixture and never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.  Keep all chip compiles in this one file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.clt_grng import GRNGConfig
from repro.core.quant import QuantConfig
from repro.kernels import ops
from repro.kernels.backend import interpret_override
from repro.kernels.clt_grng_kernel import grng_eps_pallas
from repro.kernels.decision_kernel import (decision_stats_pallas,
                                           decision_stats_sharded)

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip are written to the persistent
    cache but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _degraded_grng() -> GRNGConfig:
    return GRNGConfig(read_sigma=0.05, noise_seed=7)


def _decision_args(b, n, r, sharding, read_noise):
    f32 = jnp.float32
    args = dict(
        y_mu=jax.ShapeDtypeStruct((b, n), f32, sharding=sharding),
        x_sigma=jax.ShapeDtypeStruct((b, n), f32, sharding=sharding),
        m=jax.ShapeDtypeStruct((b, n, 16), f32, sharding=sharding),
        sel=jax.ShapeDtypeStruct((r, b, 16), f32, sharding=sharding),
        mask=jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=sharding))
    if read_noise:
        args.update(
            x_sigsq=jax.ShapeDtypeStruct((b, n), f32, sharding=sharding),
            sample_idx=jax.ShapeDtypeStruct((r, b), jnp.uint32,
                                            sharding=sharding))
    return args


@pytest.mark.parametrize("read_noise", [False, True],
                         ids=["ideal", "read_noise"])
@pytest.mark.parametrize("b,n,r", [(32, 2, 4), (8, 151936, 4)],
                         ids=["sar_head", "vocab_head"])
def test_decision_kernel_compiles(one_chip, no_cache, b, n, r, read_noise):
    cfg = _degraded_grng() if read_noise else GRNGConfig()
    args = _decision_args(b, n, r, one_chip, read_noise)

    def fn(**kw):
        return decision_stats_pallas(cfg=cfg, interpret=False, **kw)

    text = jax.jit(fn).lower(**args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("read_noise", [False, True],
                         ids=["ideal", "read_noise"])
@pytest.mark.parametrize("b,k,n", [(32, 64, 2), (8, 1024, 4096)],
                         ids=["sar_head", "wide_head"])
def test_bayes_mvm_rank16_compiles(one_chip, no_cache, b, k, n, read_noise):
    cfg = _degraded_grng() if read_noise else GRNGConfig()
    x = jax.ShapeDtypeStruct((b, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=one_chip)

    def fn(x, mu, sigma):
        return ops.bayes_head_mvm(x, mu, sigma, cfg, 8, mode="rank16",
                                  interpret=False)

    text = jax.jit(fn).lower(x, w, w).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("read_noise", [False, True],
                         ids=["ideal", "read_noise"])
def test_grng_kernel_compiles(one_chip, no_cache, read_noise):
    cfg = _degraded_grng() if read_noise else GRNGConfig()
    sel = jax.ShapeDtypeStruct((8, 16), jnp.float32, sharding=one_chip)

    def fn(sel):
        return grng_eps_pallas(sel, cfg, 64, 2, bk=128, bn=128,
                               interpret=False)

    text = jax.jit(fn).lower(sel).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", [(7200, 64, 16), (1568, 192, 32),
                                   (288, 320, 64)])
def test_cim_nonideal_trunk_compiles(one_chip, no_cache, m, k, n):
    qcfg = QuantConfig(enabled=True)
    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((m, k), f32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), f32, sharding=one_chip)
    col = jax.ShapeDtypeStruct((n,), f32, sharding=one_chip)

    def fn(x, w, gain, off):
        return ops.cim_matmul_nonideal(x, w, qcfg, gain, off,
                                       interpret=False)

    text = jax.jit(fn).lower(x, w, col, col).compile().as_text()
    assert "tpu_custom_call" in text


def _sar_round_program(severity: float, gang_mesh=None):
    """A fresh (never traced) jitted SAR round for 32 slots on an ideal
    or severity-``severity`` die, plus its live argument shapes.  With
    ``gang_mesh`` it is instead the fleet's gang round with one 32-slot
    pool per mesh device, and the arguments are the global shapes."""
    from repro.core.bayes_layer import sigma_of
    from repro.core.sampling import BayesHeadConfig
    from repro.hw import (VariationSpec, prepare_instance_head,
                          sample_instances)
    from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
    from repro.obs.telemetry import TelemetryConfig, init_telemetry
    from repro.serving import adaptive
    from repro.serving.engine import _sar_featurize_fn, _sar_round_fn
    from repro.serving.triage import TriagePolicy

    n_slots = 32
    cfg = SarCnnConfig()
    params = init_sar_cnn(jax.random.PRNGKey(3), cfg)
    policy = TriagePolicy(conf_threshold=0.7, mi_threshold=0.05)
    hcfg = BayesHeadConfig(num_samples=policy.r_max, mode="rank16",
                           grng=cfg.grng, compute_dtype=jnp.float32,
                           hoist_basis=True)
    chip = None
    if severity:
        chip = sample_instances(11, 1, VariationSpec().scaled(severity))[0]
    head, hcfg = prepare_instance_head(
        params["head"]["mu"], sigma_of(params["head"]), hcfg, chip)
    assert bool(hcfg.grng.read_sigma) == bool(severity)
    img = jax.ShapeDtypeStruct((n_slots, cfg.image_size, cfg.image_size, 1),
                               jnp.float32)
    pool = jax.eval_shape(_sar_featurize_fn(cfg, hcfg, chip, None),
                          params, head, img)
    tcfg = TelemetryConfig()
    args = (pool,
            jax.eval_shape(lambda: adaptive.init_stats(n_slots, 2)),
            jax.ShapeDtypeStruct((n_slots,), jnp.uint32),
            jax.ShapeDtypeStruct((n_slots,), jnp.bool_),
            jax.eval_shape(lambda: init_telemetry(tcfg, policy.r_max)))
    # __wrapped__: a new jit object, so no trace cached by an earlier
    # interpret-mode run in this process is reused.
    if gang_mesh is None:
        fn = _sar_round_fn.__wrapped__(hcfg, policy, True, policy.r_min,
                                       True, None, tcfg)
        return fn, args
    from repro.serving.fleet import _sar_gang_fn
    p = gang_mesh.size
    sharding = NamedSharding(gang_mesh, P("pool"))
    slot_major = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((p * x.shape[0],) + x.shape[1:],
                                       x.dtype, sharding=sharding),
        args[:4])
    telem = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((p,) + x.shape, x.dtype,
                                       sharding=sharding), args[4])
    fn = _sar_gang_fn.__wrapped__(hcfg, policy, True, policy.r_min, True,
                                  gang_mesh, tcfg)
    return fn, (*slot_major, telem)


@pytest.mark.parametrize("severity", [0.0, 2.5], ids=["ideal", "sev2.5"])
def test_sar_round_compiles(one_chip, no_cache, severity):
    fn, args = _sar_round_program(severity)
    with interpret_override(False):
        lowered = fn.lower(*_sds(args, one_chip))
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("read_noise", [False, True],
                         ids=["ideal", "read_noise"])
def test_sharded_decision_kernel_has_no_collectives(topo, no_cache,
                                                    read_noise):
    mesh = Mesh(np.asarray(topo.devices), ("slot",))
    b, n, r = 32, 2, 4
    cfg = _degraded_grng() if read_noise else GRNGConfig()
    rows = NamedSharding(mesh, P("slot"))
    args = _decision_args(b, n, r, rows, read_noise)
    args["sel"] = jax.ShapeDtypeStruct((r, b, 16), jnp.float32,
                                       sharding=NamedSharding(
                                           mesh, P(None, "slot")))
    if read_noise:
        args["sample_idx"] = jax.ShapeDtypeStruct(
            (r, b), jnp.uint32, sharding=NamedSharding(mesh, P(None, "slot")))

    def fn(**kw):
        return decision_stats_sharded(cfg=cfg, mesh=mesh, axis="slot",
                                      interpret=False, **kw)

    text = jax.jit(fn).lower(**args).compile().as_text()
    assert "tpu_custom_call" in text
    for op in _COLLECTIVES:
        assert op not in text, op


@pytest.mark.parametrize("severity", [0.0, 2.5], ids=["ideal", "sev2.5"])
def test_fleet_gang_round_compiles_without_collectives(topo, no_cache,
                                                       severity):
    mesh = Mesh(np.asarray(topo.devices), ("pool",))
    fn, args = _sar_round_program(severity, gang_mesh=mesh)
    with interpret_override(False):
        lowered = fn.lower(*args)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    for op in _COLLECTIVES:
        assert op not in text, op
