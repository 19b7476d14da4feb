"""The system under test, built from a configuration file.

This is the program's own serving path: ``SarServingEngine`` with
``serve_sar``'s defaults (telemetry, stage profiler and SLO tracker on)
and the configuration's detector, GRNG, triage policy and slot count.
The benchmark drives ``step`` directly.
"""

from __future__ import annotations

import jax

# sizes the program's detector does not take as settings: a configuration
# that states others cannot be run as stated
FIXED = {"stride": 2, "head_rank": 16}


def program_config(cfg: dict):
    """(SarCnnConfig, TriagePolicy) as the configuration states them."""
    from repro.core.clt_grng import GRNGConfig
    from repro.models.sar_cnn import SarCnnConfig
    from repro.serving.triage import TriagePolicy
    m = cfg["model"]
    for key, value in FIXED.items():
        if m[key] != value:
            raise ValueError(f"model.{key} = {m[key]!r}: the program's "
                             f"detector has {value}")
    model = SarCnnConfig(image_size=m["image_size"],
                         channels=tuple(m["channels"]), kernel=m["kernel"],
                         n_classes=m["n_classes"], sigma_init=m["sigma_init"],
                         prior_sigma=m["prior_sigma"],
                         kl_weight=m["kl_weight"],
                         grng=GRNGConfig(**m["grng"]))
    p = cfg["policy"]
    policy = TriagePolicy(conf_threshold=p["conf_threshold"],
                          mi_threshold=p["mi_threshold"], z=p["z"],
                          r_min=p["r_min"], r_max=p["r_max"])
    return model, policy


class System:
    """The engine of one chip, as the benchmark drives it."""

    def __init__(self, cfg: dict, params, *, fused: bool = True):
        from repro.serving import SarServingEngine
        model, policy = program_config(cfg)
        self.policy = policy
        self.slots = cfg["slots"]
        self.engine = SarServingEngine(params, model, n_slots=self.slots,
                                       policy=policy, fused=fused)
        self.engines = [self.engine]
        self.profiler = self.engine.profiler
        self.tick = self.engine.step
        self.r_step = self.engine.r_step
        self.engine.start()

    def submit(self, req) -> None:
        self.engine.submit(req)

    @property
    def pending(self) -> int:
        return self.engine.pending

    @property
    def n_active(self) -> int:
        return self.engine.n_active

    def devices(self):
        return [self.engine.device or jax.devices()[0]]
