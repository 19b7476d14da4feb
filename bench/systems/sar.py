"""The SAR triage system, built from a configuration that names
``"system": "sar"``.

This is the program's own serving path: ``SarServingEngine`` with
``serve_sar``'s defaults (telemetry, stage profiler and SLO tracker on)
and the configuration's detector, GRNG, triage policy and slot count.
The benchmark drives ``step`` directly.

Set-up loads the detector's trained weights
(``bench/weights/<config>.npz``, phase ``weights``), renders the traffic's
image bank on the device from the seed (phase ``bank``) and builds the
engine and the feed (phase ``build``).  After the window a sample of what
was served is checked against the plain reference (``bench/check.py``).
"""

from __future__ import annotations

import time

import jax

# the names --control may take: "unfused" serves the program's unfused
# decision path, "reference_bf16" puts the reference, computed one
# precision step below the configuration's, in the program's place
CONTROLS = ("unfused", "reference_bf16")

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


class Feed:
    """Requests over the image bank, in an order drawn from the seed."""

    def __init__(self, bank, seed: int):
        import numpy as np
        from repro.serving.engine import Request
        self._request = Request
        self.bank = bank
        self.order = np.random.default_rng(
            [seed & 0xFFFFFFFF, seed >> 32, 0xFEED]).permutation(len(bank))
        self.next_rid = 0

    def image_of(self, rid: int):
        return self.bank[self.order[rid % len(self.bank)]]

    def make(self):
        rid = self.next_rid
        self.next_rid += 1
        return self._request(rid=rid, payload=self.image_of(rid))


def build(cell, seed: int, control: str | None, phases: dict):
    """(system, feed, trained parameters) of ``cell``, each phase timed
    into ``phases``."""
    import numpy as np

    from bench import sard
    cfg, traffic = cell.cfg, cell.traffic

    t = time.perf_counter()
    params = sard.load_params(sard.WEIGHTS / f"{cell.config}.npz",
                              sard.recipe_of(cfg))
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    bank_spec = traffic["bank"]
    bank = np.asarray(sard.image_bank(
        jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                           seed >> 32),
        bank_spec["images"], cfg["model"]["image_size"],
        bank_spec["fog_share"], bank_spec["fog_severity"]))
    phases["bank"] = time.perf_counter() - t
    t = time.perf_counter()
    system = System(cfg, params, fused=control != "unfused")
    feed = Feed(bank, seed)
    phases["build"] = time.perf_counter() - t
    return system, feed, params


def decisions(record) -> int:
    """A retired crop is one triage decision."""
    return 1


def compare(cell, params, served, feed, due: list, seed: int, *,
            submitted: int, control: str | None, log) -> list:
    """The served sample against the plain reference (``bench/check.py``)."""
    from bench import check
    return check.compare(cell.cfg, params, served, feed.image_of, due, seed,
                         submitted=submitted, control=control, log=log)
