"""Continuous-batching serving engine with adaptive-fidelity slots.

The paper's deployment target is a latency/energy-constrained edge
engine; the ROADMAP's is a service under heavy traffic.  Both reduce to
the same scheduling problem: keep a fixed pool of decode slots full,
retire a request the moment its decision is made, and refill the slot
from the admission queue without stalling the others.  This module
implements that engine twice over one scheduler skeleton:

``SarServingEngine`` — the paper's workload.  A request is one aerial
image patch; its per-slot state is the rank-16 **activation basis**
(core/sampling.activation_basis): 16 basis products computed once at
admission, after which every escalation round costs only a [r,16]
mixing contraction.  Slots sit at *different* escalation depths — an
easy image retires after the first 4-sample round while its neighbor
escalates to 20 — which is where adaptive fidelity buys throughput.

``LMServingEngine`` — token streams.  Slots share a synchronized decode
clock (the KV cache layout has one scalar ``pos``); per-token head
sampling escalates in geometric rounds with early exit when every
active slot has decided.  Mid-stream admission is *exact* for RoPE
trunks: a new prompt is prefilled left-padded at the fixed admission
length, its cached K re-rotated by the pool-clock offset (RoPE scores
depend only on relative distance, so a uniform rotation re-bases the
stream), rolled into place, and masked via the per-slot ``start``
recorded by prefill (models/attention.py).  SSM slots are recurrent
state rows — the *scatter* is exact, but the admitted state carries a
documented approximation: prefill_ssm runs the left-pad prefix through
the recurrence (an exact path would re-run the bare prompt at
slot-local positions), so a fresh SSM slot starts pad-polluted.
Measured (test_serving.test_ssm_leftpad_admission_pollution_quantified):
~30% relative hidden error at admission for a short prompt behind a
long zero pad, decaying below 5% within 3 decode steps — the selective
state space forgets the pad like a short neutral context.  Trunks whose
positions cannot be re-based (learned absolute positions, e.g. whisper)
still serve correctly: admission simply waits for the pool to drain and
rebase to delta = 0, where left-padded prefill needs no re-basing.

Slot state lives in donated device buffers: admission scatters rows
into the pool pytree with ``.at[idx].set(..., mode='drop')`` (a fixed
out-of-range index parks unused admission rows), and every jitted pool
update donates its inputs, so the engine never holds two copies of a
KV cache.  All jitted shapes are fixed by (n_slots, prompt_len,
round sizes): the compile set is O(len(schedule)), not O(traffic).

Hot-path execution (this is the repo's hottest loop — see
kernels/decision_kernel.py):

  * **Device-resident escalation.**  Each dispatch runs a
    ``lax.while_loop`` of escalation rounds ON DEVICE — on-device
    ``triage.decide``, donated stats — and returns to the host only
    when some active slot has decided (so the scheduler can retire and
    refill it) or the R budget is exhausted.  The LM engine runs its
    whole geometric schedule per token in ONE dispatch
    (``lax.cond``-skipped rounds after every slot decides).  The old
    one-host-sync-per-4-samples pattern is gone; ``host_syncs`` counts
    the blocking device→host round trips that remain.

  * **Fused decision kernel** (``fused=True``, the default): each round
    folds samples into the running sufficient statistics via
    ``kernels.ops.decision_update`` — mixing, read-noise projection,
    online softmax over N, entropy, and active-slot masking all in
    VMEM; the [R, B, N] logit-sample tensor never materializes.
    ``fused=False`` keeps the pure-jnp ``mix_samples → update_stats``
    path (verdict-identical; tests/test_decision_kernel.py).

  * **Shared compile cache.**  The jitted pool functions are built by
    module-level ``lru_cache`` builders keyed on the (hashable, frozen)
    configs, so every engine instance with the same shapes and policy
    reuses the same compiled executables — constructing an engine per
    benchmark run or per chip instance no longer recompiles the world.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.sampling import (BayesHeadConfig, activation_basis,
                                 mix_samples)
from repro.obs import prof
from repro.obs.prof import NULL_PROFILER, StageProfiler
from repro.obs.telemetry import (TelemetryConfig, count_dispatch,
                                 init_telemetry, record_decisions,
                                 record_round)
from repro.obs.telemetry import snapshot as telemetry_snapshot
from repro.obs.slo import NULL_SLO, SloTracker
from repro.obs.trace import NULL_TRACER
from repro.serving import adaptive, triage
from repro.serving.metrics import RequestRecord, ServingMetrics
from repro.serving.triage import ACCEPT, ESCALATE, FLAG, TriagePolicy


@dataclasses.dataclass
class Request:
    """One unit of admission: an image (SAR) or a prompt (LM).

    ``arrival_s`` is a wall-clock timestamp (when the request entered
    the system); ``arrival_pc`` is the monotonic ``perf_counter`` twin
    stamped at ``submit`` and used for latency intervals, so a wall
    clock stepping backwards can never produce negative latencies."""
    rid: int
    payload: Any                      # [H,W,1] image | [L] token ids
    arrival_s: float = 0.0
    max_new_tokens: int = 8           # LM only
    meta: dict = dataclasses.field(default_factory=dict)
    arrival_pc: float = 0.0


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    admit_s: float = 0.0              # perf_counter stamp at admission
    first_dispatch_s: float = 0.0     # first dispatch covering this slot
    n_samples: int = 0                # accumulated over the request
    n_decisions: int = 0              # tokens decided (LM) / 1 (SAR)


# ----------------------------------------------------------------------
# process-wide jitted pool functions (shared across engine instances)
# ----------------------------------------------------------------------
def _constrainer(slot_axis: str | None):
    if slot_axis is None:
        return lambda tree: tree
    from jax.sharding import PartitionSpec as P

    def constrain(tree):
        return jax.tree.map(
            lambda leaf: jax.lax.with_sharding_constraint(
                leaf, P(slot_axis, *(None,) * (leaf.ndim - 1))),
            tree)

    return constrain


@functools.lru_cache(maxsize=None)
def _scatter_fn(slot_axis: str | None):
    prof.count_build("scatter")
    constrain = _constrainer(slot_axis)

    def scatter(pool, rows, idx):
        return constrain(jax.tree.map(
            lambda p, r: p.at[idx].set(r, mode="drop"), pool, rows))

    return jax.jit(scatter, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _stats_reset_fn():
    prof.count_build("stats_reset")

    def stats_reset(stats, idx):
        return jax.tree.map(
            lambda s: s.at[idx].set(0, mode="drop"), stats)

    return jax.jit(stats_reset, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _sar_featurize_fn(cfg, hcfg: BayesHeadConfig, chip,
                      slot_axis: str | None):
    """jit (params, head, images) -> activation-basis rows.

    Cached on the frozen configs + the chip instance's identity
    (ChipInstance is ``eq=False`` — a given die's nonideal trunk
    constants are baked into one executable, reused by every engine
    bound to that die).  Bounded: a fleet sweep over many chips evicts
    least-recently-used entries instead of pinning every die's
    executable (live engines keep their own reference)."""
    prof.count_build("sar_featurize")
    from repro.models.sar_cnn import features
    constrain = _constrainer(slot_axis)

    def featurize(params, head, images):
        feats = features(params, images, cfg, chip=chip)
        return constrain(activation_basis(head, feats, hcfg))

    return jax.jit(featurize)


def _one_round(pool, stats, base, active, *, hcfg: BayesHeadConfig,
               policy: TriagePolicy, adaptive_mode: bool, r_step: int,
               fused: bool, constrain, tcfg: TelemetryConfig | None = None,
               telem=None, shard=None):
    """One escalation round: draw r_step per active slot, fold into the
    running stats (fused kernel or jnp), finalize, decide.

    ``shard`` is an optional ``(mesh, axis_name)``: the fused kernel
    then runs shard_map-native over the slot axis (its own Pallas grid
    per device, slot-local stats, global-row hash keys — bit-identical
    to the unsharded kernel).

    With ``tcfg``/``telem`` set, the round also folds the device-resident
    telemetry pytree (round counters + GRNG probe moments) — pure extra
    arithmetic on arrays already in the graph, never a sync."""
    grng = hcfg.grng
    sel = adaptive.stream_selections(grng, base, stats["n"], r_step)
    idx = adaptive.stream_indices(base, stats["n"], r_step)
    if fused:
        from repro.kernels.ops import decision_update
        stats = decision_update(stats, pool, sel, grng,
                                sample_idx=idx, mask=active, shard=shard)
    else:
        samples = mix_samples(pool, sel, hcfg, sample_idx=idx)
        stats = adaptive.update_stats(stats, samples, mask=active)
    stats = constrain(stats)
    fin = adaptive.finalize(stats)
    if adaptive_mode:
        verdict = triage.decide(fin, policy,
                                final=fin["n"] >= policy.r_max)
    else:
        verdict = triage.fixed_r_decide(fin, policy)
    if telem is not None:
        telem = record_round(telem, tcfg, grng, sel, idx, active)
    return stats, verdict, fin, telem


def _build_multi_round(*, hcfg: BayesHeadConfig, policy: TriagePolicy,
                       adaptive_mode: bool, r_step: int, fused: bool,
                       constrain, tcfg: TelemetryConfig | None = None,
                       shard=None):
    """Un-jitted device-resident escalation loop — the shared core of
    ``_sar_round_fn`` (per-engine dispatch) and the fleet gang round
    (serving/fleet.py shard_maps it over the pool axis).

    Returns (pool, stats, base, active) -> (stats, verdict, fin, rounds)
    — or the telemetry-carrying variant when ``tcfg`` is set (telem
    rides the while_loop carry; decisions recorded once after the loop,
    which only exits when a verdict leaves ESCALATE or the pool idles).
    """
    kw = dict(hcfg=hcfg, policy=policy, adaptive_mode=adaptive_mode,
              r_step=r_step, fused=fused, constrain=constrain,
              shard=shard)

    if tcfg is None:
        def multi_round(pool, stats, base, active):
            stats, verdict, fin, _ = _one_round(pool, stats, base,
                                                active, **kw)

            def cond(state):
                _, v, _f, _k = state
                return jnp.any(active) & ~jnp.any(active
                                                  & (v != ESCALATE))

            def body(state):
                s, _v, _f, k = state
                s, v, f, _ = _one_round(pool, s, base, active, **kw)
                return (s, v, f, k + jnp.int32(1))

            return lax.while_loop(cond, body,
                                  (stats, verdict, fin, jnp.int32(1)))

        return multi_round

    kw_t = dict(kw, tcfg=tcfg)

    def multi_round_t(pool, stats, base, active, telem):
        stats, verdict, fin, telem = _one_round(pool, stats, base,
                                                active, telem=telem,
                                                **kw_t)

        def cond(state):
            _, v, _f, _k, _t = state
            return jnp.any(active) & ~jnp.any(active & (v != ESCALATE))

        def body(state):
            s, _v, _f, k, t = state
            s, v, f, t = _one_round(pool, s, base, active, telem=t,
                                    **kw_t)
            return (s, v, f, k + jnp.int32(1), t)

        stats, verdict, fin, rounds, telem = lax.while_loop(
            cond, body, (stats, verdict, fin, jnp.int32(1), telem))
        decided = active & (verdict != ESCALATE)
        telem = record_decisions(telem, tcfg, fin, verdict, decided)
        telem = count_dispatch(telem)
        return stats, verdict, fin, rounds, telem

    return multi_round_t


@functools.lru_cache(maxsize=128)
def _sar_round_fn(hcfg: BayesHeadConfig, policy: TriagePolicy,
                  adaptive_mode: bool, r_step: int, fused: bool,
                  slot_axis: str | None,
                  tcfg: TelemetryConfig | None = None,
                  mesh=None):
    """jit (pool, stats, base, active) -> (stats, verdict, fin, rounds).

    Device-resident escalation: a ``lax.while_loop`` keeps drawing
    r_step-sample rounds for the active slots while EVERY one of them
    is still in the sequential test's ambiguity band; it exits the
    moment any slot's verdict leaves ESCALATE (that slot must retire —
    a host decision) or the budget forces a decision.  ``rounds`` is
    the number of rounds executed this dispatch (every active slot drew
    ``r_step · rounds`` samples).

    With both ``slot_axis`` and ``mesh`` set (a hashable
    jax.sharding.Mesh — engines capture the ambient one at
    construction), the fused kernel inside every round runs
    shard_map-native over the slot axis: one Pallas grid per device on
    its local slots, slot-local statistics, no collectives in the
    round's data path.  The only cross-shard coordination left is the
    while_loop exit predicate (one bool per shard per round) — required
    because retirement is a global host decision.  Without a mesh the
    old behavior stands: XLA partitions the interpret-mode lowering
    under ``with_sharding_constraint``.

    With ``tcfg`` set the signature becomes
    (pool, stats, base, active, telem) -> (..., rounds, telem): the
    telemetry pytree rides the while_loop carry and is donated back,
    so enabling it changes neither dispatch count nor sync count."""
    prof.count_build("sar_round")
    constrain = _constrainer(slot_axis)
    shard = (mesh, slot_axis) if (mesh is not None
                                  and slot_axis is not None) else None
    fn = _build_multi_round(
        hcfg=hcfg, policy=policy, adaptive_mode=adaptive_mode,
        r_step=r_step, fused=fused, constrain=constrain, tcfg=tcfg,
        shard=shard)
    if tcfg is None:
        return jax.jit(fn, donate_argnums=(1,))
    return jax.jit(fn, donate_argnums=(1, 4))


@functools.lru_cache(maxsize=128)
def _lm_token_fn(hcfg: BayesHeadConfig, policy: TriagePolicy,
                 adaptive_mode: bool, schedule: tuple, fused: bool,
                 n_slots: int, n_classes: int,
                 tcfg: TelemetryConfig | None = None,
                 slot_axis: str | None = None, mesh=None):
    """jit (abasis, base, active) -> (verdict, fin, spent).

    One whole token decision on device: zeroed stats, then the full
    geometric escalation schedule unrolled with ``lax.cond``-skipped
    rounds once every active slot has decided — stats advance only for
    active & undecided slots, exactly the old per-round host loop but
    in a single dispatch.

    With ``slot_axis``+``mesh`` set (and ``n_slots`` divisible over the
    axis) the fused kernel runs shard_map-native over the slot/batch
    dimension — the mission rollout threads its fleet×episodes batch
    axis here so die-group episodes shard like serving pools do.

    With ``tcfg`` set the signature becomes
    (abasis, base, active, telem) -> (..., spent, telem): telemetry
    rides the ``lax.cond`` state (it skips with the round), and every
    active slot's token verdict is final at schedule end (triage forces
    a decision at r_max), so decisions are recorded once on ``active``."""
    prof.count_build("lm_token")
    grng = hcfg.grng
    shard = None
    if mesh is not None and slot_axis is not None:
        size = dict(mesh.shape).get(slot_axis, 0)
        if size > 0 and n_slots % size == 0:
            shard = (mesh, slot_axis)
    identity = lambda st: st                                 # noqa: E731

    def token_decision(abasis, base, active, telem=None):
        stats = adaptive.init_stats(n_slots, n_classes)
        fin = adaptive.finalize(stats)
        verdict = jnp.full((n_slots,), ESCALATE, jnp.int32)
        spent = jnp.zeros((n_slots,), jnp.int32)
        # None is a valid (empty) pytree leaf-set: when telemetry is
        # off the carry element costs nothing and the graph is the old
        # one.
        state = (stats, active, spent, verdict, fin, telem)

        for r_k in schedule:
            def run_round(st, _r=r_k):
                stats, undec, spent, _v, _f, telem = st
                upd = active & undec
                sel = adaptive.stream_selections(grng, base,
                                                 stats["n"], _r)
                idx = adaptive.stream_indices(base, stats["n"], _r)
                if fused:
                    from repro.kernels.ops import decision_update
                    stats = decision_update(stats, abasis, sel, grng,
                                            sample_idx=idx, mask=upd,
                                            shard=shard)
                else:
                    samples = mix_samples(abasis, sel, hcfg,
                                          sample_idx=idx)
                    stats = adaptive.update_stats(stats, samples,
                                                  mask=upd)
                fin = adaptive.finalize(stats)
                if adaptive_mode:
                    verdict = triage.decide(
                        fin, policy, final=fin["n"] >= policy.r_max)
                else:
                    verdict = triage.fixed_r_decide(fin, policy)
                spent = spent + jnp.where(upd, _r, 0).astype(spent.dtype)
                undec = undec & (verdict == ESCALATE)
                if telem is not None:
                    telem = record_round(telem, tcfg, grng, sel, idx,
                                         upd)
                return (stats, undec, spent, verdict, fin, telem)

            state = lax.cond(jnp.any(state[1]), run_round, identity,
                             state)
        _, _, spent, verdict, fin, telem = state
        if telem is None:
            return verdict, fin, spent
        telem = record_decisions(telem, tcfg, fin, verdict, active)
        telem = count_dispatch(telem)
        return verdict, fin, spent, telem

    # no donation: the basis is consumed, not aliased into any output,
    # and this function also runs inside the mission episode jit where
    # donation of a captured carry would warn.
    return jax.jit(token_decision)


def pull_round(profiler: StageProfiler, verdict, fin: dict, rounds):
    """The host's one blocking pull of a round dispatch's results, as
    the ``triage_loop`` span: ``round_wait`` until the round's outputs
    are ready, then one ``verdict_pull`` span per array copied to the
    host (the verdicts, each ``fin`` key, the trip count), so the span
    count is the transfer count.  Returns the host copies
    ``(verdict, fin, rounds)``.  Shared by the engine and the fleet.

    The verdicts' copy is queued before the wait, as a bare
    ``np.asarray`` would queue it, so it still starts the moment the
    round ends: the split adds no host round trip to the pull."""
    with profiler.span("triage_loop"):
        with profiler.span("round_wait"):
            verdict.copy_to_host_async()
            jax.block_until_ready((verdict, fin, rounds))
        with profiler.span("verdict_pull"):
            verdict = np.asarray(verdict)
        host_fin = {}
        for k, v in fin.items():
            with profiler.span("verdict_pull"):
                host_fin[k] = np.asarray(v)
        with profiler.span("verdict_pull"):
            rounds = np.asarray(rounds)
    return verdict, host_fin, rounds


class _EngineBase:
    """Queue + slot bookkeeping shared by both engines."""

    def __init__(self, n_slots: int, policy: TriagePolicy,
                 metrics: ServingMetrics | None,
                 telemetry: bool | TelemetryConfig = True,
                 tracer=None,
                 profiler: bool | StageProfiler = True,
                 slo=True,
                 trace_pid: int = 0):
        self.n_slots = n_slots
        self.policy = policy
        self.queue: deque[Request] = deque()
        self.slots = [_Slot() for _ in range(n_slots)]
        self.free: list[int] = list(range(n_slots))
        self.metrics = metrics or ServingMetrics()
        self._decision_counter = 0
        # Blocking device→host round trips on the decision path (one per
        # round dispatch: the verdict/fin pull).  serving_bench reports
        # host_syncs / decisions — the tentpole metric of the
        # device-resident escalation loop.
        self.host_syncs = 0
        # Device-resident telemetry (obs/telemetry): rides the jitted
        # round dispatches and is pulled only in telemetry_snapshot().
        if telemetry is True:
            telemetry = TelemetryConfig()
        self.tcfg: TelemetryConfig | None = telemetry or None
        self._telem = (init_telemetry(self.tcfg, policy.r_max)
                       if self.tcfg else None)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # Host-side stage latency histograms (obs/prof).  perf_counter
        # spans around the loop phases — never touches device state.
        if profiler is True:
            profiler = StageProfiler()
        self.profiler: StageProfiler = profiler or NULL_PROFILER
        # Collector pauses land in the same profiler (stage ``gc``) and
        # trace; engines sharing a profiler share its one hook.
        self.profiler.track_gc(self)
        # Host-side SLO lifecycle tracking (obs/slo): retired records
        # stream into time-to-verdict histograms.  True for a fresh
        # tracker this engine owns (and attaches to its summary), an
        # existing SloTracker to share one fleet-wide (the owner then
        # attaches it), False/None to disable.  Pure host bookkeeping
        # at the existing sync points: no graph change, no extra syncs.
        if slo is True:
            slo = SloTracker()
            self._own_slo = True
        else:
            self._own_slo = False
        self.slo: SloTracker = slo or NULL_SLO
        # Trace process id: 0 standalone; the fleet assigns pid p+1 so
        # every pool lands on its own named process track in ONE trace.
        self.trace_pid = int(trace_pid)
        for i in range(n_slots):
            self.tracer.name_thread(i + 1, f"slot {i}",
                                    pid=self.trace_pid)

    def submit(self, request: Request) -> None:
        if request.arrival_s == 0.0:
            request.arrival_s = time.time()
        if request.arrival_pc == 0.0:
            request.arrival_pc = time.perf_counter()
        self.queue.append(request)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self.free)

    @property
    def pending(self) -> int:
        """Requests admitted to the queue but not yet slotted."""
        return len(self.queue)

    def _next_bases(self, count: int) -> np.ndarray:
        """Reserve fresh selection-stream regions: each decision owns
        [id·r_max, (id+1)·r_max) of the global stream."""
        ids = np.arange(self._decision_counter,
                        self._decision_counter + count, dtype=np.uint32)
        self._decision_counter += count
        return ids * np.uint32(self.policy.r_max)

    def _retire(self, slot_idx: int, verdict: int, fin: dict,
                extra_samples: int,
                verdict_s: float = float("nan")) -> RequestRecord:
        """Record one decided request and free its slot.  Returns the
        record; the caller folds a tick's records into the SLO tracker
        in one ``_fold_slo`` call."""
        slot = self.slots[slot_idx]
        req = slot.req
        now = time.perf_counter()
        self.metrics.mark(now)
        rec = RequestRecord(
            rid=req.rid, verdict=int(verdict),
            n_samples=slot.n_samples + extra_samples,
            n_decisions=max(slot.n_decisions, 1),
            arrival_s=req.arrival_s, admit_s=slot.admit_s, done_s=now,
            prediction=int(fin["prediction"][slot_idx]),
            confidence=float(fin["confidence"][slot_idx]),
            mutual_information=float(fin["mutual_information"][slot_idx]),
            arrival_pc=req.arrival_pc,
            first_dispatch_s=(slot.first_dispatch_s or float("nan")),
            verdict_s=verdict_s,
        )
        self.metrics.record(rec)
        if self.tracer.enabled:
            start = slot.admit_s - self.tracer.t0
            self.tracer.complete(
                f"req {req.rid}", start, now - slot.admit_s,
                tid=slot_idx + 1, pid=self.trace_pid,
                verdict=int(verdict),
                n_samples=slot.n_samples + extra_samples,
                n_decisions=max(slot.n_decisions, 1))
            # Close this request's Perfetto flow on the slot span —
            # a fleet's router opened it when the request was routed.
            self.tracer.flow_end(f"req {req.rid}", req.rid, start,
                                 tid=slot_idx + 1, pid=self.trace_pid)
        slot.req = None
        slot.n_samples = slot.n_decisions = 0
        slot.first_dispatch_s = 0.0
        self.free.append(slot_idx)
        return rec

    def _fold_slo(self, recs: list[RequestRecord]) -> None:
        """One tick's retirements into the SLO tracker as one batch
        (span ``slo_fold``, opened only when the tick retired any)."""
        if recs:
            with self.profiler.span("slo_fold"):
                self.slo.observe_many(recs)

    def telemetry_snapshot(self) -> dict | None:
        """Host snapshot of the device-resident telemetry (one sync)."""
        if self.tcfg is None or self._telem is None:
            return None
        return telemetry_snapshot(self._telem, self.tcfg)

    def _attach_perf(self) -> None:
        """Attach the stage-profile snapshot + process compile counters
        to the run summary (surfaced as ``stage_profile`` /
        ``compile_counters`` keys; obs.registry picks both up)."""
        snap = self.profiler.snapshot()
        self.metrics.attach_profile(snap or None, prof.compile_counters())
        if self._own_slo:
            self.metrics.attach_slo(self.slo.snapshot())

    def _stamp_first_dispatch(self, active) -> None:
        """Host-side lifecycle stamp: the first dispatch that covers a
        slot.  Cheap clock arithmetic before the (already-pending)
        device round — no sync, no graph change."""
        now = time.perf_counter()
        for i in np.nonzero(active)[0]:
            if self.slots[i].first_dispatch_s == 0.0:
                self.slots[i].first_dispatch_s = now


# ----------------------------------------------------------------------
# SAR image-stream engine
# ----------------------------------------------------------------------
class SarServingEngine(_EngineBase):
    """Adaptive-fidelity victim/no-victim triage over an image stream.

    adaptive=False reproduces the paper's fixed-R dataflow inside the
    same scheduler (one r_max-sample round, decide immediately) so the
    bench compares policies, not implementations.

    Escalation here is CONSTANT-STEP (r_min samples per tick), not the
    geometric ``escalation_schedule`` the LM engine uses: slots sit at
    different escalation depths inside one fixed-shape pool round, so
    every tick must draw the same per-slot count.  ``policy.r_growth``
    therefore has no effect on this engine.  Consecutive rounds execute
    device-resident (``_sar_round_fn``): the host is re-entered only to
    retire decided slots and refill them from the queue.
    """

    def __init__(self, params, cfg, *, n_slots: int = 32,
                 policy: TriagePolicy = TriagePolicy(),
                 adaptive_mode: bool = True, metrics: ServingMetrics = None,
                 head: dict | None = None,
                 hcfg: BayesHeadConfig | None = None,
                 chip=None, slot_axis: str | None = None,
                 mesh=None,
                 fused: bool = True,
                 telemetry: bool | TelemetryConfig = True,
                 tracer=None,
                 profiler: bool | StageProfiler = True,
                 slo=True,
                 trace_pid: int = 0,
                 device=None):
        """``head``/``hcfg``: pre-deployed serving head + its config —
        the repro/hw chip-instance path (hw.calib.prepare_instance_head
        returns both; the rank-16 fast path below runs unchanged on the
        degraded instance).  Default: golden-chip head from ``params``.

        ``profiler``: host-side per-stage latency histograms
        (obs/prof.StageProfiler) over the tick (``step``) and its
        stages, and the collector's pauses (``gc``), each also a
        ``jax.profiler`` trace span — True for a fresh profiler, an
        existing StageProfiler to share one across engines, False to
        disable.  Pure host clock arithmetic: no syncs, no graph change.

        ``chip`` (a hw.ChipInstance): run the deterministic conv trunk
        on that die's nonideal CIM arrays too (models/sar_cnn.features
        with per-column ADC gain/offset + programming error) — together
        with a ``prepare_instance_head`` head this makes EVERY serving
        decision flow through the same nonideal device model.

        ``slot_axis``: mesh axis name to shard the slot (pool batch)
        dimension over — construct and run the engine inside
        ``jax.set_mesh`` and admission scatters stay slot-local while
        every pool round executes data-parallel over the slots.
        ``mesh``: the jax.sharding.Mesh carrying ``slot_axis`` (default:
        captured from the ambient mesh context at construction).  When
        the mesh is known and ``n_slots`` divides over the axis, the
        fused kernel runs shard_map-native per shard
        (kernels/decision_kernel.decision_stats_sharded) instead of
        relying on XLA to partition the interpret-mode lowering —
        verdicts are bit-identical either way (tests/test_spmd.py).

        ``fused``: fold escalation rounds through the fused Pallas
        decision kernel (kernels/decision_kernel.py) instead of the
        materializing ``mix_samples → update_stats`` path.  Verdicts
        are identical; the fused path never holds [R, B, N].

        ``telemetry``: device-resident counters/histograms/GRNG probe
        moments (obs/telemetry) riding the round dispatches — True for
        the default TelemetryConfig, a TelemetryConfig to customize,
        False to compile the exact pre-telemetry graph.  ``tracer``: an
        obs.trace.Tracer collecting per-request/per-dispatch spans.
        Neither adds host syncs or changes verdicts (tests/test_obs.py).
        ``slo``: host-side time-to-verdict tracking (obs/slo) — True
        for an owned tracker, a shared SloTracker (fleet), or False;
        like the profiler it is free at the decision level
        (tests/test_slo.py).

        ``device``: the one device this engine's parameters, pool,
        statistics and telemetry live on (None: JAX's default device,
        uncommitted).  A fleet binds pool ``p`` to device ``p``.
        """
        super().__init__(n_slots, policy, metrics, telemetry, tracer,
                         profiler, slo, trace_pid)
        self.device = device
        self._telem = jax.device_put(self._telem, device)
        from repro.core.bayes_layer import to_serving
        self.cfg = cfg
        self.adaptive_mode = adaptive_mode
        self.fused = fused
        self.hcfg = hcfg or BayesHeadConfig(
            num_samples=policy.r_max, mode="rank16", grng=cfg.grng,
            compute_dtype=jnp.float32, hoist_basis=True)
        if head is None:
            head = to_serving(params["head"], self.hcfg)
        self.r_step = policy.r_min if adaptive_mode else policy.r_max
        self._params = jax.device_put(params, device)
        self._head = jax.device_put(head, device)

        feat = _sar_featurize_fn(cfg, self.hcfg, chip, slot_axis)
        self._featurize_jit = feat
        self._featurize = lambda imgs: feat(self._params, self._head,
                                            imgs)
        self._scatter = _scatter_fn(slot_axis)
        self._stats_reset = _stats_reset_fn()
        self._mesh = self._resolve_mesh(mesh, slot_axis, n_slots)
        self._round = _sar_round_fn(self.hcfg, policy, adaptive_mode,
                                    self.r_step, fused, slot_axis,
                                    self.tcfg, mesh=self._mesh)
        self._chip = chip
        self._slot_axis = slot_axis
        self.ticks = 0                 # step() calls: the trace's step number
        self.pool = None
        self.stats = None
        self.base = None

    @staticmethod
    def _resolve_mesh(mesh, slot_axis: str | None, n_slots: int):
        """The mesh the shard_map-native round runs over, or None.

        Captures the ambient mesh when ``slot_axis`` is set but no mesh
        was passed; drops back to None (= XLA-partitioned lowering)
        when the axis is absent from the mesh or n_slots doesn't divide
        over it."""
        if slot_axis is None:
            return None
        if mesh is None:
            mesh = jax.sharding.get_abstract_mesh()
        size = dict(mesh.shape).get(slot_axis, 0)
        if size <= 0 or n_slots % size:
            return None
        return mesh

    # -- lifetime -------------------------------------------------------
    def swap_head(self, head: dict, hcfg: BayesHeadConfig) -> None:
        """Hot-swap a (re)deployed head into the RUNNING engine.

        hw/redeploy.py's self-healing loop calls this between run
        segments: after a recalibration (or an age advance of the
        served view) the new head + config replace the old ones and
        only the head-dependent builders (featurize, round) are
        re-resolved.  Those builders are module-level lru caches, so a
        previously-seen (hcfg, chip) pair is a cache HIT, and the
        epoch-free executables (scatter, stats reset, other engines')
        are untouched — ``BayesHeadConfig.calib_epoch`` keys fresh
        calibrations apart without invalidating anything else.

        Requires a quiescent pool: in-flight slots hold activations
        featurized under the old head, so swap between segments after
        ``run()`` drains the queue.  Queue contents, metrics, telemetry
        and the decision-stream counter all survive the swap.
        """
        if self.n_active:
            raise RuntimeError(
                f"swap_head with {self.n_active} in-flight slots — "
                f"drain the pool (run()) and swap between segments")
        self.hcfg = hcfg
        self._head = jax.device_put(head, self.device)
        feat = _sar_featurize_fn(self.cfg, hcfg, self._chip,
                                 self._slot_axis)
        self._featurize_jit = feat
        self._featurize = lambda imgs: feat(self._params, self._head,
                                            imgs)
        self._round = _sar_round_fn(hcfg, self.policy, self.adaptive_mode,
                                    self.r_step, self.fused,
                                    self._slot_axis, self.tcfg,
                                    mesh=self._mesh)

    # -- admission ------------------------------------------------------
    def _admit(self) -> None:
        take = min(len(self.free), len(self.queue))
        if take == 0:
            return
        with self.profiler.span("admission"):
            with self.profiler.span("admit_stack"):
                reqs = [self.queue.popleft() for _ in range(take)]
                imgs = np.stack([np.asarray(r.payload) for r in reqs])
                if take < self.n_slots:               # fixed-shape batch
                    pad = np.repeat(imgs[-1:], self.n_slots - take, axis=0)
                    imgs = np.concatenate([imgs, pad], axis=0)
            with self.tracer.span("featurize", pid=self.trace_pid,
                                  n_admitted=take), \
                    self.profiler.span("featurize"):
                rows = self._featurize(jax.device_put(imgs, self.device))
            # slot assignment, then the enqueued scatter and stats reset
            with self.profiler.span("admit_enqueue"):
                idx = np.full((self.n_slots,), self.n_slots, np.int32)
                now = time.perf_counter()
                bases = self._next_bases(take)
                for j, req in enumerate(reqs):
                    s = self.free.pop()
                    idx[j] = s
                    self.slots[s].req = req
                    self.slots[s].admit_s = now
                    self.base[s] = bases[j]
                idxj = jnp.asarray(idx)
                self.ensure_pool(like=rows)
                self.pool = self._scatter(self.pool, rows, idxj)
                self.stats = self._stats_reset(self.stats, idxj)
                self.metrics.mark(now)

    def ensure_pool(self, like: dict | None = None) -> None:
        """Materialize the (pool, stats) device state without waiting
        for the first admission.  ``like`` is an activation-basis pytree
        with leading dim ``n_slots`` (another engine's pool works) —
        the fleet gang stacks every pool engine's state into one
        dispatch, so an idle pool must still hold real zero buffers."""
        if self.pool is not None:
            return
        if like is None:
            raise ValueError("ensure_pool needs a template basis pytree")
        self.pool = jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype, device=self.device), like)
        self.stats = jax.device_put(
            adaptive.init_stats(self.n_slots, like["y_mu"].shape[-1]),
            self.device)

    def active_mask(self) -> np.ndarray:
        """[n_slots] bool — which slots hold an in-flight request."""
        return np.array([s.req is not None for s in self.slots])

    def _retire_decided(self, active, verdict, fin, spent: int,
                        verdict_s: float = float("nan")) -> int:
        """Post-dispatch draining shared with the fleet: charge samples
        to every active slot, retire those whose verdict left ESCALATE.
        ``verdict_s`` is the perf_counter stamp of the host sync that
        pulled these verdicts.  Returns the number retired."""
        recs = []
        for i in np.nonzero(active)[0]:
            self.slots[i].n_samples += spent
            if verdict[i] != ESCALATE:
                self.slots[i].n_decisions = 1
                # n_samples already accumulated; fin["n"] agrees
                recs.append(self._retire(i, verdict[i], fin,
                                         extra_samples=0,
                                         verdict_s=verdict_s))
        self._fold_slo(recs)
        return len(recs)

    # -- main loop ------------------------------------------------------
    def start(self) -> None:
        """Reset the per-run selection-stream bases.  ``run`` calls
        this; open-loop drivers (serving/load.py) call it once, then
        interleave ``submit`` with ``step`` on their own clock."""
        self.base = np.zeros((self.n_slots,), np.uint32)

    def step(self) -> bool:
        """One scheduler tick: admit from the queue, dispatch the
        device-resident escalation round, retire decided slots.
        Returns False when nothing was active (idle tick).

        The tick is one ``tick`` span (a ``sar_tick`` step in the
        trace) over admission, slot_mask, dispatch, triage_loop and
        retirement."""
        with self.profiler.span("tick", name="sar_tick", step=self.ticks):
            self.ticks += 1
            self._admit()
            if self.n_active == 0:
                return False
            with self.profiler.span("slot_mask"):
                active = self.active_mask()
                self._stamp_first_dispatch(active)
            t_disp = self.tracer.now()
            with self.profiler.span("dispatch"):
                if self.tcfg is None:
                    self.stats, verdict, fin, rounds = self._round(
                        self.pool, self.stats, jnp.asarray(self.base),
                        jnp.asarray(active))
                else:
                    (self.stats, verdict, fin, rounds,
                     self._telem) = self._round(
                        self.pool, self.stats, jnp.asarray(self.base),
                        jnp.asarray(active), self._telem)
            # ONE blocking host↔device round trip per dispatch — the
            # while_loop above already ran every all-escalate round.
            verdict, fin, rounds = pull_round(self.profiler, verdict, fin,
                                              rounds)
            spent = self.r_step * int(rounds)
            self.host_syncs += 1
            t_verdict = time.perf_counter()
            if self.tracer.enabled:
                self.tracer.complete(
                    "sar_rounds", t_disp, self.tracer.now() - t_disp,
                    pid=self.trace_pid,
                    rounds=int(rounds), n_active=int(active.sum()),
                    samples_per_slot=spent)
            with self.profiler.span("retirement"):
                self._retire_decided(active, verdict, fin, spent,
                                     verdict_s=t_verdict)
            return True

    def drain(self) -> dict:
        """Attach telemetry/perf/SLO snapshots and build the summary."""
        if self.tcfg is not None:
            self.metrics.attach_telemetry(self.telemetry_snapshot())
        self._attach_perf()
        return self.metrics.summary()

    def run(self, max_ticks: int = 100_000) -> dict:
        self.start()
        for _ in range(max_ticks):
            if not self.step() and not self.queue:
                break
        return self.drain()

    # -- compiled-cost capture (profiling path only) --------------------
    def compiled_cost_records(self) -> list[dict]:
        """obs/prof.compiled_cost records for this engine's hot jitted
        functions at the LIVE deployed shapes: the device-resident
        round fn and the featurize fn.  AOT-compiles fresh executables
        (AOT does not share the jit call cache) — call after ``run()``
        from a profiling/bench path, never inside the serving loop."""
        if self.pool is None:
            raise RuntimeError(
                "compiled_cost_records needs live pool shapes: run the "
                "engine (or admit once) first")
        sds = lambda t: jax.tree.map(                        # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        args = [sds(self.pool), sds(self.stats),
                jax.ShapeDtypeStruct((self.n_slots,), jnp.uint32),
                jax.ShapeDtypeStruct((self.n_slots,), jnp.bool_)]
        if self.tcfg is not None:
            args.append(sds(self._telem))
        recs = [prof.compiled_cost("sar_round", self._round, *args)]
        img = jax.ShapeDtypeStruct(
            (self.n_slots, self.cfg.image_size, self.cfg.image_size, 1),
            jnp.float32)
        recs.append(prof.compiled_cost(
            "sar_featurize", self._featurize_jit, sds(self._params),
            sds(self._head), img))
        return recs


# ----------------------------------------------------------------------
# LM token-stream engine
# ----------------------------------------------------------------------
def _rotate_k(k, delta, theta):
    """Re-base cached RoPE'd keys by ``delta`` positions: rotations about
    a fixed plane compose additively, so R_Δ(R_i·k) = R_{i+Δ}·k."""
    from repro.models.blocks import apply_rope
    lead = k.shape[:-3]                       # [..., Sc, H, dh]
    flat = k.reshape((-1,) + k.shape[-3:])
    pos = jnp.full((flat.shape[0], flat.shape[1]), delta, jnp.int32)
    return apply_rope(flat, pos, theta).reshape(k.shape)


class LMServingEngine(_EngineBase):
    """Continuous-batching LM decode with adaptive per-token fidelity.

    Each tick decides ONE token for every active slot in a single
    device dispatch (``_lm_token_fn``): the geometric escalation
    schedule runs on device with per-round early exit, and the host
    sees only the final (verdict, fin, spent) — one sync per token
    instead of one per escalation round.
    """

    def __init__(self, params, cfg, *, n_slots: int = 4,
                 prompt_len: int = 16, cache_len: int = 64,
                 policy: TriagePolicy = TriagePolicy(),
                 adaptive_mode: bool = True,
                 metrics: ServingMetrics = None, extras: dict | None = None,
                 fused: bool = True,
                 telemetry: bool | TelemetryConfig = True,
                 tracer=None,
                 profiler: bool | StageProfiler = True,
                 slo=True):
        super().__init__(n_slots, policy, metrics, telemetry, tracer,
                         profiler, slo)
        from repro.models.registry import get_api
        from repro.models.transformer import _head_serving
        assert cfg.bayesian_head, "adaptive serving needs the Bayesian head"
        if cfg.swa_window is not None and cache_len > cfg.swa_window:
            # Rolling (circular) SWA caches break two admission
            # invariants: the roll+rerotate alignment assumes a linear
            # layout, and decode_attention's per-slot 'start' mask is
            # only defined for linear caches.  Refuse loudly rather
            # than serve silently-wrong attention.
            raise ValueError(
                f"cache_len={cache_len} exceeds swa_window="
                f"{cfg.swa_window}: the rolling-cache decode path does "
                "not support continuous-batching admission; use "
                f"cache_len <= {cfg.swa_window} or a non-SWA arch")
        self.cfg = cfg
        self.adaptive_mode = adaptive_mode
        self.fused = fused
        self.prompt_len = prompt_len
        self.cache_len = cache_len
        # Mid-stream (delta > 0) admission re-bases cached keys by a
        # uniform RoPE rotation — only exact for rotary trunks without
        # learned absolute positions.  Other trunks still get continuous
        # batching, but admission waits for the pool to drain and
        # rebase (delta == 0), where left-padded prefill is exact.
        self.midstream_ok = bool(cfg.use_rope) and not cfg.learned_pos
        api = get_api(cfg)
        self.hcfg = BayesHeadConfig(
            num_samples=policy.r_max, mode="rank16", grng=cfg.grng,
            compute_dtype=cfg.dtype, hoist_basis=False)
        head = _head_serving(params, cfg)
        extras = extras or {}
        self.schedule = (adaptive.escalation_schedule(policy)
                         if adaptive_mode else (policy.r_max,))

        self._prefill = jax.jit(
            lambda tokens, lengths: api.prefill(
                params, tokens, cfg, cache_len=cache_len,
                prompt_lengths=lengths, **extras))

        def align_scatter(pool, new, idx, delta):
            """Roll+rerotate admission rows into the pool timeline."""
            out = {}
            for key, leaf in pool.items():
                nw = new[key]
                if key == "pos":
                    out[key] = leaf
                elif key == "start":
                    out[key] = leaf.at[idx].set(nw + delta, mode="drop")
                elif key in ("k", "v"):
                    rolled = jnp.roll(nw, delta, axis=2)
                    if key == "k" and cfg.use_rope:
                        rolled = _rotate_k(rolled, delta, cfg.rope_theta)
                    out[key] = leaf.at[:, idx].set(rolled, mode="drop")
                else:                       # xk/xv/ssm/conv: slot-local
                    out[key] = leaf.at[:, idx].set(nw, mode="drop")
            return out

        self._align_scatter = jax.jit(align_scatter, donate_argnums=(0,))

        self._decode_hidden = jax.jit(
            lambda cache, token: api.decode_hidden(params, cache, token,
                                                   cfg),
            donate_argnums=(0,))
        self._basis = jax.jit(
            lambda h: activation_basis(head, h.astype(jnp.float32),
                                       self.hcfg))
        self._scatter_hidden = jax.jit(
            lambda pool, rows, idx: pool.at[idx].set(
                rows.astype(pool.dtype), mode="drop"),
            donate_argnums=(0,))

        self._token_decision = _lm_token_fn(
            self.hcfg, policy, adaptive_mode, self.schedule, fused,
            n_slots, cfg.vocab_padded, self.tcfg)
        self.cache = None
        self.token = None
        self.hidden = None
        self.base = None
        self.vocab_padded = cfg.vocab_padded

    # -- admission ------------------------------------------------------
    def _pad_prompt(self, tokens: np.ndarray) -> tuple[np.ndarray, int]:
        tokens = np.asarray(tokens, np.int32)[-self.prompt_len:]
        length = tokens.shape[0]
        if length < self.prompt_len:
            tokens = np.concatenate(
                [np.zeros((self.prompt_len - length,), np.int32), tokens])
        return tokens, length

    def _admit(self) -> None:
        if not self.queue:
            return
        pos = int(self.cache["pos"]) if self.cache is not None else \
            self.prompt_len
        # FIFO admission with a PER-REQUEST capacity bound: a request
        # admitted at clock ``pos`` writes cache entries up to
        # pos + max_new_tokens - 1.  Stop at the first request that
        # would overflow (it waits for the pool to drain and rebase).
        if self.prompt_len + self.queue[0].max_new_tokens > self.cache_len:
            bad = self.queue[0]
            raise ValueError(
                f"request {bad.rid}: max_new_tokens={bad.max_new_tokens} "
                f"cannot fit even a fresh pool (prompt_len="
                f"{self.prompt_len}, cache_len={self.cache_len})")
        if self.cache is not None and pos > self.prompt_len \
                and not self.midstream_ok:
            return          # non-re-basable trunk: wait for pool rebase
        reqs = []
        while (self.queue and len(reqs) < len(self.free)
               and pos + self.queue[0].max_new_tokens <= self.cache_len):
            reqs.append(self.queue.popleft())
        take = len(reqs)
        if take == 0:
            return
        with self.profiler.span("admission"):
            toks = np.zeros((self.n_slots, self.prompt_len), np.int32)
            lens = np.full((self.n_slots,), self.prompt_len, np.int32)
            for j, r in enumerate(reqs):
                toks[j], lens[j] = self._pad_prompt(r.payload)
            # prefill is the LM engine's featurize: payload -> per-slot
            # device state.
            with self.tracer.span("prefill", n_admitted=take), \
                    self.profiler.span("featurize"):
                new_cache, last_h = self._prefill(jnp.asarray(toks),
                                                  jnp.asarray(lens))
            now = time.perf_counter()
            idx = np.full((self.n_slots,), self.n_slots, np.int32)
            for j, req in enumerate(reqs):
                s = self.free.pop()
                idx[j] = s
                self.slots[s].req = req
                self.slots[s].admit_s = now
            idxj = jnp.asarray(idx)
            if self.cache is None:
                self.cache = new_cache
                self.hidden = jnp.zeros((self.n_slots, last_h.shape[-1]),
                                        last_h.dtype)
            else:
                delta = pos - self.prompt_len
                self.cache = self._align_scatter(self.cache, new_cache,
                                                 idxj, jnp.int32(delta))
            # the prefill hidden decides each admitted slot's FIRST token
            # — no re-feed of the last prompt token into decode.
            self.hidden = self._scatter_hidden(self.hidden, last_h, idxj)
            self.metrics.mark(now)

    # -- main loop ------------------------------------------------------
    def run(self, max_ticks: int = 10_000) -> dict:
        """Tick = decide (head-sample self.hidden) → commit/retire →
        decode committed tokens into the next hidden.  The first
        decision of every request comes from its PREFILL hidden, so
        each prompt token enters the KV cache exactly once."""
        self.base = np.zeros((self.n_slots,), np.uint32)
        tick = 0
        while tick < max_ticks:
            tick += 1
            self._admit()
            if self.n_active == 0:
                if not self.queue:
                    break
                self.cache = None                      # rebase the pool
                continue
            active = np.array([s.req is not None for s in self.slots])
            self._stamp_first_dispatch(active)
            # one token decision for every active slot, ONE dispatch:
            # the whole escalation schedule runs device-resident.
            t_disp = self.tracer.now()
            with self.profiler.span("dispatch"):
                abasis = self._basis(self.hidden)
                self.base = self._next_bases(self.n_slots)
                if self.tcfg is None:
                    verdict, fin, spent = self._token_decision(
                        abasis, jnp.asarray(self.base),
                        jnp.asarray(active))
                else:
                    verdict, fin, spent, self._telem = \
                        self._token_decision(
                            abasis, jnp.asarray(self.base),
                            jnp.asarray(active), self._telem)
            # blocking pull of the token's escalation outcome — the
            # whole on-device schedule shows up as this host wait.
            with self.profiler.span("triage_loop"):
                verdict = np.asarray(verdict)
                spent = np.asarray(spent)
                fin = {k: np.asarray(v) for k, v in fin.items()}
            self.host_syncs += 1
            t_verdict = time.perf_counter()
            if self.tracer.enabled:
                self.tracer.complete(
                    "lm_token", t_disp, self.tracer.now() - t_disp,
                    n_active=int(active.sum()),
                    samples=int(spent[active].sum()))
            self.token = jnp.asarray(
                fin["prediction"].astype(np.int32)[:, None])
            with self.profiler.span("retirement"):
                recs = []
                for i in np.nonzero(active)[0]:
                    slot = self.slots[i]
                    slot.n_samples += int(spent[i])
                    slot.n_decisions += 1
                    done = slot.n_decisions >= slot.req.max_new_tokens
                    if verdict[i] == FLAG or (verdict[i] == ACCEPT
                                              and done):
                        recs.append(self._retire(
                            i, verdict[i], fin, extra_samples=0,
                            verdict_s=t_verdict))
                self._fold_slo(recs)
            if self.n_active == 0 and not self.queue:
                break                       # nothing left to decode for
            # advance the pool clock: committed tokens -> next hidden
            with self.profiler.span("dispatch"):
                self.hidden, self.cache = self._decode_hidden(self.cache,
                                                              self.token)
        if self.tcfg is not None:
            self.metrics.attach_telemetry(self.telemetry_snapshot())
        self._attach_perf()
        return self.metrics.summary()

    # -- compiled-cost capture (profiling path only) --------------------
    def compiled_cost_records(self) -> list[dict]:
        """obs/prof.compiled_cost record for the per-token decision fn
        at the live hidden/basis shapes (AOT; profiling path only)."""
        if self.hidden is None:
            raise RuntimeError(
                "compiled_cost_records needs live shapes: run the "
                "engine (or admit once) first")
        sds = lambda t: jax.tree.map(                        # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        abasis = jax.eval_shape(self._basis, sds(self.hidden))
        args = [abasis,
                jax.ShapeDtypeStruct((self.n_slots,), jnp.uint32),
                jax.ShapeDtypeStruct((self.n_slots,), jnp.bool_)]
        if self.tcfg is not None:
            args.append(sds(self._telem))
        return [prof.compiled_cost("lm_token", self._token_decision,
                                   *args)]
