"""Mesh-of-pools fleet serving: one SarServingEngine pool per device.

The single-pool engine already drives one device well — device-resident
escalation, ~0.05 host syncs/decision, a fused decision kernel.  This
module is the scale-out layer on top (ROADMAP item 1): ``N`` complete
engine pools tiled over a 1-D ``("pool",)`` mesh, a data-parallel
admission router in front, and ONE gang dispatch per fleet tick.

Architecture (each box is a full SarServingEngine):

    submit() ──▶ fleet backlog ──▶ least-loaded router
                                     │ (bounded per-pool queues:
                                     │  a saturated pool backpressures)
          ┌───────────┬──────────────┼──────────────┬───────────┐
          ▼           ▼              ▼              ▼
      ┌───────┐   ┌───────┐      ┌───────┐      ┌───────┐
      │pool 0 │   │pool 1 │      │pool 2 │      │pool 3 │   ("pool",)
      │ S slots│  │ S slots│     │ S slots│     │ S slots│    mesh axis
      └───┬───┘   └───┬───┘      └───┬───┘      └───┬───┘
          └───────────┴───── gang ───┴──────────────┘
                one shard_map'd round dispatch / tick
                (per-pool lax.while_loop, independent
                 trip counts, slot-local stats)
                          │
                          ▼
              one blocking host sync / tick:
              retire + refill every pool's slots

Why a *gang* dispatch: decisions/s on the single-pool engine is ~99.5%
host/dispatch overhead (wall 3958 vs model 890k decisions/s at the
bench workload), so running P pools as P independent dispatch loops
would pay that overhead P times.  Instead each fleet tick stacks the
per-pool (pool, stats, base, active) states inside ONE jitted call,
shard_maps the engine's own ``_build_multi_round`` body over the
``("pool",)`` mesh, and pulls all P pools' verdicts in one sync —
retirement drains at exactly the engine's existing host-sync points,
so fleet host_syncs/decision *improves* on the single-pool ~0.05 as P
grows.

Bit-identity: each shard runs the unmodified engine round body on one
complete pool (its own while_loop exit predicate, over only its own
slots — the same cond a standalone engine evaluates), and stream bases
are assigned by each pool engine's own decision counter at admission.
A pool inside the gang therefore produces bit-for-bit the verdicts of a
standalone engine fed the same admission sequence
(tests/test_spmd.py::test_fleet_gang_matches_standalone_pools).  An
idle pool in a gang tick runs one fully-masked round: zero stat/sample
deltas by construction (only its telemetry rounds/dispatch counters
tick, which is what executed).

Aggregation reuses the single-pool machinery unchanged: per-pool
``ServingMetrics`` (energy: Σ per-request ``request_energy`` — the
fleet summary is the exact sum of pool sums), per-pool device telemetry
merged with ``obs.telemetry.merge_snapshots``, and a shared
StageProfiler."""

from __future__ import annotations

import functools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import prof
from repro.obs.prof import NULL_PROFILER, StageProfiler
from repro.obs.slo import NULL_SLO, SloTracker
from repro.obs.telemetry import TelemetryConfig, merge_snapshots
from repro.obs.trace import NULL_TRACER
from repro.serving.engine import (Request, SarServingEngine,
                                  _build_multi_round, pull_round)
from repro.serving.metrics import ServingMetrics
from repro.serving.triage import TriagePolicy

POOL_AXIS = "pool"


def make_pool_mesh(n_pools: int):
    """1-D ``("pool",)`` mesh over the first ``n_pools`` devices."""
    from repro.launch.mesh import make_mesh
    return make_mesh((n_pools,), (POOL_AXIS,))


@functools.lru_cache(maxsize=32)
def _sar_gang_fn(hcfg, policy: TriagePolicy, adaptive_mode: bool,
                 r_step: int, fused: bool, mesh,
                 tcfg: TelemetryConfig | None = None):
    """jit (pool, stats, base, active[, telem]) -> per-pool results.

    Every argument is one global array sharded over the ``("pool",)``
    mesh, and device ``p``'s shard IS pool ``p``'s state: ``pool``,
    ``stats``, ``base`` and ``active`` are the pools' slot-major arrays
    laid end to end ([P·S, ...]), ``telem`` stacks the pools' telemetry
    on a leading [P] axis.  Each shard runs the engine's un-jitted
    ``_build_multi_round`` body on its own pool, so no pool's data
    leaves its device.  Returns (stats [P·S, ...], verdicts [P·S],
    fins [P·S, ...], rounds [P][, telem [P, ...]]) with the same
    layout — ``rounds`` carries each pool's OWN while_loop trip count.

    Cached on the same frozen configs as ``_sar_round_fn`` plus the
    (hashable) mesh, so every fleet over the same mesh shares one
    executable per shape."""
    prof.count_build("sar_gang")
    core = _build_multi_round(
        hcfg=hcfg, policy=policy, adaptive_mode=adaptive_mode,
        r_step=r_step, fused=fused, constrain=lambda t: t, tcfg=tcfg,
        shard=None)
    spec = jax.sharding.PartitionSpec(POOL_AXIS)

    if tcfg is None:
        def local(pool, stats, base, active):
            s, v, f, k = core(pool, stats, base, active)
            return s, v, f, k[None]
        n_args = 4
    else:
        def local(pool, stats, base, active, telem):
            s, v, f, k, t = core(pool, stats, base, active,
                                 jax.tree.map(lambda x: x[0], telem))
            return s, v, f, k[None], jax.tree.map(lambda x: x[None], t)
        n_args = 5
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec,) * n_args,
                                 out_specs=spec, check_vma=False))


class SarServingFleet:
    """Data-parallel fleet of SAR serving pools behind one router.

    ``n_pools`` complete ``SarServingEngine``s (each ``slots_per_pool``
    slots), one per device of a 1-D ``("pool",)`` mesh.  ``gang=None``
    auto-enables the single-dispatch gang round when the process has at
    least ``n_pools`` devices and ``n_pools > 1``; ``gang=False`` (or
    too few devices) falls back to one dispatch per pool per tick —
    identical verdicts, more host syncs.

    Routing is *consistent least-loaded*: each backlog request goes to
    the pool with the smallest (in-flight + queued) load, ties broken
    by lowest pool id, so a given submission sequence always routes the
    same way.  Per-pool admission queues are bounded by ``queue_cap``
    (default: ``slots_per_pool``): a pool with zero free slots and a
    full queue is skipped — it *backpressures* instead of receiving
    blind round-robin traffic — and when every pool is saturated the
    remainder stays in the fleet backlog until a retirement frees
    capacity (``backlog_peak`` in the summary tracks the depth).

    With the gang, pool ``p`` lives on mesh device ``p``: its engine's
    parameters, slot pool, statistics and the pool's telemetry stay on
    that device across ticks, and the gang dispatch reads them in place.

    ``head``/``hcfg``/``chip`` bind every pool to the same (possibly
    degraded) die, as in the single-pool engine."""

    def __init__(self, params, cfg, *, n_pools: int = 2,
                 slots_per_pool: int = 32,
                 policy: TriagePolicy = TriagePolicy(),
                 adaptive_mode: bool = True,
                 head: dict | None = None, hcfg=None, chip=None,
                 fused: bool = True,
                 telemetry: bool | TelemetryConfig = True,
                 layers=None, tile_program=None,
                 queue_cap: int | None = None,
                 gang: bool | None = None,
                 profiler: bool | StageProfiler = True,
                 tracer=None,
                 slo=True):
        if n_pools < 1:
            raise ValueError("n_pools must be >= 1")
        self.n_pools = n_pools
        self.slots_per_pool = slots_per_pool
        self.policy = policy
        self.queue_cap = slots_per_pool if queue_cap is None else queue_cap
        if self.queue_cap < 1:
            raise ValueError("queue_cap must be >= 1")
        if profiler is True:
            profiler = StageProfiler()
        self.profiler: StageProfiler = profiler or NULL_PROFILER
        # One tracer stitches the whole fleet into a single timeline:
        # pid 0 = router (fleet_tick spans + request flow starts),
        # pid p+1 = pool p (its engine loop, gang-dispatch track, and
        # slot tracks).  One shared SloTracker receives every pool's
        # retirements plus the fleet-level router/queue/backpressure
        # samples — both are pure host bookkeeping (tests/test_slo.py).
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if slo is True:
            slo = SloTracker()
        self.slo: SloTracker = slo or NULL_SLO
        if self.tracer.enabled:
            self.tracer.name_process(0, "router")
            self.tracer.name_thread(0, "fleet ticks", pid=0)
            for p in range(n_pools):
                self.tracer.name_process(p + 1, f"pool {p}")
                self.tracer.name_thread(0, "pool loop", pid=p + 1)
        if gang is None:
            gang = n_pools > 1 and len(jax.devices()) >= n_pools
        if gang and len(jax.devices()) < n_pools:
            raise ValueError(
                f"gang dispatch needs >= {n_pools} devices, have "
                f"{len(jax.devices())}")
        self.mesh = make_pool_mesh(n_pools) if gang else None
        devices = (list(self.mesh.devices.flat) if gang
                   else [None] * n_pools)
        self.engines = [
            SarServingEngine(
                params, cfg, n_slots=slots_per_pool, policy=policy,
                adaptive_mode=adaptive_mode,
                metrics=ServingMetrics(layers=layers,
                                       extra={"pool": p},
                                       tile_program=tile_program),
                head=head, hcfg=hcfg, chip=chip, fused=fused,
                telemetry=telemetry, profiler=profiler,
                tracer=self.tracer, slo=self.slo, trace_pid=p + 1,
                device=devices[p])
            for p in range(n_pools)]
        e0 = self.engines[0]
        self.tcfg = e0.tcfg
        self._gang = None
        self._telem = None
        if gang:
            self._sharding = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec(POOL_AXIS))
            self._gang = _sar_gang_fn(
                e0.hcfg, policy, adaptive_mode, e0.r_step, fused,
                self.mesh, self.tcfg)
            if self.tcfg is not None:
                # the pools' telemetry, stacked on a leading pool axis
                # (row p on device p); handed to the engines at drain
                self._telem = jax.device_put(
                    jax.tree.map(lambda *xs: np.stack(xs),
                                 *jax.device_get([e._telem
                                                  for e in self.engines])),
                    self._sharding)
        self.backlog: deque[Request] = deque()
        self.routes: dict[int, int] = {}          # rid -> pool id
        self.host_syncs = 0
        self.backlog_peak = 0
        self.wall_s = float("nan")
        # per-tick record for the mesh-latency model (see summary()):
        # {"wall_s", "trips": [P]} — trips is each pool's OWN while_loop
        # trip count this tick (0 = idle pool), the quantity that sets a
        # real mesh's per-tick critical path (slowest pool).
        self.tick_log: list[dict] = []

    # -- admission router ----------------------------------------------
    def submit(self, request: Request) -> None:
        if request.arrival_s == 0.0:
            request.arrival_s = time.time()
        if request.arrival_pc == 0.0:
            request.arrival_pc = time.perf_counter()
        self.backlog.append(request)
        self.backlog_peak = max(self.backlog_peak, len(self.backlog))

    def _pick_pool(self) -> int | None:
        """Least-loaded pool with queue headroom; None = all saturated."""
        best, best_load = None, None
        for p, eng in enumerate(self.engines):
            if len(eng.queue) >= self.queue_cap:
                continue                          # saturated: backpressure
            load = eng.n_active + len(eng.queue)
            if best_load is None or load < best_load:
                best, best_load = p, load
        return best

    def _route(self) -> None:
        had_work = bool(self.backlog)
        t0 = time.perf_counter()
        while self.backlog:
            p = self._pick_pool()
            if p is None:
                break                # every pool saturated — hold here
            req = self.backlog.popleft()
            self.routes[req.rid] = p
            self.engines[p].queue.append(req)
            if self.tracer.enabled:
                # open this request's flow on the router track; the
                # owning pool's slot span closes it at retirement
                self.tracer.flow_start(f"req {req.rid}", req.rid,
                                       tid=0, pid=0)
        if had_work:
            self.slo.observe_router(time.perf_counter() - t0)
        if self.backlog:
            # every pool's bounded queue is full: this tick backpressures
            self.slo.backpressure(len(self.backlog))
            if self.tracer.enabled:
                self.tracer.instant("backpressure", tid=0, pid=0,
                                    backlog=len(self.backlog))

    @property
    def pending(self) -> int:
        return len(self.backlog) + sum(len(e.queue) for e in self.engines)

    @property
    def n_active(self) -> int:
        return sum(e.n_active for e in self.engines)

    # -- dispatch -------------------------------------------------------
    def _global(self, trees):
        """The pools' per-device arrays laid end to end as one global
        array per leaf, sharded over the pool mesh — no copy, no
        dispatch: device p's shard is pool p's own buffer."""
        return jax.tree.map(
            lambda *xs: jax.make_array_from_single_device_arrays(
                (sum(x.shape[0] for x in xs),) + xs[0].shape[1:],
                self._sharding, list(xs)),
            *trees)

    @staticmethod
    def _local(tree, device):
        """``device``'s shard of every global leaf in ``tree`` (no copy)."""
        return jax.tree.map(
            lambda x: next(s.data for s in x.addressable_shards
                           if s.device == device), tree)

    def _dispatch_gang(self, actives: list[np.ndarray]) -> list[int]:
        """One shard_map'd round for ALL pools; one host sync."""
        template = next((e.pool for e in self.engines
                         if e.pool is not None), None)
        for eng in self.engines:
            eng.ensure_pool(like=template)
        pool = self._global([e.pool for e in self.engines])
        stats = self._global([e.stats for e in self.engines])
        base = jax.device_put(np.concatenate([e.base for e in self.engines]),
                              self._sharding)
        active = jax.device_put(np.concatenate(actives), self._sharding)
        with self.profiler.span("dispatch"):
            if self.tcfg is None:
                stats_out, verdicts, fins, rounds = self._gang(
                    pool, stats, base, active)
            else:
                stats_out, verdicts, fins, rounds, self._telem = \
                    self._gang(pool, stats, base, active, self._telem)
        # ONE blocking pull for the whole fleet: every pool's verdicts,
        # finalized stats and trip counts arrive in a single sync.
        shape = (self.n_pools, self.slots_per_pool)
        verdicts, fins, rounds = pull_round(self.profiler, verdicts, fins,
                                            rounds)
        verdicts = verdicts.reshape(shape)
        fins = {k: v.reshape(shape + v.shape[1:]) for k, v in fins.items()}
        self.host_syncs += 1
        t_verdict = time.perf_counter()
        with self.profiler.span("retirement"):
            for p, eng in enumerate(self.engines):
                eng.stats = self._local(stats_out, eng.device)
                if actives[p].any():
                    fin_p = {k: v[p] for k, v in fins.items()}
                    spent = eng.r_step * int(rounds[p])
                    eng._retire_decided(actives[p], verdicts[p], fin_p,
                                        spent, verdict_s=t_verdict)
        return [int(r) for r in rounds]

    def _dispatch_sequential(self, actives: list[np.ndarray]) -> list[int]:
        """Fallback: one engine dispatch per active pool per tick."""
        trips = [0] * self.n_pools
        for p, (eng, active) in enumerate(zip(self.engines, actives)):
            if not active.any():
                continue
            with self.profiler.span("dispatch"):
                if eng.tcfg is None:
                    eng.stats, verdict, fin, rounds = eng._round(
                        eng.pool, eng.stats, jnp.asarray(eng.base),
                        jnp.asarray(active))
                else:
                    (eng.stats, verdict, fin, rounds,
                     eng._telem) = eng._round(
                        eng.pool, eng.stats, jnp.asarray(eng.base),
                        jnp.asarray(active), eng._telem)
            verdict, fin, rounds = pull_round(self.profiler, verdict, fin,
                                              rounds)
            spent = eng.r_step * int(rounds)
            self.host_syncs += 1
            eng.host_syncs += 1
            trips[p] = int(rounds)
            t_verdict = time.perf_counter()
            with self.profiler.span("retirement"):
                eng._retire_decided(active, verdict, fin, spent,
                                    verdict_s=t_verdict)
        return trips

    # -- main loop ------------------------------------------------------
    def start(self) -> None:
        """Reset per-pool stream bases.  ``run`` calls this; open-loop
        drivers (serving/load.py) call it once, then interleave
        ``submit`` with ``tick`` on their own clock."""
        for eng in self.engines:
            eng.start()

    def tick(self) -> bool:
        """One fleet tick: route the backlog, admit per pool, one gang
        (or sequential) dispatch, retire.  Returns False when no pool
        had active work (idle tick)."""
        t_tick = time.perf_counter()
        t_tr = self.tracer.now()
        with self.profiler.span("route"):
            self._route()
        for eng in self.engines:
            eng._admit()
        self.slo.sample_queues(
            [len(e.queue) for e in self.engines],
            [e.n_active for e in self.engines], len(self.backlog))
        actives = [eng.active_mask() for eng in self.engines]
        if not any(a.any() for a in actives):
            return False
        for eng, active in zip(self.engines, actives):
            eng._stamp_first_dispatch(active)
        t_disp = self.tracer.now()
        if self._gang is not None:
            trips = self._dispatch_gang(actives)
        else:
            trips = self._dispatch_sequential(actives)
        self.tick_log.append(
            {"wall_s": time.perf_counter() - t_tick, "trips": trips})
        if self.tracer.enabled:
            now = self.tracer.now()
            tick_no = len(self.tick_log) - 1
            # per-pool gang-dispatch tracks: one span per pool per tick
            # carrying that pool's OWN while_loop trip count
            for p in range(self.n_pools):
                if actives[p].any():
                    self.tracer.complete(
                        "gang_dispatch", t_disp, now - t_disp,
                        tid=0, pid=p + 1, tick=tick_no, trips=trips[p],
                        n_active=int(actives[p].sum()))
            self.tracer.complete(
                "fleet_tick", t_tr, now - t_tr, tid=0, pid=0,
                tick=tick_no, backlog=len(self.backlog),
                n_active=sum(int(a.sum()) for a in actives),
                max_trips=max(trips))
        return True

    def drain(self) -> dict:
        """Attach per-pool telemetry/perf and build the fleet summary
        (the shared SLO snapshot lands on the fleet summary only)."""
        if self._telem is not None:
            for p, eng in enumerate(self.engines):
                eng._telem = jax.tree.map(
                    lambda x: x[0], self._local(self._telem, eng.device))
        for eng in self.engines:
            if eng.tcfg is not None:
                eng.metrics.attach_telemetry(eng.telemetry_snapshot())
            eng._attach_perf()
        return self.summary()

    def run(self, max_ticks: int = 100_000) -> dict:
        t0 = time.perf_counter()
        self.start()
        for _ in range(max_ticks):
            if not self.tick():
                if not self.backlog and not any(
                        e.queue for e in self.engines):
                    break
        self.wall_s = time.perf_counter() - t0
        return self.drain()

    # -- aggregation ----------------------------------------------------
    def summary(self) -> dict:
        """Fleet report: exact sums of the per-pool reports.

        ``energy_total_J`` is Σ over pools of Σ per-request
        ``request_energy`` (each pool's ``energy_total_J`` is already
        that sum, so the fleet total reconciles to the per-record sum —
        tests/test_fleet.py asserts it).  ``telemetry`` merges the
        per-pool device snapshots with ``merge_snapshots``; each
        request's counters live in exactly one pool's snapshot, so the
        merge never double-counts."""
        pool_summaries = [e.metrics.summary() for e in self.engines]
        decisions = sum(s["decisions"] for s in pool_summaries)
        requests = sum(s["requests"] for s in pool_summaries)
        wall = self.wall_s
        out = {
            "n_pools": self.n_pools,
            "slots_per_pool": self.slots_per_pool,
            "gang": self._gang is not None,
            "requests": requests,
            "decisions": decisions,
            "wall_s": wall,
            "decisions_per_s": (decisions / wall
                                if wall and wall > 0 else float("nan")),
            "host_syncs": self.host_syncs,
            "host_syncs_per_decision": (self.host_syncs / decisions
                                        if decisions else float("nan")),
            "backlog_peak": self.backlog_peak,
            "routed_per_pool": [
                sum(1 for p in self.routes.values() if p == q)
                for q in range(self.n_pools)],
            "ticks": len(self.tick_log),
            # raw per-tick record (one gang dispatch each): feeds the
            # mesh-latency model in benchmarks/fleet_bench.py, where a
            # real P-device mesh's tick critical path is its slowest
            # pool's trip count
            "tick_log": [dict(t) for t in self.tick_log],
        }
        if decisions:
            out["mean_samples_per_decision"] = sum(
                s["mean_samples_per_decision"] * s["decisions"]
                for s in pool_summaries if s["decisions"]) / decisions
            for frac in ("accept_fraction", "flag_fraction"):
                if requests and all(frac in s for s in pool_summaries):
                    out[frac] = sum(
                        s[frac] * s["requests"]
                        for s in pool_summaries if s["requests"]
                    ) / requests
        if all("energy_total_J" in s for s in pool_summaries):
            out["energy_total_J"] = float(sum(
                s["energy_total_J"] for s in pool_summaries
                if s["requests"]))
        snaps = [s.get("telemetry") for s in pool_summaries]
        snaps = [s for s in snaps if s is not None]
        if snaps:
            out["telemetry"] = merge_snapshots(snaps)
        out["pools"] = [
            {k: s.get(k) for k in
             ("pool", "requests", "decisions", "decisions_per_s",
              "mean_samples_per_decision", "energy_total_J",
              "accept_fraction", "flag_fraction")}
            for s in pool_summaries]
        snap = self.profiler.snapshot()
        if snap:
            out["stage_profile"] = snap
        slo_snap = self.slo.snapshot()
        if slo_snap:
            out["slo"] = slo_snap
            out["backpressure_ticks"] = self.slo.backpressure_ticks
        return out
