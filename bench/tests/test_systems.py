"""Self-checks of the seam between the harness and a system module, on
the CPU with a stub system defined here.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The stub retires each request on the tick after its admission and
answers with the request's own payload, drawn from the seed; its
``compare`` checks every answer against that payload.  It is found the
way a real system is: by the configuration's ``"system"`` key, in the
harness's systems directory (here pointed at a test directory).
"""

from __future__ import annotations

import json
import re
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run as bench_run  # noqa: E402

STUB = textwrap.dedent('''
    """A stub system: each request is retired on the tick after its
    admission, answered with its own payload."""

    import time
    from types import SimpleNamespace

    import jax

    CONTROLS = ("echo_off_by_one",)


    class _Profiler:
        def snapshot(self):
            return {}


    class Engine:
        def __init__(self, cfg, off_by):
            self.slots, self.off_by = cfg["slots"], off_by
            self.alter_rid = cfg.get("alter_rid")
            self.decided = cfg.get("decisions_per_record", 1)
            self.queue, self.active = [], []
            self.metrics = SimpleNamespace(records=[])

        def tick(self):
            now = time.perf_counter()
            for req, admit_s in self.active:
                answer = req.payload + self.off_by + (
                    1 if req.rid == self.alter_rid else 0)
                self.metrics.records.append(SimpleNamespace(
                    rid=req.rid, admit_s=admit_s, verdict_s=now,
                    n_samples=1, answer=answer, decided=self.decided))
            self.active = [(r, now) for r in self.queue[:self.slots]]
            del self.queue[:self.slots]


    class System:
        def __init__(self, cfg, off_by):
            self.slots = cfg["slots"]
            self.engine = Engine(cfg, off_by)
            self.engines = [self.engine]
            self.profiler = _Profiler()
            self.r_step = 1
            self.tick = self.engine.tick

        def submit(self, req):
            self.engine.queue.append(req)

        @property
        def pending(self):
            return len(self.engine.queue)

        @property
        def n_active(self):
            return len(self.engine.active)

        def devices(self):
            return jax.devices()[:1]


    class Feed:
        def __init__(self, seed):
            self.seed = seed
            self.next_rid = 0

        def payload_of(self, rid):
            return (self.seed * 1_000_003 + rid * 7_919) % 2**31

        def make(self):
            rid = self.next_rid
            self.next_rid += 1
            return SimpleNamespace(rid=rid, payload=self.payload_of(rid))


    def build(cell, seed, control, phases):
        t = time.perf_counter()
        system = System(cell.cfg, 1 if control == "echo_off_by_one" else 0)
        feed = Feed(seed)
        phases["build"] = time.perf_counter() - t
        return system, feed, None


    def decisions(record):
        return record.decided


    def compare(cell, ctx, served, feed, due, seed, *, submitted, control,
                log):
        recs = {r.rid: r for e in served.engines for r in e.metrics.records}
        wrong = sum(1 for rid, r in recs.items()
                    if r.answer != feed.payload_of(rid))
        missing = sum(1 for rid in range(submitted) if rid not in recs)
        log(f"stub checked={len(recs)} wrong={wrong}")
        return [("wrong", wrong, 0, "max"), ("missing", missing, 0, "max"),
                ("checked", len(recs), 1, "min")]
''')

SLOTS = 4


def _spec(cfg_file: Path) -> dict:
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = [m for m in real["end_to_end"]
           if m["name"] in ("decisions_per_s", "setup_s")]
    return {"configs": [{"name": "stub_cfg", "file": str(cfg_file)}],
            "workloads": [{"name": "stub_backlog", "config": "stub_cfg",
                           "traffic": "backlog", "chips": 1}],
            "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                           for m in e2e],
            "per_layer": []}


@pytest.fixture
def stub_dir(tmp_path, monkeypatch):
    systems = tmp_path / "systems"
    systems.mkdir()
    (systems / "stub.py").write_text(STUB)
    monkeypatch.setattr(bench_run, "SYSTEMS", systems)
    return tmp_path


def _cell(stub_dir: Path, **cfg):
    cfg_file = stub_dir / "stub_cfg.json"
    cfg_file.write_text(json.dumps({"system": "stub", "slots": SLOTS, **cfg}))
    return bench_run.load_cell("stub_backlog", _spec(cfg_file))


def _run(cell, seed=2**31 + 101, **kw):
    return bench_run.run_cell(cell, seed, 0.3, False, require_tpu=False,
                              log=lambda *a, **k: None, **kw)


@pytest.fixture
def seen_runs(monkeypatch):
    """The ``run`` objects the metric readers were handed."""
    runs = []
    real = bench_run.reader

    def reader(metric):
        read = real(metric)

        def spy(run):
            runs.append(run)
            return read(run)
        return spy

    monkeypatch.setattr(bench_run, "reader", reader)
    return runs


def _retired_in_window(run) -> int:
    return sum(1 for r in run.records.values()
               if run.t0 <= r.verdict_s <= run.t1)


def test_stub_runs_end_to_end_and_is_correct(stub_dir, seen_runs):
    cell = _cell(stub_dir)
    assert cell.system.__file__ == str(stub_dir / "systems" / "stub.py")
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["metrics"]["decisions_per_s"]["value"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["wrong"]["value"] == 0
    run = seen_runs[0]
    assert run.decisions == _retired_in_window(run) > 0


def test_stub_that_alters_one_answer_is_not_correct(stub_dir):
    res = _run(_cell(stub_dir, alter_rid=3))
    assert not res["correct"], res["checks"]
    assert res["checks"]["wrong"]["value"] == 1


def test_stub_control_is_not_correct(stub_dir):
    res = _run(_cell(stub_dir), control="echo_off_by_one")
    assert not res["correct"], res["checks"]


def test_control_not_named_by_the_system_is_refused(stub_dir):
    with pytest.raises(bench_run.BenchError, match="reference_bf16"):
        _run(_cell(stub_dir), control="reference_bf16")


def test_decisions_follow_the_system_module(stub_dir, seen_runs):
    res = _run(_cell(stub_dir, decisions_per_record=3))
    assert res["correct"], res["checks"]
    run = seen_runs[0]
    assert run.decisions == 3 * _retired_in_window(run) > 0


def test_configuration_without_a_system_is_refused(tmp_path):
    cfg_file = tmp_path / "nosys.json"
    cfg_file.write_text(json.dumps({"slots": SLOTS}))
    with pytest.raises(bench_run.BenchError, match=re.escape(str(cfg_file))):
        bench_run.load_cell("stub_backlog", _spec(cfg_file))


def test_configuration_naming_a_missing_system_is_refused(stub_dir):
    with pytest.raises(bench_run.BenchError, match="stub_cfg.json"):
        _cell(stub_dir, system="no_such_system")


def test_sar_configuration_names_its_module():
    cell = bench_run.load_cell("sar_ideal_backlog")
    assert cell.system.__name__ == "bench.systems.sar"
    assert Path(cell.system.__file__) == (
        ROOT / "bench" / "systems" / "sar.py")
    assert set(cell.system.CONTROLS) == {"unfused", "reference_bf16"}
