"""Fleet-scale chaos in the simulator (docs/simulation.md "Chaos at
simulator scale").

Units cover the durability model (the virtual journal's
admit/prog/fin fold, restart-resume folding progress exactly like
Scheduler.resume_from_journal, the seeded drop-resume defect), the
per-engine fault surface (slow/stuck), the declarative FaultSchedule
(JSON round trip, seed determinism, uncataloged-point and
unknown-action refusal), and the scoped spawn/cold-start pricing the
satellites added to SimPool and the cost table.

Integration covers the chaos scenario end to end: the tier-1
fixed-seed smoke (two same-seed runs byte-identical INCLUDING the
fault log and invariant verdict), transport faults charging the real
failover path, and the shrinker acceptance — a seeded durability bug
is caught by the fleet-wide invariants, minimized to a handful of
schedule events, and its replay bundle reproduces the violation in
one command.

The gossip/breaker property tests are the duplicate-delivery
contract, driven with observation sequences from seeded sim
partition runs: LWW merge converges under any delivery order with
duplicates, and the probe-token idempotency gate never charges one
probe verdict twice even when it arrives both locally and via gossip
replay.

`slow` holds the scale acceptance (>=500 engines, >=50 kill/restart
events, byte-identical, under the wall budget) and the
down-conversion fidelity spot-check (a sim-explored schedule replayed
as a subprocess chaos episode passing the same invariants).
"""

import json
import pathlib
import random
import subprocess
import sys
import time

import pytest

from ome_tpu.router import gossip
from ome_tpu.router.server import Backend
from ome_tpu.sim import faultplan
from ome_tpu.sim import scenario as scen
from ome_tpu.sim.clock import EventLoop
from ome_tpu.sim.costmodel import CostModel
from ome_tpu.sim.durability import JournalSet, SimJournal
from ome_tpu.sim.engine import SimEngine, SimRequest
from ome_tpu.sim.fleet import SimFleet

REPO = pathlib.Path(__file__).resolve().parents[1]
SIMULATE = REPO / "scripts" / "simulate.py"
CHAOS_SOAK = REPO / "scripts" / "chaos_soak.py"
PERFGATE = REPO / "scripts" / "perfgate.py"


def _cost(**kw):
    return CostModel(weights_ms=4.0, attn_ms=1.0, dispatch_ms=2.0,
                     prefill_ms_per_token=0.05, **kw)


def _engine(loop, **kw):
    return SimEngine("e0", loop.clock, loop, _cost(), **kw)


# -- the durability model ----------------------------------------------


class TestSimJournal:
    def test_admit_prog_fin_fold(self):
        """live_entries is chaos.journal_live_entries virtualized:
        admits minus fins, progress accumulated onto the live
        entry."""
        j = SimJournal("e0")
        a = j.admit(SimRequest(16, 8, trace_id="a"), incarnation=1)
        b = j.admit(SimRequest(8, 4, trace_id="b"), incarnation=1)
        j.progress(a, 1, 3)
        j.progress(a, 1, 2)
        j.finish(b, 1, "stop")
        live = j.live_entries()
        assert set(live) == {a}
        assert live[a]["produced"] == 5
        assert live[a]["trace_id"] == "a"
        j.finish(a, 2, "stop")  # tombstoned by a LATER incarnation
        assert j.live_entries() == {}

    def test_resume_folds_progress_like_scheduler(self):
        """The restart side of the WAL: produced tokens join the
        prompt for recompute, the original budget stands, and an
        entry whose whole budget was produced finishes `length` —
        only its tombstone was lost to the crash."""
        loop = EventLoop()
        j = SimJournal("e0")
        done = []
        eng = _engine(loop, max_slots=1, journal=j,
                      on_finish=done.append)
        eng.submit(SimRequest(16, 64, trace_id="victim"))
        loop.run_until(0.3)  # mid-decode
        eng.kill()
        (killed,) = done
        assert killed.status == 599
        (entry,) = j.live_entries().values()
        assert entry["produced"] == killed.output_tokens > 0

        eng2 = SimEngine("e0", loop.clock, loop, _cost(),
                         max_slots=1, journal=j, incarnation=2,
                         on_finish=done.append)
        assert eng2.resume_from_journal() == 1
        loop.run()
        resumed = done[-1]
        assert resumed.trace_id == "victim"
        assert resumed.finish_reason == "stop"
        # recompute resume: prior progress joined the prompt, the
        # budget did not restart from zero
        assert resumed.prompt_tokens == 16 + entry["produced"]
        assert resumed.output_tokens == 64
        assert j.live_entries() == {}

    def test_fully_produced_entry_finishes_length_on_resume(self):
        j = SimJournal("e0")
        jid = j.admit(SimRequest(8, 4), incarnation=1)
        j.progress(jid, 1, 4)  # whole budget produced, fin lost
        loop = EventLoop()
        eng = _engine(loop, journal=j, incarnation=2)
        assert eng.resume_from_journal() == 0
        assert j.live_entries() == {}
        assert j.records[-1]["reason"] == "length"

    def test_drop_resume_bug_fires_once(self):
        """The seeded-defect knob: the first non-empty resume
        silently loses N entries, later resumes are honest — a
        one-off replay defect, which is what the invariants must
        catch."""
        js = JournalSet()
        j = js.get("e0")
        j.admit(SimRequest(8, 4, trace_id="a"), incarnation=1)
        j.admit(SimRequest(8, 4, trace_id="b"), incarnation=1)
        js.arm_drop_resume("e0")
        first = j.resume_entries()
        assert [e["trace_id"] for e in first] == ["b"]
        again = j.resume_entries()  # disarmed after firing
        assert [e["trace_id"] for e in again] == ["a", "b"]
        assert js.live_by_engine() == {"e0": j.live_entries()}


# -- per-engine fault surface ------------------------------------------


class TestEngineFaults:
    def test_slow_inflates_service_time(self):
        def finish_time(factor):
            loop = EventLoop()
            done = []
            eng = _engine(loop, on_finish=done.append)
            eng.set_slow(factor)
            eng.submit(SimRequest(16, 32))
            loop.run()
            return done[0].finished_at

        assert finish_time(3.0) > 2.0 * finish_time(1.0)

    def test_stuck_stalls_decode_but_keeps_admitting(self):
        loop = EventLoop()
        done = []
        eng = _engine(loop, on_finish=done.append)
        eng.set_stuck(True)
        assert eng.submit(SimRequest(16, 8)) == 200  # still admits
        loop.run_until(30.0)
        assert done == []  # wedged: no progress
        assert eng.metrics_text()  # scrape surface still serves
        eng.set_stuck(False)  # heal reschedules the chunk loop
        loop.run()
        assert done and done[0].finish_reason == "stop"


# -- the declarative fault schedule ------------------------------------


class TestFaultSchedule:
    def test_json_round_trip(self, tmp_path):
        s = faultplan.generate(7, engines=10, requests=100, kills=3)
        path = tmp_path / "sched.json"
        s.save(path)
        loaded = faultplan.FaultSchedule.load(path)
        assert loaded == s
        assert loaded.to_dict() == s.to_dict()
        assert str(path) in s.replay_command(path)

    def test_generation_is_seed_deterministic(self):
        a = faultplan.generate(5, engines=20, requests=200, kills=4)
        b = faultplan.generate(5, engines=20, requests=200, kills=4)
        c = faultplan.generate(6, engines=20, requests=200, kills=4)
        assert a.to_dict() == b.to_dict()
        assert c.to_dict() != a.to_dict()
        # events arrive sorted and every kill has a later restart
        ats = [e.at for e in a.events]
        assert ats == sorted(ats)
        kills = {e.target: e.at for e in a.events
                 if e.action == "kill"}
        restarts = {e.target: e.at for e in a.events
                    if e.action == "restart"}
        assert set(kills) <= set(restarts)
        assert all(restarts[t] > kills[t] for t in kills)

    def test_wrong_schema_version_rejected(self):
        doc = faultplan.generate(1).to_dict()
        doc["schema_version"] = faultplan.SCHEMA_VERSION + 1
        with pytest.raises(ValueError, match="schema_version"):
            faultplan.FaultSchedule.from_dict(doc)

    def test_uncataloged_fault_point_refused(self):
        """The chaos.py:preflight discipline: a schedule naming a
        fault point outside the failure-semantics catalog is refused
        before anything runs."""
        from ome_tpu.chaos import ChaosError
        s = faultplan.generate(
            1, fault_spec="made_up_point.raise@1:2")
        with pytest.raises(ChaosError, match="made_up_point"):
            faultplan.preflight(s)

    def test_unknown_event_action_refused(self):
        s = faultplan.generate(1)
        s.events[0].action = "meteor"
        with pytest.raises(ValueError, match="meteor"):
            faultplan.preflight(s)

    def test_down_convert_maps_kills_onto_serving_engines(self):
        s = faultplan.generate(3, engines=50, requests=400, kills=2,
                               slow=0, partitions=0, fault_spec="")
        events = faultplan.to_chaos_events(
            s, ["unified0", "unified1"], spread=6.0)
        assert len(events) == 2  # only kills down-convert
        for at, action, target in events:
            assert action == "sigkill"
            assert target in ("unified0", "unified1")
            assert 0.0 < at < 6.0


# -- satellite: scoped spawn override + cold-start pricing -------------


class TestSpawnAndWarmup:
    def test_add_engines_does_not_mutate_pool_spawn_delay(self):
        """The scoped form of the old save/restore: pre-provisioning
        with delay=0 must leave the pool's configured cold-start
        pricing untouched for later controller-driven spawns."""
        fleet = SimFleet(_cost(warmup_ms=500.0), spawn_delay=2.0)
        fleet.add_engines(3)
        assert fleet.pool.spawn_delay == 2.0
        assert fleet.pool.warmup_delay == 0.5
        assert len(fleet.pool.member_urls()) == 3  # ready at t=0

    def test_cold_start_prices_spawn_plus_warmup(self):
        fleet = SimFleet(_cost(warmup_ms=500.0), spawn_delay=2.0)
        member = fleet.pool.spawn()  # a controller-style scale-up
        fleet.run_until(2.4)
        assert not member.ready  # still compiling
        fleet.run_until(2.6)
        assert member.ready

    def test_warmup_ms_emitter_loader_round_trip(self):
        """Satellite contract: bench.py measures first-request wall
        time as warmup_ms, scripts/perfgate.py's cost-table emitter
        carries it, and CostModel round-trips it."""
        import importlib.util as _ilu
        spec = _ilu.spec_from_file_location("perfgate", PERFGATE)
        perfgate = _ilu.module_from_spec(spec)
        spec.loader.exec_module(perfgate)
        parsed = json.loads(
            (REPO / "tests" / "data" / "bench_history"
             / "BENCH_r05.json").read_text())["parsed"]
        parsed = dict(parsed, warmup_ms=1234.5)
        table = perfgate.cost_table(parsed, "BENCH_r05.json")
        assert table["warmup_ms"] == 1234.5
        cm = CostModel.from_cost_table(table)
        assert cm.warmup_ms == 1234.5
        assert cm.to_dict()["warmup_ms"] == 1234.5
        # absent field stays a zero-cost default (older tables)
        table.pop("warmup_ms")
        assert CostModel.from_cost_table(table).warmup_ms == 0.0


# -- satellite: the admission ladder -----------------------------------


class TestAdmissionShedLadder:
    def _warm(self, eng, loop, n=2):
        for _ in range(n):
            assert eng.submit(SimRequest(8, 16)) == 200
        loop.run()

    def test_deep_saturation_sheds_429_with_retry_after(self):
        loop = EventLoop()
        eng = _engine(loop, max_slots=1, max_queue_wait=0.5)
        self._warm(eng, loop)  # EWMAs have samples now
        statuses = [eng.submit(SimRequest(8, 64))
                    for _ in range(20)]
        assert statuses[0] == 200  # shallow queue still admits
        assert 429 in statuses  # estimated wait crossed the cap
        # the shed happened BEFORE the queue bound: the ladder, not
        # the queue-full path
        assert eng.pending.qsize() < 19
        hint = eng.retry_after_hint()
        assert 1 <= hint <= 30
        assert eng.stats["rejected_total"] == statuses.count(429)

    def test_cold_start_admits_optimistically(self):
        loop = EventLoop()
        eng = _engine(loop, max_slots=1, max_queue_wait=0.05,
                      max_pending=64)
        statuses = [eng.submit(SimRequest(8, 64))
                    for _ in range(20)]
        assert statuses == [200] * 20  # no EWMAs yet: no estimate
        assert eng.retry_after_hint(default=3.0) == 3

    def test_disabled_ladder_never_sheds(self):
        loop = EventLoop()
        eng = _engine(loop, max_slots=1, max_queue_wait=None,
                      max_pending=512)
        self._warm(eng, loop)
        statuses = [eng.submit(SimRequest(8, 64))
                    for _ in range(100)]
        assert 429 not in statuses


# -- the chaos scenario (tier-1) ---------------------------------------


class TestChaosScenario:
    def test_fixed_seed_smoke_byte_identical(self):
        """The satellite-6 smoke: two same-seed chaos runs —
        schedule generation, fault application, restarts, resume,
        invariant verdict — are byte-identical."""
        a = scen.run_chaos(seed=7, engines=8, requests=120, kills=2)
        b = scen.run_chaos(seed=7, engines=8, requests=120, kills=2)
        assert scen.canonical_json(a) == scen.canonical_json(b)
        assert a["violations"] == []
        assert a["fault_log"]  # faults really applied
        kinds = {e["action"] for e in a["fault_log"]}
        assert "kill" in kinds and "restart" in kinds
        assert a["sim"]["engines_spawned"] == 8

    def test_transport_fault_charges_failover_path(self):
        """A cataloged transport fault (submit raises: refused
        connection) must ride the REAL retry-budget failover, not a
        sim-only shortcut — and still satisfy the invariants."""
        s = faultplan.generate(
            2, engines=4, requests=120, kills=0, slow=0,
            partitions=0,
            fault_spec="sim_transport_submit.raise@2:3")
        rep = scen.run_chaos(schedule=s)
        assert rep["violations"] == []
        assert rep["failovers"] >= 1
        # the spec fires 3 times; a request whose retries all land on
        # the faulted point may legitimately end with an error OUTCOME
        # (never a lost request — the invariants above prove that)
        assert rep["completed"] >= rep["requests"] - 3

    def test_seeded_violation_caught_shrunk_and_bundled(
            self, tmp_path):
        """The shrinker acceptance: an intentionally-seeded
        drop-resume defect is caught by the journal-reconciliation
        invariant, minimized to <=5 schedule events, and the replay
        bundle reproduces it."""
        bug = {"kind": "drop_resume", "target": "*", "n": 1}
        rep = scen.run_chaos(seed=0, engines=6, requests=800,
                             kills=8, inject_bug=bug)
        assert any(v.startswith("journal:")
                   for v in rep["violations"]), rep["violations"]

        sched = faultplan.FaultSchedule.from_dict(rep["schedule"])
        minimal, stats = faultplan.shrink(
            sched,
            lambda s: scen.run_chaos(schedule=s)["violations"],
            violations=rep["violations"])
        assert len(minimal.events) <= 5
        assert stats["after"]["events"] <= stats["before"]["events"]
        assert stats["runs"] <= 48

        replay = scen.run_chaos(schedule=minimal)
        assert faultplan.violation_kinds(replay["violations"]) \
            >= faultplan.violation_kinds(rep["violations"])

        cmd = faultplan.write_bundle(tmp_path, minimal,
                                     replay["violations"], stats)
        doc = json.loads((tmp_path / "violation.json").read_text())
        assert doc["violations"]
        saved = faultplan.FaultSchedule.load(
            tmp_path / "schedule.json")
        assert saved == minimal
        assert "schedule.json" in cmd


class TestChaosCli:
    def test_clean_schedule_determinism_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, str(SIMULATE), "--scenario", "chaos",
             "--seed", "7", "--engines", "8", "--requests", "120",
             "--kills", "2", "--check-determinism"],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        rep = json.loads(proc.stdout)
        assert rep["violations"] == []
        assert "determinism check OK" in proc.stderr

    def test_seeded_violation_bundle_repro_one_command(
            self, tmp_path):
        """The one-command acceptance: --seed-violation --shrink
        writes the bundle (exit 2), and replaying the bundled
        schedule reproduces the violation (exit 2 again)."""
        proc = subprocess.run(
            [sys.executable, str(SIMULATE), "--scenario", "chaos",
             "--seed", "0", "--engines", "6", "--requests", "800",
             "--kills", "8", "--seed-violation", "--shrink",
             "--bundle-dir", str(tmp_path)],
            capture_output=True, text=True, timeout=600)
        assert proc.returncode == 2, proc.stderr
        rep = json.loads(proc.stdout)
        assert rep["violations"]
        assert len(rep["minimal_schedule"]["events"]) <= 5

        again = subprocess.run(
            [sys.executable, str(SIMULATE), "--scenario", "chaos",
             "--schedule", str(tmp_path / "schedule.json")],
            capture_output=True, text=True, timeout=300)
        assert again.returncode == 2, again.stderr
        assert json.loads(again.stdout)["violations"]


# -- satellite: gossip/breaker duplicate delivery ----------------------


def _partition_fault_log(seed=5):
    """Applied partition/heal events from a seeded sim chaos run —
    the observation source for the duplicate-delivery properties."""
    s = faultplan.generate(seed, engines=4, requests=80, kills=0,
                           slow=0, partitions=2, fault_spec="")
    rep = scen.run_chaos(schedule=s)
    events = [e for e in rep["fault_log"]
              if e["action"] in ("partition", "heal")]
    assert events, rep["fault_log"]
    return events


class TestGossipBreakerDuplicateDelivery:
    def test_lww_merge_converges_under_duplicate_delivery(self):
        """Observations from sim partition events, every one
        delivered TWICE (locally and via gossip) in six shuffled
        orders: the merged map is identical every time, and
        re-merging the converged state is a no-op."""
        events = _partition_fault_log()
        deliveries = []
        for i, e in enumerate(events):
            down = e["action"] == "partition"
            deliveries.append({f"sim://{e['target']}": {
                "stamp": e["t"], "origin": f"r{i % 2}",
                "pool": "engine", "healthy": not down,
                "draining": False,
                "cb_state": "open" if down else "closed",
                "fails": 1 if down else 0,
                "cb_trips": 1 if down else 0}})
        rng = random.Random(5)
        converged = None
        for _ in range(6):
            order = deliveries * 2  # duplicate every delivery
            rng.shuffle(order)
            state = {}
            for snap in order:
                state = gossip.merge_backends(state, snap)
            if converged is None:
                converged = state
            assert state == converged
        assert gossip.merge_backends(converged, converged) \
            == converged
        # the survivor holds the NEWEST observation per backend
        for url, rec in converged.items():
            stamps = [s[url]["stamp"] for s in deliveries
                      if url in s]
            assert rec["stamp"] == max(stamps)

    def test_probe_verdict_never_charged_twice(self):
        """The probe-token idempotency gate, driven at each sim
        partition time: one real half-open probe failure charges the
        breaker once; the SAME verdict arriving again (gossip
        replay while the backend is half-open again) is a no-op —
        cb_trips and the cooldown deadline do not move."""
        times = [e["t"] for e in _partition_fault_log()
                 if e["action"] == "partition"]
        for now in times:
            b = Backend("http://victim:9", cb_threshold=3,
                        cb_cooldown=0.5)
            for _ in range(3):
                b.record_failure(now)  # trip: closed -> open
            assert b.cb_state == "open" and b.cb_trips == 1

            t1 = b.cb_open_until + 0.01
            assert b.selectable(t1)  # cooldown over: half-open
            tok = b.begin_probe()
            b.record_failure(t1, probe_token=tok)  # real verdict
            assert b.cb_trips == 2

            t2 = b.cb_open_until + 0.01
            assert b.selectable(t2)  # half-open again
            deadline = b.cb_open_until
            b.record_failure(t2, probe_token=tok)  # gossip replay
            assert b.cb_trips == 2  # NOT double-penalized
            assert b.cb_open_until == deadline  # cooldown unmoved
            assert b.cb_state == "half_open"  # still probing

            tok2 = b.begin_probe()  # a NEW probe verdict does count
            b.record_failure(t2, probe_token=tok2)
            assert b.cb_trips == 3


# -- slow: scale acceptance + subprocess fidelity ----------------------


@pytest.mark.slow
class TestChaosScale:
    def test_500_engines_50_kills_under_budget(self):
        """The scale acceptance: >=500 engines, >=50 kill/restart
        events, byte-identical across two runs, fleet-wide
        invariants clean, under the 2-CPU-minute budget."""
        t0 = time.monotonic()
        a = scen.run_chaos(seed=7, engines=500, requests=5000,
                           kills=60)
        wall = time.monotonic() - t0
        b = scen.run_chaos(seed=7, engines=500, requests=5000,
                           kills=60)
        assert scen.canonical_json(a) == scen.canonical_json(b)
        assert a["violations"] == []
        kills = sum(1 for e in a["schedule"]["events"]
                    if e["action"] == "kill")
        restarts = sum(1 for e in a["fault_log"]
                       if e["action"] == "restart")
        assert kills >= 50 and restarts >= 50
        assert a["sim"]["engines_spawned"] == 500
        assert wall < 120.0, f"{wall:.1f}s wall"


@pytest.mark.slow
class TestChaosDownConvert:
    def test_sim_schedule_passes_subprocess_invariants(
            self, tmp_path):
        """The fidelity spot-check: a sim-explored schedule
        down-converts onto a real 2-engine topology and the
        subprocess harness's own invariants pass."""
        s = faultplan.generate(3, engines=50, requests=400, kills=2,
                               slow=0, partitions=0, fault_spec="")
        path = tmp_path / "sched.json"
        s.save(path)
        proc = subprocess.run(
            [sys.executable, str(CHAOS_SOAK), "--schedule",
             str(path), "--prefill", "0", "--decode", "0",
             "--unified", "2", "--requests", "8", "--spread", "6"],
            capture_output=True, text=True, timeout=600,
            cwd=REPO)
        assert proc.returncode == 0, \
            proc.stdout[-2000:] + proc.stderr[-2000:]
        assert "0 violation(s)" in proc.stdout
