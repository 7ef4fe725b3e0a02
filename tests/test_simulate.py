"""Fleet flight simulator (serve/simulate.py) + the unified clock.

The simulator's promise is twofold and both halves are pinned here:

* it runs the REAL policy code (ServeQueue lanes, BrownoutGovernor,
  RouterPolicy dispatch/affinity/steering, FleetActions, SLO burn
  monitor) on a virtual clock — deterministically, at fleet scale, in
  seconds of wall time;
* everything it does lands on the standard telemetry stream, so the
  unmodified obs plane (`obs doctor`, the golden-fixture
  contract) consumes a simulated fleet exactly like a live one.

Scenario soaks at design size run under `-m slow`; tier-1 keeps the
small pinned runs, the determinism pin, the seeded-regression demo,
and the obs-plane consumption tests.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from hyperion_tpu.obs import doctor
from hyperion_tpu.serve import simulate
from hyperion_tpu.utils.clock import SYSTEM, Clock, VirtualClock

REPO = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).parent / "data" / "telemetry" / "sim"


def small_failover(**kw) -> dict:
    """The gen_fixtures.py sim arm's scenario: failover scaled to 4
    replicas / 150 requests with asserts rescaled to match."""
    scn = dict(simulate.SCENARIOS["failover"])
    scn.update(replicas=4, requests=150, duration_s=90.0)
    scn["assert"] = {"completed_rate": {"min": 0.80},
                     "duplicate_tokens": {"max": 0},
                     "ejections": {"min": 2},
                     "readmits": {"min": 2}}
    scn.update(kw)
    return scn


# ----------------------------------------------------------------- clock


class TestClock:
    def test_system_clock_is_monotonic_and_walled(self):
        t0 = SYSTEM()
        assert SYSTEM() >= t0
        assert SYSTEM.wall() > 1_600_000_000.0  # a calendar time

    def test_virtual_clock_advances_both_accumulators(self):
        clk = VirtualClock(100.0, wall0=1_000.0)
        clk.advance(2.5)
        assert clk() == 102.5 and clk.wall() == 1_002.5

    def test_virtual_advance_to_never_rewinds(self):
        clk = VirtualClock(100.0)
        clk.advance_to(110.0)
        clk.advance_to(50.0)  # in the past: no-op
        assert clk() == 110.0

    def test_virtual_sleep_advances(self):
        clk = VirtualClock(100.0)
        clk.sleep(3.0)
        assert clk() == 103.0

    def test_virtual_is_a_clock(self):
        # every `clock=` site accepts either; the subtype relation is
        # what makes the injection seamless
        assert isinstance(VirtualClock(), Clock)


# ------------------------------------------------ simulator core promise


class TestSimulator:
    def test_small_failover_passes_its_asserts(self, tmp_path):
        res = simulate.run_scenario(small_failover(),
                                    out=str(tmp_path / "s"))
        assert res["ok"], res["asserts"]
        rep = res["report"]
        assert rep["duplicate_tokens"] == 0
        assert rep["ejections"] >= 2 and rep["readmits"] >= 2
        # the virtual run plays 90 virtual seconds; wall time must be
        # a tiny fraction of that (the whole point of the harness)
        assert res["virtual_s"] >= 89.0
        assert res["wall_s"] < res["virtual_s"]

    def test_same_seed_same_report(self, tmp_path):
        r1 = simulate.run_scenario(small_failover(),
                                   out=str(tmp_path / "a"))
        r2 = simulate.run_scenario(small_failover(),
                                   out=str(tmp_path / "b"))
        assert r1["report"] == r2["report"]
        assert r1["asserts"] == r2["asserts"]

    def test_different_seed_different_traffic(self, tmp_path):
        r1 = simulate.run_scenario(small_failover(),
                                   out=str(tmp_path / "a"))
        r2 = simulate.run_scenario(small_failover(seed=99),
                                   out=str(tmp_path / "b"))
        assert r1["report"] != r2["report"]

    def test_failover_never_duplicates_tokens(self, tmp_path):
        # the exactly-once promise under virtual failover: the REAL
        # StreamDedup replays the redispatched streams and counts
        # duplicate deliveries — the count must be exactly zero. The
        # denser request rate guarantees streams are IN FLIGHT on the
        # killed half, so redispatch actually exercises the replay.
        res = simulate.run_scenario(small_failover(requests=900),
                                    out=str(tmp_path / "s"))
        assert res["report"]["duplicate_tokens"] == 0
        assert res["report"]["redispatched"] >= 1  # failover happened

    def test_seeded_regression_demo_hysteresis_disabled_flaps(
            self, tmp_path):
        """THE acceptance demo: slow_burn passes with the production
        steer hysteresis and FAILS its reversal bound when hysteresis
        is disabled (steer_clear_sweeps=1) — the scenario harness
        catches a policy regression through exported obs metrics."""
        bad = simulate.run_scenario(
            "slow_burn", out=str(tmp_path / "bad"),
            router={"steer_clear_sweeps": 1})
        assert not bad["ok"]
        failed = [a for a in bad["asserts"] if not a["ok"]]
        assert any(a["key"] == "steer_reversals" for a in failed), failed
        assert bad["report"]["steer_reversals"] > 2

    @pytest.mark.slow
    def test_slow_burn_passes_with_production_hysteresis(self, tmp_path):
        good = simulate.run_scenario("slow_burn",
                                     out=str(tmp_path / "good"))
        assert good["ok"], good["asserts"]
        assert 1 <= good["report"]["steer_reversals"] <= 2

    @pytest.mark.slow
    @pytest.mark.parametrize("name", sorted(simulate.SCENARIOS))
    def test_design_size_scenario_asserts_hold(self, name, tmp_path):
        res = simulate.run_scenario(name, out=str(tmp_path / name))
        assert res["ok"], (name, res["asserts"])

    @pytest.mark.slow
    def test_herd_at_fleet_scale(self, tmp_path):
        """The scale acceptance: 10^5 requests over 200 replicas play
        in well under a minute of wall clock, zero jits."""
        res = simulate.run_scenario("herd", replicas=200,
                                    requests=100_000,
                                    out=str(tmp_path / "herd"))
        assert res["ok"], res["asserts"]
        assert res["wall_s"] < 60.0


# ------------------------------------------------- obs-plane consumption


class TestObsPlaneConsumption:
    def test_doctor_reads_fixture_unchanged(self):
        d = doctor.diagnose(FIXTURE)
        assert d["verdict"] == "healthy"
        assert d["sim"]["scenario"] == "failover"
        assert d["sim"]["ok"] is True
        assert d["sim"]["failed"] == 0
        assert d["sim"]["incident"] is None

    def test_doctor_names_failed_sim_assert(self, tmp_path):
        scn = small_failover()
        scn["assert"]["completed_rate"] = {"min": 1.01}  # impossible
        res = simulate.run_scenario(scn, out=str(tmp_path))
        assert not res["ok"]
        d = doctor.diagnose(tmp_path)
        assert d["sim"]["ok"] is False
        assert "completed_rate" in d["reason"] and "sim:" in d["reason"]
        md = doctor.render_markdown(d)
        assert "FAILED" in md and "completed_rate" in md

    def test_doctor_markdown_renders_passing_sim_row(self):
        md = doctor.render_markdown(doctor.diagnose(FIXTURE))
        assert "simulation `failover`" in md
        assert "assertion(s) held" in md

    def test_fixture_sim_report_event_contract(self):
        """Pin the simulator's own event vocabulary: the header and
        verdict records future tooling (and the doctor today) key on."""
        recs = [json.loads(line) for line in
                (FIXTURE / "telemetry.jsonl").read_text().splitlines()]
        (hdr,) = [r for r in recs if r["name"] == "sim_scenario"]
        assert hdr["scenario"] == "failover"
        for field in ("replicas", "requests", "duration_s", "seed",
                      "faults"):
            assert isinstance(hdr[field], (int, float)), field
        (rep,) = [r for r in recs if r["name"] == "sim_report"]
        assert rep["ok"] is True and rep["failed"] == 0
        assert isinstance(rep["report"], dict)
        for key in simulate.REPORT_KEYS:
            assert key in rep["report"], key
        # the standard router vocabulary rides the same stream
        names = {r["name"] for r in recs}
        assert {"router_start", "router_end", "replica_ready",
                "route_dispatch", "route_complete",
                "replica_ejected"} <= names

# --------------------------------------------------------- CLI + guards


class TestCLI:
    def test_list_exits_zero(self, capsys):
        assert simulate.main(["--list"]) == 0
        out = capsys.readouterr().out
        for name in simulate.SCENARIOS:
            assert name in out

    def test_unknown_scenario_exits_two(self, capsys):
        assert simulate.main(["nope"]) == 2
        assert "unknown scenario" in capsys.readouterr().err

    def test_no_scenario_exits_two(self, capsys):
        assert simulate.main([]) == 2
        capsys.readouterr()

    def test_cli_main_dispatches_simulate(self, capsys):
        from hyperion_tpu.cli.main import main as cli_main

        assert cli_main(["simulate", "--list"]) == 0
        assert "herd" in capsys.readouterr().out


class TestClockInjectionGuard:
    """Satellite guard: the policy modules the simulator drives must
    never read real time directly — every read goes through the
    injected clock, or the virtual clock silently loses authority."""

    GUARDED = ("hyperion_tpu/serve/queue.py",
               "hyperion_tpu/serve/router.py",
               "hyperion_tpu/serve/simulate.py")

    @pytest.mark.parametrize("rel", GUARDED)
    def test_no_direct_time_reads(self, rel):
        src = (REPO / rel).read_text()
        # time.perf_counter is allowed: simulate.py reports its own
        # wall-clock cost with it (harness bookkeeping, not policy time)
        hits = re.findall(r"time\.(?:monotonic|time)\(", src)
        assert not hits, (rel, hits)
