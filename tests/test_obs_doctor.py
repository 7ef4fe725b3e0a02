"""obs doctor / obs diff + the telemetry record contract.

The golden fixture streams under tests/data/telemetry/ (regenerable via
gen_fixtures.py there) are the compatibility anchor: the schema test
pins every span/event/snapshot/heartbeat field that `doctor`, `diff`,
and `summarize` read, so a producer-side refactor that would silently
break offline tooling fails HERE, in tier-1, not in a post-mortem.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from hyperion_tpu.obs import diff as obs_diff
from hyperion_tpu.obs import doctor, report
from hyperion_tpu.obs.heartbeat import read_heartbeat
from hyperion_tpu.obs.registry import MetricsRegistry
from hyperion_tpu.obs.trace import Tracer
from hyperion_tpu.utils.clock import VirtualClock

FIXTURES = Path(__file__).parent / "data" / "telemetry"

ALL_FIXTURES = ("healthy", "nan", "stalled", "hung", "crashed", "serve",
                "slo")
# the fleet fixture is three streams in one layout (router + two
# replicas); each joins the record contract individually — the fleet
# join (tests/test_fleet_trace.py) only works if every constituent
# stream honors the same envelope the single-process tools read
FLEET_FIXTURES = ("fleet", "fleet/replica_0", "fleet/replica_1")
# the flight-simulator fixture (serve/simulate.py) is events+snapshots
# only — like the fleet router stream it has no tick spans, so it joins
# the envelope and heartbeat contracts but not the span contract
SIM_FIXTURES = ("sim",)


def write_run(path, run: str, step_ms: float, *, steps: int = 8,
              tokens_per_s: float = 4096.0, wall0: float = 1_000.0,
              terminal: bool = True, gauges: dict | None = None):
    """One synthetic healthy-shaped run appended to `path`; `gauges`
    sets or overrides what the final snapshot carries."""
    clk, wall = VirtualClock(100.0), VirtualClock(wall0)
    t = Tracer(path, run=run, proc=0, clock=clk, wall=wall)
    t.event("train_start", job="language_ddp")
    with t.span("epoch", step=0) as ep:
        for i in range(steps):
            with t.span("train_step", step=i):
                clk.advance(step_ms / 1e3)
                wall.advance(step_ms / 1e3)
        ep.set(epoch=1, steps=steps)
    reg = MetricsRegistry()
    reg.gauge("tokens_per_s").set(tokens_per_s)
    reg.gauge("mfu").set(0.3)
    reg.gauge("hbm_peak_mb").set(512.0)
    for name, value in (gauges or {}).items():
        reg.gauge(name).set(value)
    t.snapshot(reg, step=steps)
    if terminal:
        t.event("train_end", preempted=False)
    t.close()


def write_input_wait_run(path, run: str, frac: float, wait_s: float = 8.0):
    """A finished run whose last snapshot carries the input-wait gauges
    (`observe_input_wait`) — the evidence `doctor` reads for the
    input-bound call."""
    clk, wall = VirtualClock(100.0), VirtualClock(1_000.0)
    t = Tracer(path, run=run, proc=0, clock=clk, wall=wall)
    t.event("train_start", job="language_ddp")
    reg = MetricsRegistry()
    reg.gauge("input_wait_s").set(wait_s)
    reg.gauge("input_wait_frac").set(frac)
    t.snapshot(reg, step=8)
    t.event("train_end", preempted=False)
    t.close()


# --------------------------------------------------------------- doctor


class TestDoctorFixtures:
    """The tier-1 smoke required by the issue: `hyperion_tpu obs doctor`
    over every committed fixture stream, through the real CLI."""

    @pytest.mark.parametrize("name,verdict,rc", [
        ("healthy", "healthy", 0),
        ("nan", "diverged", 1),
        ("stalled", "stalled", 1),
        ("hung", "hung", 1),
        ("crashed", "crashed", 1),
    ])
    def test_cli_classifies_fixture(self, name, verdict, rc, capsys):
        from hyperion_tpu.cli.main import main as cli_main

        args = ["obs", "doctor", str(FIXTURES / name)]
        if name == "stalled":
            # "stalled" means alive-and-degrading: judge it from a
            # vantage point where the committed heartbeat is fresh
            # (staleness outranks the stall pattern — see the hung
            # cross-check below)
            hb = read_heartbeat(FIXTURES / name / "heartbeat.json")
            args += ["--now", str(hb["t_wall"] + 30)]
        code = cli_main(args)
        out = capsys.readouterr().out
        assert f"verdict: {verdict}" in out, out
        assert code == rc

    def test_stalled_then_dead_is_hung(self):
        # the SAME degraded stream, judged long after the last beat:
        # the process is gone, so staleness wins — with the stall
        # history kept as evidence in the reason
        d = doctor.diagnose(FIXTURES / "stalled")  # real now: very stale
        assert d["verdict"] == "hung"
        assert "degraded" in d["reason"]
        assert d["stall"] is not None

    def test_nan_fixture_evidence(self):
        d = doctor.diagnose(FIXTURES / "nan")
        assert d["verdict"] == "diverged"
        assert any(h["anomaly"] == "nonfinite_loss"
                   for h in d["health_events"])
        assert d["heartbeat"]["phase"] == "aborted"

    def test_stalled_fixture_evidence(self):
        hb = read_heartbeat(FIXTURES / "stalled" / "heartbeat.json")
        d = doctor.diagnose(FIXTURES / "stalled", now=hb["t_wall"] + 30)
        assert d["verdict"] == "stalled"
        assert d["stall"]["ratio"] >= doctor.STALL_RATIO
        assert d["heartbeat"]["phase"] == "train"

    def test_crashed_fixture_evidence(self):
        d = doctor.diagnose(FIXTURES / "crashed")
        assert d["verdict"] == "crashed"
        assert d["truncated_tail"] is True and d["bad_lines"] == 1

    def test_hung_fixture_goes_running_when_fresh(self):
        # the SAME stream classifies as running when "now" is close to
        # its timestamps — hung is purely a staleness verdict
        hb = read_heartbeat(FIXTURES / "hung" / "heartbeat.json")
        d = doctor.diagnose(FIXTURES / "hung", now=hb["t_wall"] + 10)
        assert d["verdict"] == "running"
        d = doctor.diagnose(FIXTURES / "hung", now=hb["t_wall"] + 10_000)
        assert d["verdict"] == "hung"

    def test_healthy_fixture_summary_fields(self):
        d = doctor.diagnose(FIXTURES / "healthy")
        assert d["verdict"] == "healthy"
        assert d["steps"] == 8 and d["hbm_peak_mb"] == 900.0
        assert d["heartbeat"]["phase"] == "done"

    def test_missing_target_exits_2(self, tmp_path, capsys):
        assert doctor.main([str(tmp_path / "nope")]) == 2
        assert "no telemetry stream" in capsys.readouterr().err

    def test_empty_stream_is_empty_verdict(self, tmp_path, capsys):
        (tmp_path / "telemetry.jsonl").write_text("")
        assert doctor.main([str(tmp_path)]) == 2
        assert "empty" in capsys.readouterr().out

    def test_json_output(self, capsys):
        assert doctor.main([str(FIXTURES / "healthy"), "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["verdict"] == "healthy"

    def test_report_entry_point_dispatches_doctor(self, monkeypatch,
                                                  capsys):
        # `python -m hyperion_tpu.obs.report doctor <dir>` — main(None)
        # must resolve sys.argv BEFORE the doctor/diff dispatch
        import sys as _sys

        monkeypatch.setattr(_sys, "argv",
                            ["report", "doctor", str(FIXTURES / "healthy")])
        assert report.main() == 0
        assert "verdict: healthy" in capsys.readouterr().out

    def test_failed_publish_is_not_healthy(self, tmp_path):
        # a run that completes its lifecycle but says failed=true on its
        # terminal event must not classify healthy
        t = Tracer(tmp_path / "telemetry.jsonl", run="bench_x", proc=0)
        t.event("bench_start", metric="matmul")
        t.event("publish", value=0.0, failed=True, error="--child-matmul timed out")
        t.close()
        d = doctor.diagnose(tmp_path)
        assert d["verdict"] == "failed"
        assert "timed out" in d["reason"]
        assert doctor.EXIT_BY_VERDICT["failed"] == 1

    def test_successful_publish_stays_healthy(self, tmp_path):
        t = Tracer(tmp_path / "telemetry.jsonl", run="bench_y", proc=0)
        t.event("bench_start", metric="matmul")
        t.event("publish", value=175.75, plausible=True, vs_baseline=1.45)
        t.close()
        assert doctor.diagnose(tmp_path)["verdict"] == "healthy"

    def test_foreign_heartbeat_is_ignored(self, tmp_path):
        # heartbeat from a DIFFERENT run id must not vouch for this one
        write_run(tmp_path / "telemetry.jsonl", "r_old", 10.0,
                  terminal=False)
        (tmp_path / "heartbeat.json").write_text(json.dumps(
            {"v": 1, "run": "r_new", "t_wall": 2_000.0, "phase": "train"}
        ))
        d = doctor.diagnose(tmp_path, run="r_old", now=5_000.0)
        assert d["heartbeat"] is None
        assert d["verdict"] == "hung"  # stream stale, no heartbeat for it


class TestInputBound:
    """`obs doctor` calls a run input-bound when the input_wait_frac
    gauge says the step loop mostly waited on the input queue — an
    orthogonal note on the liveness verdict, not a verdict itself."""

    def test_flags_input_bound_run(self, tmp_path):
        write_input_wait_run(tmp_path / "telemetry.jsonl", "r1", frac=0.8)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["verdict"] == "healthy"  # alive AND starved can coexist
        assert d["input_bound"] is True
        assert d["input_wait_frac"] == 0.8
        assert "input-bound" in d["reason"]
        assert "input wait" in doctor.render_markdown(d)
        assert "**input-bound**" in doctor.render_markdown(d)

    def test_well_fed_run_stays_quiet(self, tmp_path):
        write_input_wait_run(tmp_path / "telemetry.jsonl", "r1", frac=0.04)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["input_bound"] is False
        assert "input-bound" not in d["reason"]
        # the evidence row still renders, unflagged
        assert "input wait" in doctor.render_markdown(d)

    def test_no_gauge_means_no_claim(self, tmp_path):
        write_run(tmp_path / "telemetry.jsonl", "r1", 10.0)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["input_bound"] is False
        assert d["input_wait_frac"] is None
        assert "input wait" not in doctor.render_markdown(d)


def write_spec_serve_run(path, run: str, drafted: int, accepted: int,
                         tokens_per_tick: float = 1.4):
    """A finished serve-shaped run whose last snapshot carries the
    speculative-decoding counters/gauges (serve/metrics.py `on_spec`)."""
    clk, wall = VirtualClock(100.0), VirtualClock(1_000.0)
    t = Tracer(path, run=run, proc=0, clock=clk, wall=wall)
    t.event("serve_start")
    reg = MetricsRegistry()
    reg.counter("serve_ticks").inc(50)
    reg.counter("serve_completed").inc(4)
    reg.counter("serve_spec_drafted").inc(drafted)
    reg.counter("serve_spec_accepted").inc(accepted)
    reg.counter("serve_spec_rejected").inc(drafted - accepted)
    if drafted:
        reg.gauge("serve_spec_accept_rate").set(accepted / drafted)
    reg.gauge("serve_tokens_per_tick").set(tokens_per_tick)
    reg.gauge("queue_depth").set(0.0)
    t.snapshot(reg, step=50)
    t.event("serve_end")
    t.close()


class TestSpeculationIncident:
    """`obs doctor` on a spec-enabled serve run: the accept rate is an
    incident below SPEC_ACCEPT_FLOOR (the k+1-wide verify forward is
    then mostly wasted), with the knobs to turn named in the reason."""

    def test_low_acceptance_is_named(self, tmp_path):
        write_spec_serve_run(tmp_path / "telemetry.jsonl", "r1",
                             drafted=400, accepted=60,
                             tokens_per_tick=1.05)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["verdict"] == "healthy"
        assert d["serve"]["spec_drafted"] == 400
        assert d["spec_incidents"], "low acceptance produced no incident"
        assert ("draft mispredicting — lower --spec-k or disable "
                "--draft") in d["reason"]
        md = doctor.render_markdown(d)
        assert "serve speculation" in md
        assert "**low acceptance**" in md

    def test_healthy_acceptance_stays_quiet(self, tmp_path):
        write_spec_serve_run(tmp_path / "telemetry.jsonl", "r1",
                             drafted=400, accepted=240)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["spec_incidents"] == []
        assert "mispredicting" not in d["reason"]
        # the evidence row still renders, unflagged
        md = doctor.render_markdown(d)
        assert "serve speculation" in md
        assert "low acceptance" not in md

    def test_spec_off_run_has_no_row(self, tmp_path):
        write_spec_serve_run(tmp_path / "telemetry.jsonl", "r1",
                             drafted=0, accepted=0)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["spec_incidents"] == []
        assert "serve speculation" not in doctor.render_markdown(d)


def write_tiered_serve_run(path, run: str, *, host_cache_mb,
                           evicted=0, spilled=0, host_hits=0,
                           tier_miss=0, restores=0, saved_chains=None):
    """A finished serve-shaped run with the tiered-KV evidence trail:
    `serve_start` declares the tier budget, the snapshot carries the
    tier counters (serve/metrics.py), and `host_restore` /
    `hostcache_saved` events say the tier actually moved bytes."""
    clk, wall = VirtualClock(100.0), VirtualClock(1_000.0)
    t = Tracer(path, run=run, proc=0, clock=clk, wall=wall)
    t.event("serve_start", host_cache_mb=host_cache_mb)
    for i in range(restores):
        t.event("host_restore", request=f"q{i}", tick=i, blocks=2,
                tokens=16, bytes=4096)
    reg = MetricsRegistry()
    reg.counter("serve_ticks").inc(50)
    reg.counter("serve_completed").inc(4)
    reg.counter("serve_blocks_evicted").inc(evicted)
    reg.counter("serve_host_spilled_blocks").inc(spilled)
    reg.counter("serve_host_restored_blocks").inc(2 * restores)
    reg.counter("serve_tier_hits_host").inc(host_hits)
    reg.counter("serve_tier_hits_device").inc(1)
    reg.counter("serve_tier_miss").inc(tier_miss)
    reg.gauge("queue_depth").set(0.0)
    t.snapshot(reg, step=50)
    if saved_chains is not None:
        t.event("hostcache_saved", chains=saved_chains, mb=0.5,
                path=str(path.parent / "hostcache"))
    t.event("serve_end")
    t.close()


class TestTieredKVIncidents:
    """`obs doctor` on the host-spill tier: evictions with the tier OFF
    and spills the workload never came back for are DIFFERENT named
    incidents with different knobs — and a tier that fed re-hits is
    evidence, not a complaint."""

    def test_evictions_with_tier_disabled_are_named(self, tmp_path):
        write_tiered_serve_run(tmp_path / "telemetry.jsonl", "r1",
                               host_cache_mb=0, evicted=7, tier_miss=3)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["verdict"] == "healthy"
        assert d["tier_incidents"], "disabled tier produced no incident"
        assert "host tier DISABLED" in d["reason"]
        assert "--host-cache-mb" in d["reason"]
        md = doctor.render_markdown(d)
        assert "serve cache tiers" in md
        assert "**tier incident**" in md

    def test_spills_without_rehits_is_undersized(self, tmp_path):
        write_tiered_serve_run(tmp_path / "telemetry.jsonl", "r1",
                               host_cache_mb=4, evicted=7, spilled=7,
                               host_hits=0, tier_miss=5)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["tier_incidents"]
        assert "--host-cache-mb likely undersized" in d["reason"]
        assert d["host_tier"]["budget_mb"] == 4

    def test_tier_feeding_rehits_stays_quiet(self, tmp_path):
        write_tiered_serve_run(tmp_path / "telemetry.jsonl", "r1",
                               host_cache_mb=64, evicted=7, spilled=7,
                               host_hits=3, tier_miss=5, restores=3,
                               saved_chains=5)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["tier_incidents"] == []
        assert "cache tier" not in d["reason"]
        # the evidence row still renders, unflagged, with the
        # drain-time save cited
        assert d["host_tier"]["restore_events"] == 3
        assert d["host_tier"]["saved"] == {"chains": 5, "mb": 0.5}
        md = doctor.render_markdown(d)
        assert "serve cache tiers" in md
        assert "**tier incident**" not in md

    def test_tierless_run_has_no_row(self, tmp_path):
        write_spec_serve_run(tmp_path / "telemetry.jsonl", "r1",
                             drafted=0, accepted=0)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["tier_incidents"] == []
        assert d["host_tier"] is None
        assert "serve cache tiers" not in doctor.render_markdown(d)


class TestTenantAttributionAndRouterActions:
    """PR 14: when adversarial tenants drive the pressure, the doctor
    NAMES the offending tenant from the admit/shed event trail; and
    the acting router's telemetry (router_steer / class_brownout /
    router_scale) rolls up into one narrated line."""

    def _run(self, tmp_path, events):
        clk, wall = VirtualClock(100.0), VirtualClock(1_000.0)
        t = Tracer(tmp_path / "telemetry.jsonl", run="r1", proc=0,
                   clock=clk, wall=wall)
        t.event("serve_start")
        for name, kw in events:
            clk.advance(0.1)
            wall.advance(0.1)
            t.event(name, **kw)
        t.event("serve_end")
        t.close()
        return doctor.diagnose(tmp_path, now=1_100.0)

    def test_offending_tenant_is_named(self, tmp_path):
        d = self._run(tmp_path, [
            ("request_admitted", {"request": "a0", "tenant": "adv_burst",
                                  "sla_class": "batch"}),
            ("request_admitted", {"request": "a1", "tenant": "adv_burst",
                                  "sla_class": "batch"}),
            ("request_rejected", {"request": "a2", "tenant": "adv_burst",
                                  "sla_class": "batch", "shed": True,
                                  "reason": "shed_deadline"}),
            ("request_admitted", {"request": "u0", "tenant": "alice",
                                  "sla_class": "interactive"}),
        ])
        assert d["tenants"][0]["tenant"] == "adv_burst"
        assert d["tenants"][0]["shed"] == 1
        assert any("adv_burst" in s for s in d["tenant_incidents"])
        assert "adv_burst" in d["reason"]
        md = doctor.render_markdown(d)
        assert "`adv_burst`" in md and "**offender**" in md
        # the civilian tenant renders unflagged
        assert "`alice`" in md
        assert md.count("**offender**") == 1

    def test_untagged_run_makes_no_tenant_claim(self, tmp_path):
        d = self._run(tmp_path, [
            ("request_admitted", {"request": "a0",
                                  "sla_class": "interactive"}),
        ])
        assert d["tenants"] == [] and d["tenant_incidents"] == []
        assert "tenant" not in d["reason"]

    def test_router_actions_are_narrated(self, tmp_path):
        d = self._run(tmp_path, [
            ("router_steer", {"replica": 1, "on": True,
                              "alerts": ["ttft_p99"]}),
            ("class_brownout", {"replica": 1, "active": True,
                                "acked": True}),
            ("router_scale", {"direction": "up", "replica": 2,
                              "fleet": 3}),
            ("router_steer", {"replica": 1, "on": False}),
            ("class_brownout", {"replica": 1, "active": False,
                                "acked": True}),
            ("router_scale", {"direction": "down", "replica": 2,
                              "fleet": 2}),
        ])
        acts = d["router_actions"]
        assert len(acts) == 3
        assert any("replica(s) 1" in a and "all reversed" in a
                   for a in acts)
        assert any("brownout ordered 1x, lifted 1x" in a for a in acts)
        assert any("1 standby spawn(s), 1 retire(s)" in a for a in acts)
        assert "router actions:" in d["reason"]
        assert "router action" in doctor.render_markdown(d)

    def test_unreversed_steer_is_called_out(self, tmp_path):
        d = self._run(tmp_path, [
            ("router_steer", {"replica": 0, "on": True,
                              "alerts": ["ttft_p99"]}),
        ])
        assert any("still steered at the end" in a
                   for a in d["router_actions"])


class TestRouterWalPostMortem:
    """PR 15: a dead router life leaves its dispatch WAL next to the
    telemetry stream. Pending entries with no `router_end` event are the
    streams it still owes clients — the doctor must cite the WAL tail as
    evidence, read-only (recovery belongs to the next router life)."""

    def _tele(self, tmp_path, *, ended: bool):
        clk, wall = VirtualClock(100.0), VirtualClock(1_000.0)
        t = Tracer(tmp_path / "telemetry.jsonl", run="r1", proc=0,
                   clock=clk, wall=wall)
        t.event("router_start", replicas=2)
        t.event("route_dispatch", request="q1", replica=1)
        if ended:
            t.event("router_end")
        t.close()

    def _wal(self, tmp_path, *, settle: bool):
        from hyperion_tpu.serve.router_journal import RouterJournal

        j = RouterJournal(tmp_path / "router_journal.jsonl")
        j.dispatch("q1", line='{"id": "q1", "prompt_ids": [7]}',
                   replica=1, session="s1")
        j.hwm("q1", 3)
        if settle:
            j.done("q1", "completed")
        j.close()

    def test_orphaned_wal_becomes_the_incident(self, tmp_path):
        self._tele(tmp_path, ended=False)
        self._wal(tmp_path, settle=False)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        wal = d["router_wal"]
        assert wal["pending"] == 1
        assert "router_journal.jsonl" in wal["incident"]
        assert "in-flight" in wal["incident"]
        # the tail is the evidence: placement and high-water mark cited
        assert "q1" in wal["incident"] and "i=3" in wal["incident"]
        assert "router WAL" in d["reason"]
        md = doctor.render_markdown(d)
        assert "router WAL" in md and "owed streams" in md

    def test_clean_router_end_makes_no_claim(self, tmp_path):
        self._tele(tmp_path, ended=True)
        self._wal(tmp_path, settle=False)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["router_wal"] is not None
        assert "incident" not in d["router_wal"]
        assert "router WAL" not in d["reason"]

    def test_settled_wal_makes_no_claim(self, tmp_path):
        self._tele(tmp_path, ended=False)
        self._wal(tmp_path, settle=True)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["router_wal"]["pending"] == 0
        assert "incident" not in d["router_wal"]

    def test_no_wal_file_means_no_row(self, tmp_path):
        self._tele(tmp_path, ended=False)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["router_wal"] is None
        assert "router WAL" not in doctor.render_markdown(d)


def write_rss_run(path, run: str, series):
    """A finished serve-shaped run whose snapshots carry the host RSS
    gauge as a SERIES — the evidence `doctor` reads for the host-leak
    trend."""
    clk, wall = VirtualClock(100.0), VirtualClock(1_000.0)
    t = Tracer(path, run=run, proc=0, clock=clk, wall=wall)
    t.event("serve_start")
    for i, mb in enumerate(series):
        reg = MetricsRegistry()
        reg.counter("serve_ticks").inc(10 * (i + 1))
        reg.gauge("queue_depth").set(0.0)
        reg.gauge("host_rss_mb").set(mb)
        t.snapshot(reg, step=10 * (i + 1))
        clk.advance(1.0)
        wall.advance(1.0)
    t.event("serve_end")
    t.close()


class TestRssTrend:
    """`obs doctor` on the host-memory ledger: `ru_maxrss` is a
    high-water mark, so the leak signal is a peak STILL RISING at the
    newest snapshots after a material climb — plateaued-after-warmup
    (the normal shape) must stay quiet."""

    def test_monotonic_climb_is_warned(self, tmp_path):
        write_rss_run(tmp_path / "telemetry.jsonl", "r1",
                      [400.0, 440.0, 480.0, 520.0])
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["verdict"] == "healthy"
        assert d["rss_trend"] == {"first_mb": 400.0, "last_mb": 520.0,
                                  "samples": 4}
        assert d["rss_warning"] is not None
        assert "host RSS climbing monotonically" in d["reason"]
        md = doctor.render_markdown(d)
        assert "host RSS" in md and "**climbing**" in md

    def test_plateaued_rss_stays_quiet(self, tmp_path):
        # material climb, but the peak froze over the last snapshots:
        # warmup growth, not a leak
        write_rss_run(tmp_path / "telemetry.jsonl", "r1",
                      [400.0, 520.0, 520.0, 520.0])
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["rss_warning"] is None
        assert "climbing" not in d["reason"]
        # the evidence row still renders, unflagged
        md = doctor.render_markdown(d)
        assert "host RSS" in md and "**climbing**" not in md

    def test_short_series_makes_no_claim(self, tmp_path):
        # two points cannot distinguish warmup from leak
        write_rss_run(tmp_path / "telemetry.jsonl", "r1", [400.0, 900.0])
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["rss_trend"]["samples"] == 2
        assert d["rss_warning"] is None

    def test_no_gauge_means_no_row(self, tmp_path):
        write_run(tmp_path / "telemetry.jsonl", "r1", 10.0)
        d = doctor.diagnose(tmp_path, now=1_100.0)
        assert d["rss_trend"] is None
        assert "host RSS" not in doctor.render_markdown(d)

    def test_live_heartbeat_pulse_carries_rss(self, tmp_path):
        """Satellite contract: every beat carries the process RSS (via
        getrusage — no new deps), and the tolerant reader passes it
        through untouched."""
        from hyperion_tpu.obs.heartbeat import Heartbeat, host_rss_mb

        hb = Heartbeat(tmp_path / "heartbeat.json", run="r1", every=1)
        hb.pulse(step=1, phase="serve")
        back = read_heartbeat(tmp_path / "heartbeat.json")
        assert isinstance(back["rss_mb"], (int, float))
        assert back["rss_mb"] > 0
        assert host_rss_mb() > 0


# -------------------------------------------------- telemetry contract


class TestRecordContract:
    """Pin the wire fields the offline tools rely on. A change that
    breaks these breaks `obs doctor`/`diff`/`summarize` on every stream
    already on disk — bump trace.SCHEMA_VERSION and migrate instead."""

    RESERVED = ("v", "kind", "name", "run", "proc", "step", "t_wall",
                "t_mono")

    def records(self, name):
        out = []
        for line in (FIXTURES / name / "telemetry.jsonl").read_text() \
                .splitlines():
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # the crashed fixture's torn tail, by design
        assert out, f"fixture {name} unreadable"
        return out

    @pytest.mark.parametrize(
        "name", ALL_FIXTURES + FLEET_FIXTURES + SIM_FIXTURES)
    def test_every_record_carries_envelope(self, name):
        for r in self.records(name):
            assert r["v"] == 1
            assert r["kind"] in ("span", "event", "snapshot")
            assert isinstance(r["name"], str)
            assert isinstance(r["run"], str)
            assert isinstance(r["proc"], int)
            assert isinstance(r["t_wall"], (int, float))
            assert isinstance(r["t_mono"], (int, float))
            assert r["step"] is None or isinstance(r["step"], int)

    # the fleet ROUTER stream is events-only (relays are threads, not
    # ticks) — only its replica streams join the span contract
    @pytest.mark.parametrize("name", ALL_FIXTURES + FLEET_FIXTURES[1:])
    def test_span_records(self, name):
        spans = [r for r in self.records(name) if r["kind"] == "span"]
        assert spans
        for s in spans:
            assert isinstance(s["dur_ms"], (int, float))
            assert isinstance(s["path"], str) and s["path"].endswith(s["name"])

    def test_snapshot_record_shape(self):
        (snap,) = [r for r in self.records("healthy")
                   if r["kind"] == "snapshot"]
        m = snap["metrics"]
        assert set(m) == {"counters", "gauges", "histograms", "labels"}
        # the gauges summarize/doctor/diff read
        for g in ("tokens_per_s", "mfu", "hbm_peak_mb"):
            assert g in m["gauges"]
        assert "step_time_ms" in m["histograms"]

    def test_health_event_shape(self):
        (ev,) = [r for r in self.records("nan") if r["name"] == "health"]
        assert ev["kind"] == "event"
        assert ev["anomaly"] in ("nonfinite_loss", "nonfinite_grad",
                                 "loss_spike", "grad_explosion",
                                 "step_stall")
        assert ev["fatal"] is True
        assert ev["action"] in ("warn", "checkpoint", "abort")

    @pytest.mark.parametrize(
        "name", ALL_FIXTURES + FLEET_FIXTURES + SIM_FIXTURES)
    def test_heartbeat_contract(self, name):
        hb = read_heartbeat(FIXTURES / name / "heartbeat.json")
        assert hb is not None
        for field, typ in (("v", int), ("schema", int), ("run", str),
                           ("pid", int),
                           ("proc", int), ("step", int), ("phase", str),
                           ("t_wall", (int, float)),
                           ("t_mono", (int, float)), ("beats", int)):
            assert isinstance(hb[field], typ), (name, field)

    @pytest.mark.parametrize(
        "name", ALL_FIXTURES + FLEET_FIXTURES + SIM_FIXTURES)
    def test_heartbeat_reader_tolerates_unknown_fields(self, name, tmp_path):
        """Live-plane payload growth (alerts, occupancy, whatever comes
        next) must never break an older reader: read_heartbeat returns
        the whole dict, no field whitelist, and the age helper keeps
        working with strangers in the record."""
        import time as _time

        from hyperion_tpu.obs.heartbeat import heartbeat_age_s

        hb = read_heartbeat(FIXTURES / name / "heartbeat.json")
        grown = {**hb, "alerts": ["ttft_p99"], "from_the_future": {"x": 1}}
        p = tmp_path / "heartbeat.json"
        p.write_text(json.dumps(grown))
        back = read_heartbeat(p)
        assert back["from_the_future"] == {"x": 1}
        assert back["phase"] == hb["phase"]
        assert heartbeat_age_s(back, now=_time.time()) is not None

    @pytest.mark.parametrize("name", ALL_FIXTURES)
    def test_summarize_reads_every_fixture(self, name):
        s = report.summarize(FIXTURES / name / "telemetry.jsonl")
        assert not s.get("error")
        assert s["steps"] >= 5


# ----------------------------------------------------------------- diff


# one case per `obs diff` gate: the metric and the shape it arrives in —
# a telemetry stream, `obs summarize --json` of one, or a trainer
# `*_summary.json` (the shapes `normalize` documents)
GATE_CASES = [
    ("step_time_p50_ms", "stream"),
    ("step_time_p99_ms", "summarize_json"),
    ("step_time_mean_ms", "trainer_summary"),
    ("tokens_per_s", "trainer_summary"),
    ("samples_per_s", "stream"),
    ("mfu", "summarize_json"),
    ("hbm_peak_mb", "trainer_summary"),
]
_TRAINER_SUMMARY_KEY = {"step_time_mean_ms": "step_ms",
                        "tokens_per_s": "tokens_per_s",
                        "hbm_peak_mb": "peak_hbm_mb"}


def _gate_input(shape, where, metric, value, capsys):
    """One `obs diff` input in `shape` whose `metric` reads `value`."""
    where.mkdir()
    if shape == "trainer_summary":
        path = where / "run_summary.json"
        path.write_text(json.dumps({_TRAINER_SUMMARY_KEY[metric]: value}))
        return path
    stream = where / "telemetry.jsonl"
    if metric.startswith("step_time_"):
        write_run(stream, "run", value)
    else:
        write_run(stream, "run", 10.0, gauges={metric: value})
    if shape == "stream":
        return stream
    assert report.main(["summarize", str(stream), "--json"]) == 0
    path = where / "summary.json"
    path.write_text(capsys.readouterr().out)
    return path


class TestDiff:
    def test_injected_step_time_regression_flagged(self, tmp_path, capsys):
        """The acceptance bar: a >=10%% injected step-time regression
        between two synthetic runs flips the exit code."""
        from hyperion_tpu.cli.main import main as cli_main

        a, b = tmp_path / "a", tmp_path / "b"
        a.mkdir(), b.mkdir()
        write_run(a / "telemetry.jsonl", "run_a", 10.0)
        write_run(b / "telemetry.jsonl", "run_b", 12.0)  # +20% step time
        rc = cli_main(["obs", "diff", str(a), str(b)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSED" in out and "step_time_p50_ms" in out

    def test_within_threshold_passes(self, tmp_path, capsys):
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        write_run(a, "run_a", 10.0)
        write_run(b, "run_b", 10.5)  # +5% < default 10%
        assert obs_diff.main([str(a), str(b)]) == 0
        assert "no regressions" in capsys.readouterr().out

    def test_throughput_direction_is_inverted(self, tmp_path):
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        write_run(a, "run_a", 10.0, tokens_per_s=4000.0)
        write_run(b, "run_b", 10.0, tokens_per_s=3000.0)  # -25% tok/s
        d = obs_diff.diff(obs_diff.load_summary(a),
                          obs_diff.load_summary(b))
        assert "tokens_per_s" in d["regressions"]
        # and an IMPROVEMENT the other way is not a regression
        d = obs_diff.diff(obs_diff.load_summary(b),
                          obs_diff.load_summary(a))
        assert "tokens_per_s" not in d["regressions"]

    def test_threshold_is_configurable(self, tmp_path):
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        write_run(a, "run_a", 10.0)
        write_run(b, "run_b", 10.5)
        assert obs_diff.main([str(a), str(b), "--threshold", "0.01"]) == 1

    def test_normalize_trainer_summary(self):
        m = obs_diff.normalize({"step_ms": 42.0, "tokens_per_s": 1000.0,
                                "peak_hbm_mb": 13580.0})
        assert m["step_time_mean_ms"] == 42.0
        assert m["hbm_peak_mb"] == 13580.0

    def test_normalize_drops_nonfinite_and_unknown(self):
        assert obs_diff.normalize({"tokens_per_s": float("nan"),
                                   "unknown_key": 3}) == {}

    def test_unreadable_input_exits_2(self, tmp_path, capsys):
        good = tmp_path / "a.jsonl"
        write_run(good, "r", 10.0)
        assert obs_diff.main([str(good),
                              str(tmp_path / "missing.json")]) == 2

    @pytest.mark.parametrize("metric,shape", GATE_CASES)
    def test_gate_flags_only_its_bad_direction(self, tmp_path, capsys,
                                               metric, shape):
        """Every gate, fed in a shape a command of this repo writes: a
        25% move in the metric's bad direction is flagged and flips the
        exit code, the same move in its good direction is quiet."""
        base = 0.4 if metric == "mfu" else 100.0
        lo = _gate_input(shape, tmp_path / "lo", metric, base, capsys)
        hi = _gate_input(shape, tmp_path / "hi", metric, base * 1.25,
                         capsys)
        good, bad = ((hi, lo), (lo, hi)) \
            if obs_diff.METRICS[metric] == "lower" else ((lo, hi), (hi, lo))
        assert obs_diff.main([*map(str, bad), "--json"]) == 1
        assert metric in json.loads(capsys.readouterr().out)["regressions"]
        assert obs_diff.main([*map(str, good), "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["regressions"] == []

    def test_every_gate_has_a_producing_shape(self):
        """The rule at the top of `METRICS`: a gate exists only if a
        command in this repo emits it. A new gate needs a case above,
        built in a shape some command writes, or this fails."""
        assert sorted(m for m, _ in GATE_CASES) == sorted(obs_diff.METRICS)


# ------------------------------------------- summarize failure satellite


class TestSummarizeEmptyStreams:
    def test_empty_file_one_line_nonzero(self, tmp_path, capsys):
        p = tmp_path / "telemetry.jsonl"
        p.write_text("")
        assert report.main(["summarize", str(p)]) == 1
        cap = capsys.readouterr()
        assert cap.out == ""
        assert len(cap.err.strip().splitlines()) == 1
        assert "no parseable records" in cap.err

    def test_garbage_only_file_nonzero(self, tmp_path, capsys):
        p = tmp_path / "telemetry.jsonl"
        p.write_text("not json\n{{{\n")
        assert report.main(["summarize", str(p)]) == 1
        assert "no parseable records" in capsys.readouterr().err

    def test_filtered_to_empty_run_nonzero(self, tmp_path, capsys):
        p = tmp_path / "telemetry.jsonl"
        write_run(p, "real_run", 10.0)
        assert report.main(["summarize", str(p), "--run", "ghost"]) == 1
        cap = capsys.readouterr()
        assert cap.out == ""  # never an all-zero report
        assert "ghost" in cap.err and "--list-runs" in cap.err

    def test_json_mode_also_errors_cleanly(self, tmp_path, capsys):
        p = tmp_path / "telemetry.jsonl"
        p.write_text("")
        assert report.main(["summarize", str(p), "--json"]) == 1
        assert capsys.readouterr().out == ""


class TestExpertRowsByForm:
    """An expert model's tick records carry `expert_rows_kernel` /
    `expert_rows_ragged` (serve/engine.py `_count_experts`): flows the
    snapshot sums over the window's steps, and a sentence of doctor's
    `host tick profile` row."""

    @pytest.mark.parametrize("steps, summed, sentence", [
        # a tick of 48 slots x 6 picks x 8 layers through the kernel; a
        # step that also ran a 512-token chunk on `ragged_dot`
        ([{"expert_rows_kernel": 2304, "expert_rows_ragged": 0},
          {"expert_rows_kernel": 2304, "expert_rows_ragged": 24576}],
         {"expert_rows_kernel": 4608, "expert_rows_ragged": 24576},
         "experts: 4608 row(s) through the grouped kernel, "
         "24576 through ragged_dot"),
        # off a TPU: every row through `ragged_dot`
        ([{"expert_rows_kernel": 0, "expert_rows_ragged": 144}] * 3,
         {"expert_rows_kernel": 0, "expert_rows_ragged": 432},
         "experts: 0 row(s) through the grouped kernel, "
         "432 through ragged_dot"),
        # a model without experts, or a process that predates the
        # counters: nothing is said
        ([{}], {}, None),
    ], ids=["tick_on_the_kernel", "all_on_ragged_dot", "no_experts"])
    def test_rolled_up_and_said_in_words(self, tmp_path, steps, summed,
                                         sentence):
        from hyperion_tpu.obs.tickprof import TickProfiler

        clk = VirtualClock()
        tp = TickProfiler(wall=clk)
        for n, c in enumerate(steps):
            tp.record(n, {"device": 0.020}, 0.021,
                      {"kv_tokens": 100, "prefill_tokens": 0, **c})
            clk.advance(0.021)
        snap = tp.snapshot(now=clk.t)
        assert {k: v for k, v in snap["counters"].items()
                if k.startswith("expert_rows")} == summed
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        row = next(ln for ln in doctor.render_markdown(
                       doctor.diagnose(tmp_path)).splitlines()
                   if ln.startswith("| host tick profile"))
        if sentence:
            assert sentence in row
        else:
            assert "grouped kernel" not in row


class TestStateRowsVisited:
    """A recurrent model's tick records carry `state_rows_visited` /
    `state_rows_held` (serve/engine.py `_count_state`): flows the
    snapshot sums over the window's steps, and a sentence of doctor's
    `host tick profile` row that names the update's form."""

    @pytest.mark.parametrize("steps, summed, sentence", [
        # 128 slots x 3 recurrent layers; the kernel visits the rows the
        # ticks advanced, 54 and 54 live rows a layer
        ([{"state_rows_visited": 162, "state_rows_held": 384}] * 2,
         {"state_rows_visited": 324, "state_rows_held": 768},
         "state update: 324 of 768 rows visited (kernel)"),
        # the dense form (off a TPU) reads and writes every slot's row
        ([{"state_rows_visited": 9, "state_rows_held": 9}] * 3,
         {"state_rows_visited": 27, "state_rows_held": 27},
         "state update: 27 of 27 rows visited (every row)"),
        # a model without recurrent layers: nothing is said
        ([{}], {}, None),
    ], ids=["kernel", "every_row", "no_recurrent_layers"])
    def test_rolled_up_and_said_in_words(self, tmp_path, steps, summed,
                                         sentence):
        from hyperion_tpu.obs.tickprof import TickProfiler

        clk = VirtualClock()
        tp = TickProfiler(wall=clk)
        for n, c in enumerate(steps):
            tp.record(n, {"device": 0.020}, 0.021,
                      {"kv_tokens": 100, "prefill_tokens": 0, **c})
            clk.advance(0.021)
        snap = tp.snapshot(now=clk.t)
        assert {k: v for k, v in snap["counters"].items()
                if k.startswith("state_rows")} == summed
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        row = next(ln for ln in doctor.render_markdown(
                       doctor.diagnose(tmp_path)).splitlines()
                   if ln.startswith("| host tick profile"))
        if sentence:
            assert sentence in row
        else:
            assert "state update" not in row


class TestPromptPositionsByRead:
    """Every tick record carries `prompt_positions_tiled` /
    `prompt_positions_gather` (serve/engine.py `_count_prompt`): flows
    the snapshot sums over the window's steps, and a sentence of
    doctor's `host tick profile` row where a window was prefilled."""

    @pytest.mark.parametrize("steps, summed, sentence", [
        # a 2048-position bucket and a 512-position chunk through the
        # tiled kernel, the 32-position suffix of a prefix hit gathered
        ([{"prompt_positions_tiled": 2048, "prompt_positions_gather": 0},
          {"prompt_positions_tiled": 512, "prompt_positions_gather": 32}],
         {"prompt_positions_tiled": 2560, "prompt_positions_gather": 32},
         "prompt windows: 2560 position(s) through the tiled kernel, "
         "32 through the gather's softmax"),
        # off a TPU: every window gathers
        ([{"prompt_positions_tiled": 0, "prompt_positions_gather": 24}] * 2,
         {"prompt_positions_tiled": 0, "prompt_positions_gather": 48},
         "prompt windows: 0 position(s) through the tiled kernel, "
         "48 through the gather's softmax"),
        # steps that prefilled nothing, or a process that predates the
        # counters: nothing is said
        ([{"prompt_positions_tiled": 0, "prompt_positions_gather": 0}],
         {"prompt_positions_tiled": 0, "prompt_positions_gather": 0}, None),
        ([{}], {}, None),
    ], ids=["wide_tiled_narrow_gathered", "all_gathered", "ticks_only",
            "no_counters"])
    def test_rolled_up_and_said_in_words(self, tmp_path, steps, summed,
                                         sentence):
        from hyperion_tpu.obs.tickprof import TickProfiler

        clk = VirtualClock()
        tp = TickProfiler(wall=clk)
        for n, c in enumerate(steps):
            tp.record(n, {"device": 0.020}, 0.021,
                      {"kv_tokens": 100, "prefill_tokens": 0, **c})
            clk.advance(0.021)
        snap = tp.snapshot(now=clk.t)
        assert {k: v for k, v in snap["counters"].items()
                if k.startswith("prompt_positions")} == summed
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        row = next(ln for ln in doctor.render_markdown(
                       doctor.diagnose(tmp_path)).splitlines()
                   if ln.startswith("| host tick profile"))
        if sentence:
            assert sentence in row
        else:
            assert "tiled kernel" not in row
