"""Live observability plane: windowed registry math, exposition socket
round trips, SLO burn-rate hysteresis, and the `obs top` dashboard.

Everything here is host-only — fake clocks, unix sockets, JSONL files;
no jax import, zero jit compiles. The engine-integration half (a live
engine's exposition payload, the seeded overload drill that raises and
clears a real alert) lives in tests/test_serve.py on the suite's
already-compiled shapes.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import pytest

from hyperion_tpu.obs import slo as slo_mod
from hyperion_tpu.obs import top as top_mod
from hyperion_tpu.obs.export import (
    MetricsExporter,
    exposition_path,
    read_exposition,
)
from hyperion_tpu.obs.registry import MetricsRegistry, percentile
from hyperion_tpu.obs.trace import Tracer
from hyperion_tpu.utils.clock import VirtualClock

FIXTURES = Path(__file__).parent / "data" / "telemetry"
REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------ windowed math


class TestWindowedInstruments:
    def test_histogram_window_matches_offline_percentile(self):
        """The windowed p99 over a window covering EVERYTHING must
        equal the offline nearest-rank percentile the timeline tools
        compute — one percentile definition, live and post-hoc."""
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        h = reg.histogram("ttft_ms")
        vals = [float(7 * i % 53) for i in range(40)]
        for v in vals:
            h.observe(v)
            clk.advance(0.1)
        w = h.windowed(1000.0)
        assert w["count"] == 40
        for p in (50, 95, 99):
            assert w[f"p{p}"] == percentile(vals, p)
        assert w["mean"] == pytest.approx(sum(vals) / len(vals))

    def test_histogram_window_drops_old_observations(self):
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        h = reg.histogram("x")
        h.observe(1000.0)          # t=100
        clk.advance(50.0)
        for _ in range(5):
            h.observe(10.0)        # t=150
        # 10s window at t=150: only the recent 10s, the 1000 is gone
        w = h.windowed(10.0)
        assert w["count"] == 5 and w["p99"] == 10.0 and w["max"] == 10.0
        # lifetime summary still remembers the spike
        assert h.summary()["max"] == 1000.0
        # empty window reports count 0, never stale numbers
        clk.advance(100.0)
        assert h.windowed(10.0) == {"count": 0}

    def test_counter_windowed_delta(self):
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        c = reg.counter("tokens")
        c.inc(5)
        clk.advance(30.0)
        c.inc(7)
        assert c.value == 12
        assert c.windowed_delta(10.0) == 7      # only the recent inc
        assert c.windowed_delta(60.0) == 12
        clk.advance(100.0)
        assert c.windowed_delta(60.0) == 0

    def test_gauge_windowed_envelope(self):
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        g = reg.gauge("queue_depth")
        g.set(3.0)
        clk.advance(5.0)
        g.set(9.0)
        w = g.windowed(60.0)
        assert w == {"count": 2, "last": 9.0, "mean": 6.0,
                     "min": 3.0, "max": 9.0}
        g.set(None)  # None never enters the ring
        assert g.windowed(60.0)["count"] == 2

    def test_windowed_snapshot_shape(self):
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        reg.counter("tokens").inc(30)
        reg.gauge("q").set(2.0)
        reg.histogram("ttft_ms").observe(12.0)
        snap = reg.windowed_snapshot(60.0)
        assert snap["window_s"] == 60.0
        assert snap["counters"]["tokens"] == {"delta": 30.0,
                                              "covered_s": 60.0,
                                              "per_s": 0.5}
        assert snap["histograms"]["ttft_ms"]["p99"] == 12.0
        assert snap["gauges"]["q"]["last"] == 2.0
        # the lifetime snapshot() wire shape is untouched (pinned
        # elsewhere by the fixture contract): windows are a SEPARATE
        # section, not a new key inside it
        assert set(reg.snapshot()) == {"counters", "gauges",
                                       "histograms", "labels"}

    def test_truncated_ring_reports_honest_rates(self):
        """A counter busier than its ring cap covers less history than
        the asked-for window; the rate must divide by the COVERED
        span, not the window, or 100 tokens/s reads as 13.65."""
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        c = reg.counter("tokens")
        for _ in range(10_000):          # 100/s for 100s; ring cap 8192
            c.inc()
            clk.advance(0.01)
        row = reg.windowed_snapshot(600.0)["counters"]["tokens"]
        assert row["delta"] == 8192.0            # what the ring holds
        assert row["covered_s"] == pytest.approx(81.92, rel=0.01)
        assert row["per_s"] == pytest.approx(100.0, rel=0.01)
        assert c.covered_window_s(600.0) == pytest.approx(81.92,
                                                          rel=0.01)
        # a young/idle counter genuinely covers the whole window
        q = reg.counter("quiet")
        q.inc(3)
        assert q.covered_window_s(600.0) == 600.0

    def test_counter_ratio_clamps_to_common_covered_span(self):
        """Cross-counter ratios (reject rate, availability) must be
        computed over the span EVERY involved ring still covers — a
        truncated busy accept stream against an untruncated rare
        reject stream would otherwise inflate the rate."""
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        rej, acc = reg.counter("serve_rejected"), \
            reg.counter("serve_accepted")
        for _ in range(6):               # rejects early, then none
            rej.inc()
            clk.advance(1.0)
        for _ in range(9_000):           # busy accepts: ring wraps
            acc.inc()
            clk.advance(0.066)
        # naive windowed deltas over 600s would count all 6 rejects
        # against only the RETAINED accepts — an inflated rate
        assert rej.windowed_delta(600.0) == 6.0
        assert acc.covered_window_s(600.0) < 600.0
        # the common covered span excludes the early rejects entirely:
        # over the history every ring still holds, zero rejects
        assert slo_mod.serve_window_value(reg, "reject_rate", 600.0) \
            == 0.0


# ------------------------------------------------- burn-rate alerting


def _mon(clk, reg, *, fast=10.0, slow=60.0, target=100.0):
    return slo_mod.SLOMonitor(
        slo_mod.standard_targets(ttft_p99_ms=target), reg,
        fast_s=fast, slow_s=slow, eval_every_s=0.0, clock=clk)


class TestBurnRate:
    def test_raise_needs_both_windows(self):
        """A fast-window spike alone never pages: the slow window must
        also be burning. Feed one burst, evaluate before the slow
        window has enough history... both windows see the same burst
        here, so instead pin the asymmetric case: bad-fast/good-slow."""
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        mon = _mon(clk, reg, fast=10.0, slow=60.0)
        h = reg.histogram("ttft_ms")
        # 55s of healthy traffic, then a 5s spike: fast window is all
        # spike (burn 4x), slow window p99 still rides the spike...
        # nearest-rank p99 over 60s needs >1% bad to move, so 56
        # good + 4 bad keeps slow p99 high — use the mass instead:
        # 56 good then 4 bad puts slow p99 AT the bad value only when
        # bad >= 1% of count; keep good dominant enough that slow p99
        # stays good.
        for _ in range(600):
            h.observe(10.0)
            clk.advance(0.1)       # 60s of good, 600 samples
        for _ in range(5):
            h.observe(400.0)
            clk.advance(0.2)       # 1s of bad: fast p99 flips, slow not
        assert reg.histogram("ttft_ms").windowed(10.0)["p99"] == 400.0
        assert reg.histogram("ttft_ms").windowed(60.0)["p99"] == 10.0
        assert mon.evaluate() == []          # slow window vetoes
        assert not mon.active

    def test_overload_raises_once_then_clears_once(self):
        """THE seeded drill: sustained overload raises exactly one
        alert (hovering at 4x burn never re-raises), the load drops,
        and the alert clears exactly once after BOTH windows drain —
        no flapping anywhere in between."""
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        mon = _mon(clk, reg, fast=10.0, slow=30.0)
        tr_log = []
        for i in range(40):                  # 40s of 400ms TTFTs
            reg.histogram("ttft_ms").observe(400.0)
            clk.advance(1.0)
            tr_log += mon.evaluate()
        assert [t["kind"] for t in tr_log] == ["raised"]
        assert tr_log[0]["alert"] == "ttft_p99"
        assert tr_log[0]["burn_fast"] == pytest.approx(4.0)
        assert mon.active_names() == ["ttft_p99"]
        for i in range(60):                  # silence: windows drain
            clk.advance(1.0)
            tr_log += mon.evaluate()
        kinds = [t["kind"] for t in tr_log]
        assert kinds == ["raised", "cleared"], kinds
        assert not mon.active
        assert tr_log[-1]["active_s"] > 0

    def test_hysteresis_holds_at_the_threshold(self):
        """Values hovering AT the threshold (burn 1.0) raise once and
        stay raised: clearing demands burn <= clear_ratio (0.9) in
        both windows, so threshold-hugging load cannot flap."""
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        mon = _mon(clk, reg, fast=5.0, slow=15.0, target=100.0)
        transitions = []
        for i in range(60):
            reg.histogram("ttft_ms").observe(100.0)   # burn exactly 1.0
            clk.advance(1.0)
            transitions += mon.evaluate()
        assert [t["kind"] for t in transitions] == ["raised"]
        # drop to just above the clear line: still holds
        for i in range(30):
            reg.histogram("ttft_ms").observe(95.0)    # burn 0.95 > 0.9
            clk.advance(1.0)
            transitions += mon.evaluate()
        assert [t["kind"] for t in transitions] == ["raised"]
        # comfortably under the clear ratio: exactly one clear
        for i in range(30):
            reg.histogram("ttft_ms").observe(50.0)
            clk.advance(1.0)
            transitions += mon.evaluate()
        assert [t["kind"] for t in transitions] == ["raised", "cleared"]

    def test_reject_rate_and_availability_metrics(self):
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        for _ in range(8):
            reg.counter("serve_accepted").inc()
            reg.counter("serve_completed").inc()
        reg.counter("serve_rejected").inc(2)
        assert slo_mod.serve_window_value(
            reg, "reject_rate", 60.0, clk()) == pytest.approx(0.2)
        assert slo_mod.serve_window_value(
            reg, "availability", 60.0, clk()) == pytest.approx(0.8)
        # empty window: None, which burns 0 — silence is compliance
        clk.advance(120.0)
        assert slo_mod.serve_window_value(reg, "reject_rate", 60.0,
                                          clk()) is None
        assert slo_mod.burn("reject_rate", None, 0.05) == 0.0
        assert slo_mod.burn("availability", 0.95, 0.99) \
            == pytest.approx(5.0)
        with pytest.raises(ValueError):
            slo_mod.serve_window_value(reg, "nope", 60.0, clk())

    def test_evaluate_is_rate_limited(self):
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        mon = slo_mod.SLOMonitor(
            slo_mod.standard_targets(ttft_p99_ms=100.0), reg,
            fast_s=10.0, slow_s=30.0, clock=clk)  # default cadence
        for _ in range(6):               # past the quantile floor
            reg.histogram("ttft_ms").observe(400.0)
        assert mon.evaluate() != []      # first call always evaluates
        clk.advance(0.01)
        reg.histogram("ttft_ms").observe(400.0)
        assert mon.evaluate() == []      # inside the gap: no work
        assert mon.active_names() == ["ttft_p99"]

    def test_single_bad_request_never_pages(self):
        """The quantile evidence floor: one cold 600ms TTFT in an
        otherwise-idle window is NOT a p99 breach — the windowed p99
        of one sample is that sample, and paging on it would break
        the 'single bad second never pages' contract."""
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        mon = _mon(clk, reg, fast=10.0, slow=30.0, target=500.0)
        reg.histogram("ttft_ms").observe(600.0)  # one cold request
        assert mon.evaluate() == [] and not mon.active
        # sustained slow traffic past the floor DOES page
        for _ in range(slo_mod.QUANTILE_MIN_COUNT):
            clk.advance(1.0)
            reg.histogram("ttft_ms").observe(600.0)
        (tr,) = mon.evaluate()
        assert tr["kind"] == "raised"

    def test_publish_emits_standard_vocabulary(self, tmp_path):
        clk = VirtualClock()
        reg = MetricsRegistry(clock=clk)
        mon = _mon(clk, reg, fast=5.0, slow=10.0)
        t = Tracer(tmp_path / "telemetry.jsonl", run="slo_t", proc=0)
        for _ in range(6):
            reg.histogram("ttft_ms").observe(400.0)
        trs = mon.evaluate()
        slo_mod.publish(trs, t, reg, step=3, active=len(mon.active))
        t.close()
        recs = [json.loads(line) for line in
                (tmp_path / "telemetry.jsonl").read_text().splitlines()]
        (ev,) = [r for r in recs if r["name"] == "alert_raised"]
        assert ev["alert"] == "ttft_p99" and ev["step"] == 3
        assert ev["threshold"] == 100.0 and ev["burn_fast"] == 4.0
        assert reg.counter("serve_alerts_raised").value == 1
        assert reg.gauge("serve_alerts_active").value == 1.0


# -------------------------------------------------- exposition socket


class TestExposition:
    def test_round_trip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("tokens").inc(42)
        reg.histogram("ttft_ms").observe(7.0)

        def payload():
            return {"role": "engine", "phase": "serve", "active": 1,
                    "metrics": reg.snapshot(),
                    "windows": reg.windowed_snapshot(60.0)}

        sock = exposition_path(tmp_path)
        assert sock == tmp_path / "obs.sock"
        with MetricsExporter(sock, payload, label="t-obs") as exp:
            assert exp.enabled
            doc = read_exposition(sock)
            assert doc["kind"] == "exposition" and doc["v"] == 1
            assert doc["phase"] == "serve"
            assert doc["metrics"]["counters"]["tokens"] == 42
            assert doc["windows"]["histograms"]["ttft_ms"]["p99"] == 7.0
            assert isinstance(doc["pid"], int)
            # a second request gets a fresh answer (one per connection)
            assert read_exposition(sock) is not None
        # closed: socket unlinked, reads degrade to None
        assert not sock.exists()
        assert read_exposition(sock) is None

    def test_payload_error_answers_instead_of_killing(self, tmp_path):
        def bad():
            raise RuntimeError("boom")

        with MetricsExporter(tmp_path / "obs.sock", bad) as exp:
            doc = read_exposition(tmp_path / "obs.sock")
            assert "boom" in doc["error"]
            assert exp.enabled  # the exporter survived its own bug

    def test_stale_socket_file_is_reclaimed(self, tmp_path):
        sock = tmp_path / "obs.sock"
        sock.touch()  # a crash leftover nobody is listening on
        with MetricsExporter(sock, lambda: {"ok": True}):
            assert read_exposition(sock)["ok"] is True

    def test_read_nothing_is_none(self, tmp_path):
        assert read_exposition(tmp_path / "absent.sock") is None

    def test_refused_exporter_close_leaves_owner_socket(self, tmp_path):
        """A second exporter pointed at a LIVE socket is refused and
        degrades — and its close() must NOT unlink the rightful
        owner's socket on the way out."""
        sock = tmp_path / "obs.sock"
        first = MetricsExporter(sock, lambda: {"who": "first"}).start()
        try:
            second = MetricsExporter(sock,
                                     lambda: {"who": "second"}).start()
            assert not second.enabled     # refused, degraded
            second.close()
            doc = read_exposition(sock)   # the owner still answers
            assert doc is not None and doc["who"] == "first"
        finally:
            first.close()
        assert not sock.exists()          # the binder cleaned up

    def test_exposition_path_from_file_anchor(self, tmp_path):
        assert exposition_path(tmp_path / "heartbeat.json") \
            == tmp_path / "obs.sock"
        assert exposition_path(tmp_path / "telemetry.jsonl") \
            == tmp_path / "obs.sock"


# ------------------------------------------------------------ obs top


def _fake_fleet(base: Path) -> None:
    """A router-layout dir: router heartbeat at the base, replica_0
    live behind a real exposition socket, replica_1 dead (stale
    heartbeat only), replica_2 never beat."""
    base.mkdir(parents=True, exist_ok=True)
    now = time.time()
    (base / "heartbeat.json").write_text(json.dumps(
        {"v": 1, "schema": 1, "run": "route_x", "pid": 42, "proc": 0,
         "step": 9, "phase": "route", "t_wall": now, "t_mono": 1.0,
         "beats": 3, "active": 1, "queue": 0, "alerts": []}))
    for i in range(3):
        (base / f"replica_{i}").mkdir(exist_ok=True)
    (base / "replica_1" / "heartbeat.json").write_text(json.dumps(
        {"v": 1, "schema": 1, "run": "serve_r1_1", "pid": 43, "proc": 1,
         "step": 17, "phase": "serve", "t_wall": now - 3600,
         "t_mono": 5.0, "beats": 9, "active": 2, "queue": 4,
         "alerts": ["ttft_p99"]}))


@pytest.fixture()
def live_fleet(tmp_path):
    base = tmp_path / "fleet"
    _fake_fleet(base)
    reg = MetricsRegistry()
    reg.counter("tokens").inc(120)
    reg.histogram("ttft_ms").observe(12.5)

    def payload():
        return {"role": "engine", "run": "serve_r0_1", "phase": "serve",
                "tick": 33, "active": 1, "slots": 2, "occupancy": 0.5,
                "queue": 1, "draining": False, "brownout": True,
                "blocks_in_use": 6, "blocks_free": 10,
                "alerts": ["reject_rate"],
                "metrics": reg.snapshot(),
                "windows": reg.windowed_snapshot(60.0)}

    exp = MetricsExporter(base / "replica_0" / "obs.sock",
                          payload).start()
    try:
        yield base
    finally:
        exp.close()


class TestObsTop:
    def test_discovery_orders_router_then_replicas(self, live_fleet):
        names = [n for n, _ in top_mod.discover(live_fleet)]
        assert names == ["router", "replica 0", "replica 1",
                         "replica 2"]

    def test_once_json_rows(self, live_fleet, capsys):
        from hyperion_tpu.cli.main import main as cli_main

        rc = cli_main(["obs", "top", str(live_fleet), "--once", "--json",
                       "--stale-s", "30"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        rows = {r["name"]: r for r in doc["rows"]}
        assert set(rows) == {"router", "replica 0", "replica 1",
                             "replica 2"}
        for r in doc["rows"]:   # the stable key contract
            assert set(top_mod.ROW_KEYS) <= set(r)
        live = rows["replica 0"]
        assert live["source"] == "socket" and live["state"] == "live"
        assert live["occupancy"] == 0.5 and live["queue"] == 1
        assert live["ttft_p99_ms"] == 12.5
        assert live["tokens_per_s"] == 2.0      # 120 tokens / 60s window
        assert live["brownout"] is True
        assert live["alerts"] == ["reject_rate"]
        assert live["blocks_in_use"] == 6
        dead = rows["replica 1"]
        assert dead["source"] == "heartbeat" and dead["state"] == "dead"
        assert dead["active"] == 2 and dead["queue"] == 4
        assert dead["alerts"] == ["ttft_p99"]
        assert dead["age_s"] > 1000
        assert rows["replica 2"]["state"] == "no heartbeat"
        assert rows["router"]["state"] == "beating"  # hb fresh, no sock

    def test_render_flags_dead_and_alerts(self, live_fleet):
        rows = top_mod.sample_all(live_fleet, stale_s=30.0)
        out = top_mod.render(rows, str(live_fleet), window_s=60.0,
                             color=False)
        assert "replica 1" in out and "dead" in out
        assert "reject_rate" in out
        assert "DEAD:" in out and "alerts firing:" in out

    def test_empty_target_exits_2(self, tmp_path, capsys):
        assert top_mod.main([str(tmp_path / "nothing"), "--once"]) == 2
        assert "nothing to watch" in capsys.readouterr().err

    def test_json_without_once_exits_2(self, live_fleet, capsys):
        assert top_mod.main([str(live_fleet), "--json"]) == 2
        assert "--once" in capsys.readouterr().err

    def test_row_keys_pin_isolation_columns(self):
        """PR 14 column contract: per-class queue depth and the `act`
        cell are part of ROW_KEYS (CI parses --json rows by key), and
        the exposition mapping fills them."""
        for key in ("queue_interactive", "queue_batch", "act"):
            assert key in top_mod.ROW_KEYS
        row = {k: None for k in top_mod.ROW_KEYS}
        exp = {"phase": "serve", "tick": 5, "active": 1, "slots": 2,
               "queue": 3, "queue_by_class": {"interactive": 1,
                                              "batch": 2},
               "act": {"class_brownout": True, "chunking": 2}}
        out = top_mod._row_from_exposition(dict(row), exp)
        assert out["queue_interactive"] == 1 and out["queue_batch"] == 2
        assert out["act"] == "cbrown+chunk:2"
        # a router-side payload renders steering + fleet posture
        assert top_mod._act_cell(
            {"enabled": True, "steered": [0, 2], "fleet": 3,
             "max_replicas": 4}) == "steer:0,2+fleet:3/4"
        # carrying the payload while idle reads '-', no payload None
        assert top_mod._act_cell({"enabled": True, "steered": []}) == "-"
        assert top_mod._act_cell({}) is None
        # the render pipeline accepts the new columns end to end
        out.update(name="replica 0", dir="x", source="socket",
                   state="live", alerts=[], age_s=0.0)
        text = top_mod.render([out], "x", window_s=60.0, color=False)
        assert "q i/b" in text and "cbrown+chunk:2" in text

    def test_smoke_script_top_invocation_parses(self):
        """Flag-drift guard (the capture-script pattern): the smoke
        script's `obs top` probe must parse against the real arg
        surface."""
        import re
        import shlex

        script = (REPO / "scripts" / "serve_smoke.sh").read_text()
        script = re.sub(r"\\\n\s*", " ", script)
        calls = re.findall(
            r"python -m hyperion_tpu\.cli\.main obs top\s+(.*)", script)
        assert calls, "serve_smoke.sh lost its obs top probe"
        for call in calls:
            toks = shlex.split(call.split(">")[0])
            args = top_mod.build_parser().parse_args(
                [re.sub(r"\$\{?\w+\}?", "x", t) for t in toks])
            assert args.once and args.json  # the scripted probe mode


# ----------------------------------------- doctor + diff consumption


class TestAlertConsumers:
    def test_doctor_names_cleared_alert_on_golden_fixture(self):
        from hyperion_tpu.obs import doctor

        d = doctor.diagnose(FIXTURES / "slo")
        assert d["verdict"] == "healthy"
        assert "slo:" in d["reason"] and "ttft_p99" in d["reason"]
        (row,) = d["slo_alerts"]
        assert row["alert"] == "ttft_p99"
        assert row["raised"] == 1 and row["cleared"] == 1
        assert row["active"] is False
        assert d["serve"]["alerts_raised"] == 1
        md = doctor.render_markdown(d)
        assert "SLO alert `ttft_p99`" in md and "(cleared)" in md

    def test_doctor_flags_still_firing_alert(self, tmp_path):
        from hyperion_tpu.obs import doctor

        t = Tracer(tmp_path / "telemetry.jsonl", run="fire", proc=0)
        t.event("serve_start", slots=2)
        t.event("alert_raised", alert="reject_rate",
                metric="reject_rate", threshold=0.05, fast=0.4,
                slow=0.3, burn_fast=8.0, burn_slow=6.0)
        t.close()
        d = doctor.diagnose(tmp_path)
        assert "FIRING" in d["reason"] and "reject_rate" in d["reason"]
        assert d["slo_alerts"][0]["active"] is True
        assert "**FIRING**" in doctor.render_markdown(d)
        # exit-code contract unchanged: a firing alert is evidence on
        # the verdict, not a new verdict
        assert d["verdict"] in ("running", "hung")

    def test_doctor_flap_that_ends_firing_counts_its_clears(
            self, tmp_path):
        from hyperion_tpu.obs import doctor

        t = Tracer(tmp_path / "telemetry.jsonl", run="flap", proc=0)
        for name in ("alert_raised", "alert_cleared", "alert_raised"):
            t.event(name, alert="ttft_p99", metric="ttft_p99_ms",
                    threshold=100.0, fast=400.0, active_s=1.0)
        t.close()
        d = doctor.diagnose(tmp_path)
        (row,) = d["slo_alerts"]
        assert row["raised"] == 2 and row["cleared"] == 1
        assert row["active"] is True
        # the incident text must not claim "never cleared"
        assert "cleared 1x, re-raised" in d["reason"]
        assert "never cleared" not in d["reason"]

    def test_doctor_json_carries_alert_keys(self, capsys):
        from hyperion_tpu.obs import doctor

        assert doctor.main([str(FIXTURES / "slo"), "--json"]) == 0
        d = json.loads(capsys.readouterr().out)
        for key in ("verdict", "reason", "serve", "slo_alerts",
                    "slo_incidents", "fleet", "heartbeat"):
            assert key in d
        assert d["slo_alerts"][0]["alert"] == "ttft_p99"

    def test_diff_json_stable_keys(self, tmp_path, capsys):
        """The machine-readable satellite: `obs diff --json` keys are
        a stable contract (CI parses them), exit codes unchanged."""
        from hyperion_tpu.obs import diff as obs_diff

        a, b = tmp_path / "a.json", tmp_path / "b.json"
        a.write_text(json.dumps({"step_ms": 10.0, "tokens_per_s": 100.0}))
        b.write_text(json.dumps({"step_ms": 20.0, "tokens_per_s": 100.0}))
        rc = obs_diff.main([str(a), str(b), "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1  # regression still flips the exit code
        for key in ("a", "b", "threshold_pct", "rows", "regressions",
                    "comparable_metrics"):
            assert key in doc
        assert doc["regressions"] == ["step_time_mean_ms"]


# --------------------------- introspection plane (PR-13, host-only)


class TestTickProfiler:
    def test_snapshot_dominates_and_derives_other(self):
        from hyperion_tpu.obs.tickprof import TickProfiler

        clk = VirtualClock()
        tp = TickProfiler(wall=clk)
        for i in range(4):
            tp.record(i, {"device": 0.006, "journal": 0.002}, 0.010)
            clk.advance(1.0)
        snap = tp.snapshot(window_s=60.0, now=clk.t)
        assert snap["ticks"] == 4 and snap["dominant"] == "device"
        assert snap["segments"]["device"]["frac"] == pytest.approx(0.6)
        # unattributed host time surfaces as "other", never vanishes
        assert snap["segments"]["other"]["s"] == pytest.approx(0.008)
        assert snap["total_s"] == pytest.approx(0.040)

    def test_window_cut_and_tail_bound(self):
        from hyperion_tpu.obs.tickprof import TickProfiler

        clk = VirtualClock()
        tp = TickProfiler(capacity=8, wall=clk)
        for i in range(20):
            tp.record(i, {"slo": 0.001}, 0.001)
            clk.advance(10.0)
        # ring bounded at capacity, tail bounded at n
        assert len(tp.tail(100)) == 8
        assert [r["tick"] for r in tp.tail(3)] == [17, 18, 19]
        # only the last 25s of records land in the window
        snap = tp.snapshot(window_s=25.0, now=clk.t)
        assert snap["ticks"] == 2
        assert snap["dominant"] == "slo"

    def test_empty_snapshot_is_nulls_not_crashes(self):
        from hyperion_tpu.obs.tickprof import TickProfiler

        snap = TickProfiler().snapshot()
        assert snap["ticks"] == 0 and snap["dominant"] is None
        assert snap["dominant_frac"] is None and snap["segments"] == {}


class _FakeSpans:
    """Stands in for `utils/profiling.annotate`: records what opened
    and closed, in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        outer = self

        class _Span:
            def __enter__(self):
                outer.log.append(("open", name, args))

            def __exit__(self, *exc):
                outer.log.append(("close", name))

        return _Span()


class TestTickSegments:
    """`with prof.tick(n)` / `with prof.seg(key)`: the one way a step's
    stretches are timed, and spans on the profiler's clock meanwhile."""

    def _prof(self, **kw):
        from hyperion_tpu.obs.tickprof import TickProfiler

        clk = VirtualClock()
        return TickProfiler(wall=clk.wall, clock=clk, **kw), clk

    def test_segments_land_in_the_record_with_the_steps_wall(self):
        tp, clk = self._prof()
        with tp.tick(4) as tk:
            with tp.seg("queue_pop"):
                clk.advance(0.001)
            clk.advance(0.002)              # nobody's: `other`
            with tp.seg("device"):
                clk.advance(0.010)
            tk.count(kv_tokens=120, prefill_tokens=64)
        with tp.tick(5) as tk:
            clk.advance(0.001)
            tk.count(kv_tokens=121, prefill_tokens=8)
        rec, _ = tp.tail(2)
        # filed under the number the step was opened with, its span's
        assert rec["tick"] == 4 and rec["total_s"] == pytest.approx(0.013)
        assert rec["s"] == {"queue_pop": 0.001, "device": 0.01}
        # what the step counted, and the profiler's own four beside it
        assert rec["c"] == {"kv_tokens": 120, "prefill_tokens": 64,
                            "step_us": 13000, "inflight_us": 0,
                            "fetch_after_ready_us": 0, "gc_us": 0}
        snap = tp.snapshot(now=clk.wall())
        assert snap["segments"]["other"]["s"] == pytest.approx(0.003)
        # the level as of the newest step, the flow summed over the window
        assert snap["counters"] == {"kv_tokens": 121, "prefill_tokens": 72}

    def test_a_segment_inside_another_is_netted_out_of_it(self):
        # journal and sink writes inside `accept`, the table upload
        # inside `device`: the arithmetic `Engine.step` did by hand
        tp, clk = self._prof()
        with tp.tick(0):
            with tp.seg("accept") as acc:
                clk.advance(0.003)
                for _ in range(2):
                    with tp.seg("journal"):
                        clk.advance(0.004)
                    with tp.seg("sink") as sk:
                        clk.advance(0.001)
        assert acc.gross == pytest.approx(0.013)
        assert acc.s == pytest.approx(0.003) and sk.s == pytest.approx(0.001)
        assert tp.tail(1)[0]["s"] == {
            "accept": 0.003, "journal": 0.008, "sink": 0.002}

    def test_children_ride_beside_their_parent_and_out_of_every_sum(self):
        tp, clk = self._prof()
        with tp.tick(0):
            with tp.seg("device") as dev:
                with tp.seg("bt_upload"):
                    clk.advance(0.001)
                with tp.seg("device/dispatch"):
                    clk.advance(0.002)
                with tp.seg("device/fetch"):
                    clk.advance(0.007)
            clk.advance(0.004)
        s = tp.tail(1)[0]["s"]
        assert s == {"bt_upload": 0.001, "device": 0.009,
                     "device/dispatch": 0.002, "device/fetch": 0.007}
        assert dev.gross == pytest.approx(0.010)
        assert s["device/dispatch"] + s["device/fetch"] <= s["device"] + 1e-9
        snap = tp.snapshot(now=clk.wall())
        assert set(snap["segments"]) == {"device", "bt_upload", "other"}
        assert snap["segments"]["other"]["s"] == pytest.approx(0.004)
        assert snap["children"] == {"device/fetch": 0.007,
                                    "device/dispatch": 0.002}
        assert snap["dominant"] == "device"

    def test_a_segment_of_its_own_inside_a_child_leaves_both(self):
        tp, clk = self._prof()
        with tp.tick(0):
            with tp.seg("admit"):
                with tp.seg("admit/fetch"):
                    clk.advance(0.002)
                    with tp.seg("sink"):
                        clk.advance(0.005)
        assert tp.tail(1)[0]["s"] == {
            "admit": 0.002, "admit/fetch": 0.002, "sink": 0.005}

    def test_outside_a_step_or_unrecorded_a_segment_is_a_stopwatch(self):
        tp, clk = self._prof()
        with tp.seg("admit/dispatch") as sg:      # warm-up: no step open
            clk.advance(0.5)
        assert sg.gross == pytest.approx(0.5) and tp.ticks_recorded == 0
        with tp.tick(0):
            with tp.seg("sink", record=False) as sg:  # another thread's
                clk.advance(0.25)
        assert sg.gross == pytest.approx(0.25)
        assert tp.tail(1)[0]["s"] == {}
        assert tp.tail(1)[0]["total_s"] == pytest.approx(0.25)

    def test_a_step_that_raises_leaves_no_record(self):
        tp, clk = self._prof()
        with pytest.raises(RuntimeError):
            with tp.tick(0):
                with tp.seg("device"):
                    clk.advance(0.01)
                    raise RuntimeError("chaos")
        assert tp.ticks_recorded == 0
        with tp.tick(1):          # and the next one starts clean
            with tp.seg("slo"):
                clk.advance(0.001)
        assert tp.tail(1)[0]["s"] == {"slo": 0.001}

    def test_segments_are_spans_named_after_their_keys(self):
        spans = _FakeSpans()
        tp, clk = self._prof(annotate=spans)
        with tp.tick(17):
            with tp.seg("admit"):
                with tp.seg("admit/upload", bucket=2048, start=16):
                    clk.advance(0.001)
        with tp.seg("device/fetch"):            # outside a step: still one
            pass
        assert spans.log == [
            ("open", "serve.step", {"tick": 17}),
            ("open", "serve.step/admit", {}),
            ("open", "serve.step/admit/upload",
             {"bucket": 2048, "start": 16}),
            ("close", "serve.step/admit/upload"),
            ("close", "serve.step/admit"),
            ("close", "serve.step"),
            ("open", "serve.step/device/fetch", {}),
            ("close", "serve.step/device/fetch"),
        ]

    def _slow_admission_profile(self):
        """Ten steps whose `admit` owns the wall, most of it in the
        `admit/blocks` child, with the counters a step hands over."""
        tp, clk = self._prof()
        for n in range(10):
            with tp.tick(n) as tk:
                with tp.seg("admit"):
                    with tp.seg("admit/blocks"):
                        clk.advance(0.030)
                    with tp.seg("admit/upload"):
                        clk.advance(0.010)
                with tp.seg("device"):
                    clk.advance(0.020)
                tk.count(kv_tokens=1000 + n, prefill_tokens=64)
        return tp.snapshot(now=clk.wall())

    def test_doctor_names_the_child_and_reads_the_counters(self, tmp_path):
        """The readers of `children` and `counters`: doctor's incident
        says which call inside the dominant segment holds the time, and
        its profile row what the steps counted."""
        from hyperion_tpu.obs import doctor

        snap = self._slow_admission_profile()
        assert doctor._largest_child(snap, "admit") == \
            " (mostly `admit/blocks`, 75% of it)"
        assert doctor._largest_child(snap, "device") == ""
        assert doctor._largest_child({"dominant": "admit"}, "admit") == ""
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        d = doctor.diagnose(tmp_path)
        assert d["host_segment_incidents"] == [
            "host segment 'admit' (mostly `admit/blocks`, 75% of it) "
            "owns 67% of tick time over the last 10 tick(s) — "
            "prefill/admission host work"]
        row = next(ln for ln in doctor.render_markdown(d).splitlines()
                   if ln.startswith("| host tick profile"))
        assert "`admit` (mostly `admit/blocks`, 75% of it) 67%" in row
        assert "1009 KV tokens live, 640 prefilled" in row

    @pytest.mark.parametrize("asked,tiers,sentence", [
        # (sampling_rows, restricted_rows) of each decode tick
        ([(0, 0)] * 9, {"ticks": 9, "greedy": 9, "drawn": 0, "sorted": 0,
                        "restricted_rows": None}, None),
        ([(0, 0)] * 5 + [(4, 0)] * 3,
         {"ticks": 8, "greedy": 5, "drawn": 3, "sorted": 0,
          "restricted_rows": None}, None),
        ([(0, 0)] * 4 + [(4, 0)] * 2 + [(5, 1), (6, 3), (3, 2)],
         {"ticks": 9, "greedy": 4, "drawn": 2, "sorted": 3,
          "restricted_rows": [1, 3]},
         "3 of 9 ticks sorted the vocabulary for 1-3 restricted row(s)"),
    ], ids=["all_greedy", "some_drawn", "some_sorted"])
    def test_sampling_tiers_of_the_windows_ticks(self, tmp_path, asked,
                                                 tiers, sentence):
        """The tick records' `sampling_rows` / `restricted_rows` roll up
        into ticks by tier of `sample_token_slots`; a step that ran no
        decode tick (no `device` segment) is no tick; doctor says in
        words when ticks sorted the vocabulary."""
        from hyperion_tpu.obs import doctor

        tp, clk = self._prof()
        with tp.tick(0) as tk:              # admission only: no tick
            with tp.seg("admit"):
                clk.advance(0.010)
            tk.count(kv_tokens=0, prefill_tokens=64, sampling_rows=0,
                     restricted_rows=0)
        for n, (rows, restricted) in enumerate(asked, start=1):
            with tp.tick(n) as tk:
                with tp.seg("device"):
                    clk.advance(0.020)
                tk.count(kv_tokens=100, prefill_tokens=0,
                         sampling_rows=rows, restricted_rows=restricted)
        snap = tp.snapshot(now=clk.wall())
        assert snap["sampling_tiers"] == tiers
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        row = next(ln for ln in doctor.render_markdown(
                       doctor.diagnose(tmp_path)).splitlines()
                   if ln.startswith("| host tick profile"))
        if sentence:
            assert sentence in row
        else:
            assert "sorted the vocabulary" not in row

    @pytest.mark.parametrize("steps,summed,sentence", [
        # a model of one layer kind: a prefill by block, then two ticks
        ([{"kv_blocks_written": 96, "kv_rows_written": 47},
          {"kv_blocks_written": 0, "kv_rows_written": 48},
          {"kv_blocks_written": 0, "kv_rows_written": 48}],
         {"kv_blocks_written": 96, "kv_rows_written": 143},
         "written by block: 96 block(s), row by row: 143 position(s)"),
        # two kinds: the row sums them; the windowed kind's chunk lost
        # the blocks its window let go
        ([{"kv_blocks_written": 32, "kv_rows_written": 20,
           "kv_blocks_written_window": 24, "kv_rows_written_window": 20}],
         {"kv_blocks_written": 32, "kv_rows_written": 20,
          "kv_blocks_written_window": 24, "kv_rows_written_window": 20},
         "written by block: 56 block(s), row by row: 40 position(s)"),
        # a prompt that a mid-block prefix hit started inside a block
        ([{"kv_blocks_written": 0, "kv_rows_written": 512 + 3}],
         {"kv_blocks_written": 0, "kv_rows_written": 515},
         "written by block: 0 block(s), row by row: 515 position(s)"),
        # a process that predates the counters says nothing
        ([{}], {}, None),
    ], ids=["one_kind", "two_kinds", "mid_block_hit", "older_records"])
    def test_write_counters_of_the_windows_steps(self, tmp_path, steps,
                                                 summed, sentence):
        """The tick records' `kv_blocks_written` / `kv_rows_written`
        (and a windowed kind's, `_<kind>` behind) are flows: the
        snapshot sums each over the window's steps, and doctor's row
        says both, summed over the kinds."""
        from hyperion_tpu.obs import doctor

        tp, clk = self._prof()
        for n, c in enumerate(steps):
            with tp.tick(n) as tk:
                with tp.seg("device"):
                    clk.advance(0.020)
                tk.count(kv_tokens=100, prefill_tokens=0, **c)
        snap = tp.snapshot(now=clk.wall())
        got = {k: v for k, v in snap["counters"].items()
               if "written" in k}
        assert got == summed
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        row = next(ln for ln in doctor.render_markdown(
                       doctor.diagnose(tmp_path)).splitlines()
                   if ln.startswith("| host tick profile"))
        if sentence:
            assert sentence in row
        else:
            assert "written by block" not in row

    def test_records_without_the_counters_carry_no_tiers(self):
        # a process that predates the counters: nothing to roll up
        tp, clk = self._prof()
        with tp.tick(0) as tk:
            with tp.seg("device"):
                clk.advance(0.020)
            tk.count(kv_tokens=100, prefill_tokens=0)
        assert "sampling_tiers" not in tp.snapshot(now=clk.wall())

    def test_top_shows_live_kv_tokens(self):
        snap = self._slow_admission_profile()
        assert "kv_tokens" in top_mod.ROW_KEYS
        row = {k: None for k in top_mod.ROW_KEYS}
        out = top_mod._row_from_exposition(
            dict(row), {"phase": "serve", "tick": 9, "tickprof": snap})
        assert out["kv_tokens"] == 1009
        assert out["dominant_segment"] == "admit"
        # a process that predates the counter leaves the cell empty
        old = top_mod._row_from_exposition(
            dict(row), {"phase": "serve", "tickprof": {"dominant": "slo"}})
        assert old["kv_tokens"] is None
        out.update(name="process", dir="x", source="socket", state="live",
                   alerts=[], age_s=0.0)
        text = top_mod.render([out], "x", window_s=60.0, color=False)
        assert "kv tok" in text and " 1009 " in text

    def test_module_stays_free_of_jax(self):
        import subprocess
        import sys

        code = ("import sys; import hyperion_tpu.obs.tickprof as t; "
                "p = t.TickProfiler(); "
                "c = p.tick(0); c.__enter__(); p.seg('slo').__enter__(); "
                "print('jax' in sys.modules)")
        out = subprocess.run([sys.executable, "-c", code], check=True,
                             capture_output=True, text=True, timeout=60)
        assert out.stdout.strip() == "False"


class TestFlightAccount:
    """What the profiler itself counts into every record's `c`
    (`FLIGHT_COUNTERS`): the step's wall, the part of it with a program
    out, the fetches of a program that had ended, the collector."""

    def _prof(self, **kw):
        from hyperion_tpu.obs.tickprof import TickProfiler

        clk = VirtualClock()
        return TickProfiler(wall=clk.wall, clock=clk, **kw), clk

    @staticmethod
    def _watch(tp):
        import gc

        from hyperion_tpu.obs import tickprof

        return [cb for cb in gc.callbacks
                if isinstance(cb, tickprof._CollectorWatch)
                and cb._prof() is tp]

    def _a_tick(self, tp, clk):
        with tp.seg("device"):
            with tp.seg("device/dispatch"):
                clk.advance(0.001)
            clk.advance(0.0005)             # between the two: in flight
            with tp.seg("device/fetch"):
                clk.advance(0.0002)         # before the first wait
                with tp.seg("device/fetch/tokens"):
                    clk.advance(0.008)      # the program's own run
                with tp.seg("device/fetch/finished"):
                    clk.advance(0.0027)     # a second round trip
                with tp.seg("device/fetch/counters"):
                    clk.advance(0.0001)

    def test_a_child_of_a_child_is_left_out_of_every_sum(self):
        tp, clk = self._prof()
        with tp.tick(0):
            with tp.seg("admit"):
                with tp.seg("admit/blocks"):
                    with tp.seg("admit/blocks/lookup"):
                        clk.advance(0.004)
                    with tp.seg("admit/blocks/alloc_evict"):
                        clk.advance(0.005)
                    clk.advance(0.001)
                clk.advance(0.002)
            self._a_tick(tp, clk)
        s = tp.tail(1)[0]["s"]
        assert s["admit"] == pytest.approx(0.012)
        assert s["admit/blocks"] == pytest.approx(0.010)
        assert s["admit/blocks/lookup"] == pytest.approx(0.004)
        assert s["device"] == pytest.approx(0.0125)
        assert s["device/fetch"] == pytest.approx(0.011)
        assert s["device/fetch/finished"] == pytest.approx(0.0027)
        snap = tp.snapshot(now=clk.wall())
        # the two segments are the whole step: no child was added again
        assert snap["total_s"] == pytest.approx(0.0245)
        assert snap["segments"]["admit"]["s"] \
            + snap["segments"]["device"]["s"] == pytest.approx(0.0245)
        assert not any("/" in k for k in snap["segments"])
        assert snap["children"]["admit/blocks/alloc_evict"] == \
            pytest.approx(0.005)
        assert snap["children"]["device/fetch/tokens"] == \
            pytest.approx(0.008)

    def test_dispatch_to_fetch_is_in_flight_and_later_arrays_are_lag(self):
        tp, clk = self._prof()
        with tp.tick(0) as tk:
            with tp.seg("admit"):
                with tp.seg("admit/blocks"):
                    clk.advance(0.010)      # nothing of ours on the chip
                with tp.seg("admit/dispatch", bucket=64):
                    clk.advance(0.001)
                with tp.seg("admit/fetch", bucket=64):
                    with tp.seg("admit/fetch/tokens"):
                        clk.advance(0.030)
                    with tp.seg("admit/fetch/finished"):
                        clk.advance(0.002)
            clk.advance(0.0003)
            self._a_tick(tp, clk)
            tk.count(kv_tokens=7)
        rec = tp.tail(1)[0]
        c = rec["c"]
        assert c["kv_tokens"] == 7
        assert c["step_us"] == round(rec["total_s"] * 1e6) == 55800
        # admit: 1 + 30 + 2 ms; the tick: 1 + 0.5 + 0.2 + 8 + 2.7 + 0.1
        assert c["inflight_us"] == 33000 + 12500
        # `finished` of the prefill; `finished` and `counters` of the tick
        assert c["fetch_after_ready_us"] == 2000 + 2700 + 100
        assert c["gc_us"] == 0
        assert 0 <= c["fetch_after_ready_us"] <= c["inflight_us"] \
            <= c["step_us"]
        # no segment's seconds moved for it
        assert rec["s"]["admit"] == pytest.approx(0.043)
        assert rec["s"]["device"] == pytest.approx(0.0125)

    def test_a_step_that_ran_no_tick_still_carries_the_four(self):
        from hyperion_tpu.obs.tickprof import FLIGHT_COUNTERS

        tp, clk = self._prof()
        with tp.tick(3):
            with tp.seg("queue_pop"):
                clk.advance(0.0004)
        with tp.tick(4):
            # a fetch no dispatch went before, outside a step's account
            with tp.seg("device/fetch"):
                with tp.seg("device/fetch/tokens"):
                    clk.advance(0.001)
                with tp.seg("device/fetch/finished"):
                    clk.advance(0.001)
        first, second = tp.tail(2)
        assert first["c"] == {"step_us": 400, "inflight_us": 0,
                              "fetch_after_ready_us": 0, "gc_us": 0}
        assert tuple(first["c"]) == FLIGHT_COUNTERS
        assert second["c"] == {"step_us": 2000, "inflight_us": 0,
                               "fetch_after_ready_us": 0, "gc_us": 0}
        snap = tp.snapshot(now=clk.wall())
        assert snap["inflight"] == {"step_us": 2400, "inflight_us": 0,
                                    "fetch_after_ready_us": 0, "gc_us": 0}

    def test_the_windows_sums_ride_the_snapshot_under_one_key(self):
        tp, clk = self._prof()
        for n in range(3):
            with tp.tick(n):
                self._a_tick(tp, clk)
                clk.advance(0.0005)
        snap = tp.snapshot(now=clk.wall())
        assert snap["inflight"] == {
            "step_us": 3 * 13000, "inflight_us": 3 * 12500,
            "fetch_after_ready_us": 3 * 2800, "gc_us": 0}
        # a flow of its own key: the counters' keys are what they were
        assert set(snap["counters"]) == {"kv_tokens", "prefill_tokens"}
        assert "inflight" not in self._prof()[0].snapshot()

    @pytest.mark.parametrize("args, want", [
        # the share of the steps' wall with nothing of the engine's out
        ({"counter": "inflight_us", "over": "step_us", "scale": 100,
          "complement": True}, 100 * (1 - 12500 / 13000)),
        # the share spent fetching from a program that had ended
        ({"counter": "fetch_after_ready_us", "over": "step_us",
          "scale": 100}, 100 * 2800 / 13000),
        # the collector's milliseconds a second
        ({"counter": "gc_us", "over": "step_us", "scale": 1000}, 0.0),
    ])
    def test_the_benchmarks_counter_ratio_divides_the_records_sums(
            self, args, want):
        """No cell lists such a metric yet (PERF.md section 7): the
        accepted reader needs a data file alone to read one."""
        from benchmarks.readers import counter_ratio

        tp, clk = self._prof()
        for n in range(3):
            with tp.tick(n):
                self._a_tick(tp, clk)
                clk.advance(0.0005)
        counted = [{"kv_tokens": 7, **r["c"]} for r in tp.tail(3)]
        assert counter_ratio.read({"counted": counted}, **args) == \
            pytest.approx(want)
        # the parent's records lack the four: nothing to read
        assert counter_ratio.read(
            {"counted": [{"kv_tokens": 7}]}, **args) is None

    def test_a_collection_is_a_span_and_changes_no_segments_seconds(self):
        spans = _FakeSpans()
        tp, clk = self._prof(annotate=spans)
        tp.watch_collector()
        (watch,) = self._watch(tp)
        watch("start", {"generation": 2})       # outside any step: nothing
        clk.advance(0.5)
        watch("stop", {"generation": 2, "collected": 0})
        with tp.tick(9):
            with tp.seg("accept"):
                clk.advance(0.001)
                watch("start", {"generation": 1})
                clk.advance(0.040)
                watch("stop", {"generation": 1, "collected": 5})
                clk.advance(0.001)
        rec = tp.tail(1)[0]
        assert rec["s"] == {"accept": 0.042}    # nothing netted out
        assert rec["total_s"] == pytest.approx(0.042)
        assert rec["c"]["gc_us"] == 40000 and rec["c"]["step_us"] == 42000
        assert spans.log == [
            ("open", "serve.step", {"tick": 9}),
            ("open", "serve.step/accept", {}),
            ("open", "serve.step/gc", {"generation": 1}),
            ("close", "serve.step/gc"),
            ("close", "serve.step/accept"),
            ("close", "serve.step"),
        ]

    def test_another_threads_collection_is_not_the_steps(self):
        import threading

        tp, clk = self._prof()
        tp.watch_collector()
        (watch,) = self._watch(tp)

        def collect():
            watch("start", {"generation": 0})
            clk.advance(0.010)
            watch("stop", {"generation": 0})

        with tp.tick(0):
            th = threading.Thread(target=collect)
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
        assert tp.tail(1)[0]["c"]["gc_us"] == 0

    def test_a_real_collection_inside_a_step_and_none_after_the_profiler(
            self):
        import gc

        from hyperion_tpu.obs.tickprof import TickProfiler

        tp, other = TickProfiler(), TickProfiler()
        tp.watch_collector()
        other.watch_collector()
        with tp.tick(0):
            with tp.seg("accept"):
                gc.collect()
        with other.tick(0):
            pass
        assert tp.tail(1)[0]["c"]["gc_us"] > 0
        assert tp.tail(1)[0]["s"]["accept"] * 1e6 \
            >= tp.tail(1)[0]["c"]["gc_us"] - 1
        # the other profiler's hook saw the same collection and no step
        assert other.tail(1)[0]["c"]["gc_us"] == 0
        (watch,) = self._watch(tp)
        assert len(self._watch(other)) == 1
        del tp
        gc.collect()
        assert watch not in gc.callbacks and watch._prof() is None
        assert len(self._watch(other)) == 1


class TestFlightRecorder:
    def test_first_spill_due_then_cadence(self, tmp_path):
        from hyperion_tpu.obs.tickprof import FlightRecorder

        fr = FlightRecorder(tmp_path / "flight.json", spill_every=16)
        assert fr.due(2)  # a crash at tick 2 must still find evidence
        fr.spill("periodic", {"phase": "serve"}, tick=2)
        assert not fr.due(10) and not fr.due(17)
        assert fr.due(18)

    def test_spill_round_trip_and_final_tick(self, tmp_path):
        from hyperion_tpu.obs.tickprof import (
            FLIGHT_SCHEMA,
            FlightRecorder,
            flight_final_tick,
            read_flight,
        )

        fr = FlightRecorder(tmp_path / "flight.json", run="serve_x")
        fr.note("recompile_after_warmup", executable="prefill")
        fr.spill("sigterm", {"ticks": [{"tick": 40}, {"tick": 41}]},
                 tick=41)
        doc = read_flight(tmp_path / "flight.json")
        assert doc["v"] == FLIGHT_SCHEMA and doc["run"] == "serve_x"
        assert doc["reason"] == "sigterm" and doc["spills"] == 1
        assert doc["events"][0]["name"] == "recompile_after_warmup"
        assert flight_final_tick(doc) == 41
        # no spill tick stamp: the newest ring entry's tick answers
        assert flight_final_tick({"ticks": [{"tick": 7}]}) == 7
        assert flight_final_tick({}) is None

    def test_null_recorder_and_unreadable_file(self, tmp_path):
        from hyperion_tpu.obs.tickprof import (
            null_flight_recorder,
            read_flight,
        )

        fr = null_flight_recorder()
        fr.note("x")
        fr.spill("periodic", {"a": 1}, tick=1)  # accepted, writes nothing
        assert not fr.enabled and not fr.due(1)
        assert read_flight(tmp_path / "absent.json") is None
        bad = tmp_path / "torn.json"
        bad.write_text("{not json")
        assert read_flight(bad) is None

    def test_io_failure_degrades_not_raises(self, tmp_path):
        from hyperion_tpu.obs.tickprof import FlightRecorder

        blocker = tmp_path / "blocker"
        blocker.write_text("")  # a FILE where the parent dir must go
        fr = FlightRecorder(blocker / "flight.json")
        fr.spill("periodic", {}, tick=1)
        assert not fr.enabled  # degraded, process unharmed


class TestCompileLedger:
    def test_growth_reports_once_and_counts(self):
        from hyperion_tpu.obs.ledger import CompileLedger

        led = CompileLedger()
        base = {"tick_executables": 1, "prefill_executables": 2}
        # no-op until baselined: an unwarmed engine has no invariant
        assert led.check({"tick_executables": 9}) == []
        led.set_baseline(base)
        assert led.check(base) == []
        grown = {"tick_executables": 1, "prefill_executables": 3}
        (g,) = led.check(grown)
        assert g == {"executable": "prefill_executables", "before": 2,
                     "after": 3}
        assert led.recompiles == 1
        # last-seen advanced: the same counts report nothing new
        assert led.check(grown) == []
        assert led.last_seen["prefill_executables"] == 3

    def test_warmup_record_shape(self):
        from hyperion_tpu.obs.ledger import CompileLedger

        led = CompileLedger()
        rec = led.record_warmup({"tick_executables": 1},
                                compile_s={"tick": 1.25}, total_s=2.0)
        assert rec["stats"] == {"tick_executables": 1}
        assert rec["compile_s"]["tick"] == 1.25
        assert rec["total_s"] == 2.0
        assert led.warmup is rec


class TestEventVocabGuard:
    """scripts/check_event_vocab.py — an event the producers emit but
    no consumer names has silently vanished from every waterfall and
    diagnosis; the guard makes the rename loud."""

    def _guard(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "check_event_vocab",
            Path(__file__).parent.parent / "scripts"
            / "check_event_vocab.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_current_events_all_consumed(self):
        assert self._guard().main([]) == 0

    def test_orphaned_event_fails(self, tmp_path, monkeypatch, capsys):
        mod = self._guard()
        # a producer dir with an event no consumer has ever heard of
        prod = tmp_path / "serve"
        prod.mkdir()
        (prod / "thing.py").write_text(
            'tracer.event(\n    "serve_event_nobody_consumes", x=1)\n')
        monkeypatch.setattr(mod, "PRODUCER_DIR", str(prod))
        assert mod.main([]) == 1
        err = capsys.readouterr().err
        assert "serve_event_nobody_consumes" in err
        assert "thing.py:1" in err

    def test_wrapped_name_literal_is_found(self, tmp_path, monkeypatch):
        """Call sites that wrap the name onto the next line (the
        dominant style under serve/) must still be scanned."""
        mod = self._guard()
        prod = tmp_path / "serve"
        prod.mkdir()
        (prod / "w.py").write_text(
            'self.tracer.event(\n'
            '    "route_dispatch", request=rid)\n')
        monkeypatch.setattr(mod, "PRODUCER_DIR", str(prod))
        assert mod.main([]) == 0


class TestExpositionControl:
    def test_control_round_trip_and_bare_clients(self, tmp_path):
        from hyperion_tpu.obs.export import request_control

        calls = []

        def control(req):
            calls.append(req)
            return {"status": "started", "dir": req.get("out")}

        sock = tmp_path / "obs.sock"
        with MetricsExporter(sock, lambda window_s=60.0: {"phase": "x"},
                             control_fn=control):
            # fast path unchanged: the newline probe gets exposition
            doc = read_exposition(sock)
            assert doc["kind"] == "exposition" and doc["phase"] == "x"
            # a JSON request line routes to the control fn
            res = request_control(sock, {"cmd": "profile", "out": "d"})
            assert res["kind"] == "control" and res["status"] == "started"
            assert calls == [{"cmd": "profile", "out": "d"}]
            # garbage on the request line degrades to exposition,
            # never an error (nc -U stays a valid client)
            import socket as socket_mod

            s = socket_mod.socket(socket_mod.AF_UNIX,
                                  socket_mod.SOCK_STREAM)
            s.connect(str(sock))
            s.sendall(b"not json\n")
            data = b""
            while not data.endswith(b"\n"):
                chunk = s.recv(65536)
                if not chunk:
                    break
                data += chunk
            s.close()
            assert json.loads(data)["kind"] == "exposition"

    def test_control_request_without_control_fn_gets_exposition(
            self, tmp_path):
        from hyperion_tpu.obs.export import request_control

        sock = tmp_path / "obs.sock"
        with MetricsExporter(sock, lambda window_s=60.0: {"phase": "x"}):
            res = request_control(sock, {"cmd": "profile"})
            assert res["kind"] == "exposition" and res["phase"] == "x"

