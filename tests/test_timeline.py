"""`obs trace` consumer: timeline reconstruction from the golden serve
fixture, Chrome trace-event export validity, tail-attribution math, the
doctor's named serving incidents. Everything here is host-only JSONL parsing — zero jit compiles
(the live producer↔consumer round trip lives in tests/test_serve.py,
riding shapes the suite already compiled).
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from hyperion_tpu.obs import timeline
from hyperion_tpu.obs.doctor import diagnose
from hyperion_tpu.obs.report import read_records

FIXTURES = Path(__file__).resolve().parent / "data" / "telemetry"
SERVE_DIR = FIXTURES / "serve"


@pytest.fixture(scope="module")
def serve_records():
    return read_records(SERVE_DIR / "telemetry.jsonl")


@pytest.fixture(scope="module")
def serve_reqs(serve_records):
    return timeline.requests_from_records(serve_records)


# ---------------------------------------------------- reconstruction


class TestReconstruction:
    def test_all_requests_reconstructed(self, serve_reqs):
        by_id = {r.id: r for r in serve_reqs}
        assert len(by_id) == 8
        assert sum(1 for r in serve_reqs if r.status == "done") == 6
        assert by_id["r6"].status == "rejected"
        assert by_id["r7"].status == "timed_out"

    def test_phase_totals_from_finished_event(self, serve_reqs):
        r0 = next(r for r in serve_reqs if r.id == "r0")
        assert r0.phases["queue_wait"] == pytest.approx(0.30)
        assert r0.phases["prefill"] == pytest.approx(0.020)
        assert r0.phases["decode"] == pytest.approx(0.050)
        assert r0.e2e_s == pytest.approx(0.373)
        assert r0.ttft_s == pytest.approx(0.320)
        # the explicit remainder keeps the decomposition exact
        assert r0.other_s == pytest.approx(
            r0.e2e_s - sum(r0.phases.values()))

    def test_preemption_replay_reconstructed(self, serve_reqs):
        r3 = next(r for r in serve_reqs if r.id == "r3")
        assert r3.preempts == 1
        assert r3.phases["preempt_replay"] == pytest.approx(0.080)
        assert ("preempted" in {m[0] for m in r3.marks})
        names = [s[0] for s in r3.segments]
        assert "replay_wait" in names and "replay_prefill" in names

    def test_waterfall_segments_ordered(self, serve_reqs):
        """Segments within a request must be non-overlapping and in
        time order — the property that makes the waterfall readable."""
        for r in serve_reqs:
            end = -math.inf
            for _name, t0, dur in sorted(r.segments, key=lambda s: s[1]):
                assert dur >= 0
                assert t0 >= end - 1e-9, f"{r.id} segments overlap"
                end = t0 + dur

    def test_rejected_and_timed_out_carry_queued(self, serve_reqs):
        by_id = {r.id: r for r in serve_reqs}
        assert by_id["r6"].queued_s == 0.0
        assert by_id["r7"].queued_s == pytest.approx(0.6)


# -------------------------------------------------------- attribution


class TestAttribution:
    def test_components_sum_to_measured_latency(self, serve_reqs):
        """The acceptance property: per-phase components + other ==
        the measured value, exactly, for every attribution row."""
        att = timeline.attribution(serve_reqs)
        assert att["rows"], "no attribution rows"
        for row in att["rows"]:
            total = sum(row["components_ms"].values()) + row["other_ms"]
            assert total == pytest.approx(row["value_ms"], abs=0.01)
            # and the NAMED phases carry the value (other is slack,
            # not a dumping ground): within 5% on this fixture
            assert sum(row["components_ms"].values()) >= 0.95 * row["value_ms"]

    def test_queue_wait_dominates_fixture(self, serve_reqs):
        att = timeline.attribution(serve_reqs)
        by_key = {(r["metric"], r["q"]): r for r in att["rows"]}
        assert by_key[("ttft", 99)]["dominant"] == "queue_wait"
        assert by_key[("ttft", 99)]["dominant_frac"] > 0.5
        assert by_key[("e2e", 99)]["dominant"] == "queue_wait"
        # the preempted request IS the e2e p99 cohort: replay visible
        assert by_key[("e2e", 99)]["components_ms"][
            "preempt_replay"] == pytest.approx(80.0)

    def test_rejects_and_timeouts_in_tables(self, serve_reqs):
        """Satellite contract: dead requests appear in the attribution
        output instead of vanishing from tail analysis."""
        att = timeline.attribution(serve_reqs)
        assert att["rejected"]["count"] == 1
        assert att["timed_out"]["count"] == 1
        assert att["timed_out"]["queued_p99_ms"] == pytest.approx(600.0)

    def test_worst_requests_include_timeouts(self, serve_reqs):
        worst = timeline.worst_requests(serve_reqs, k=3)
        done = [w for w in worst if w["status"] == "done"]
        assert len(done) == 3
        assert done == sorted(done, key=lambda w: -w["e2e_ms"])
        assert any(w["status"] == "timed_out" for w in worst)


# ------------------------------------------------------ Chrome export


class TestChromeExport:
    def test_export_is_valid_trace_event_json(self, serve_reqs,
                                              serve_records, tmp_path):
        doc = timeline.chrome_trace(serve_reqs, serve_records,
                                    run="fix_serve")
        # JSON round trip: what a real viewer loads
        doc = json.loads(json.dumps(doc))
        evs = doc["traceEvents"]
        assert evs
        for e in evs:
            assert e["ph"] in ("X", "i", "M")
            if e["ph"] != "M":
                assert isinstance(e["ts"], (int, float))
                assert math.isfinite(e["ts"]) and e["ts"] >= 0
            if e["ph"] == "X":
                assert math.isfinite(e["dur"]) and e["dur"] >= 0

    def test_every_request_owns_a_thread(self, serve_reqs, serve_records):
        evs = timeline.chrome_trace(
            serve_reqs, serve_records, run="fix_serve")["traceEvents"]
        names = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        for rid in ("r0", "r3", "r7"):
            assert any(rid in n for n in names), f"{rid} missing: {names}"
        # engine ticks ride their own track
        assert any(e["name"] == "serve_tick" for e in evs)
        # one tid per request: segments of different requests never
        # share a thread row
        tid_by_req = {}
        for e in evs:
            rid = e.get("args", {}).get("request")
            if rid and e["ph"] == "X" and e["name"] != "serve_prefill":
                tid_by_req.setdefault(rid, set()).add(e["tid"])
        assert all(len(tids) == 1 for tids in tid_by_req.values())
        tids = [next(iter(t)) for t in tid_by_req.values()]
        assert len(set(tids)) == len(tids)


# ------------------------------------------------------- doctor + diff


class TestDoctorIncidents:
    def test_queue_wait_dominated_run_raises_named_incident(self):
        d = diagnose(SERVE_DIR)
        assert d["verdict"] == "healthy"
        assert d["tail_incidents"], "no incident on queue-dominated run"
        assert any("queue wait" in i and "--slots" in i
                   for i in d["tail_incidents"])
        assert "queue wait" in d["reason"]
        assert d["tail_attribution"]

    def test_heartbeat_payload_surfaced(self):
        """Satellite contract: the serve loop's heartbeat payload (tick
        / active slots / queue depth) reaches the doctor's evidence."""
        d = diagnose(SERVE_DIR)
        assert d["heartbeat"] is not None
        assert d["heartbeat"]["active"] is not None
        assert d["heartbeat"]["queue"] is not None

    def test_non_serve_runs_have_no_tail_rows(self):
        d = diagnose(FIXTURES / "healthy")
        assert d["verdict"] == "healthy"
        assert d["tail_attribution"] == []
        assert d["tail_incidents"] == []


# --------------------------------------------------------- CLI + drift


class TestCli:
    def test_trace_cli_round_trip(self, tmp_path, capsys):
        export = tmp_path / "t.json"
        rc = timeline.main([str(SERVE_DIR), "--export", str(export),
                            "--top", "2"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "Tail attribution" in out and "queue_wait" in out
        doc = json.loads(export.read_text())
        assert doc["traceEvents"]

    def test_trace_cli_json_mode(self, tmp_path, capsys):
        rc = timeline.main([str(SERVE_DIR), "--export", "none", "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["attribution"]["rows"]
        assert doc["export"] is None

    def test_trace_cli_empty_stream_exits_2(self, tmp_path, capsys):
        (tmp_path / "telemetry.jsonl").write_text(
            '{"v":1,"kind":"event","name":"train_start","run":"x"}\n')
        assert timeline.main([str(tmp_path)]) == 2

    def test_smoke_script_trace_invocation_parses(self):
        """Flag-drift guard (the serve-invocation pattern): the
        `obs trace` call in scripts/serve_smoke.sh must parse against
        the real arg surface."""
        import re
        import shlex

        script = (Path(__file__).resolve().parents[1] / "scripts"
                  / "serve_smoke.sh").read_text()
        script = re.sub(r"\\\n\s*", " ", script)
        calls = re.findall(
            r"python -m hyperion_tpu\.cli\.main obs trace\s+(.*)", script)
        assert calls, "serve_smoke.sh lost its obs trace round trip"
        for call in calls:
            toks = shlex.split(call.split(">")[0])
            args = timeline.build_parser().parse_args(
                [re.sub(r"\$\{?\w+\}?", "x", t) for t in toks])
            assert args.export is not None


def test_dominant_of_shared_rule():
    """The one definition of "dominant phase" (argmax + other-demotion)
    that both `_cohort_row` and loadgen's bench row use."""
    assert timeline.dominant_of({}, 1.0) is None
    assert timeline.dominant_of({"queue_wait": 5.0, "decode": 2.0},
                                4.0) == "queue_wait"
    assert timeline.dominant_of({"queue_wait": 3.0, "decode": 2.0},
                                4.0) == "other"


def test_cohort_dominant_matches_attribution(serve_reqs):
    """loadgen's bench path (`cohort_dominant`) and `attribution()`
    must name the same phase for the same requests."""
    done = [r for r in serve_reqs if r.status == "done" and r.phases]
    named = timeline.cohort_dominant(
        [r.e2e_s for r in done], [r.phases for r in done])
    att = timeline.attribution(serve_reqs)
    e2e99 = next(r for r in att["rows"]
                 if r["metric"] == "e2e" and r["q"] == 99)
    assert named == e2e99["dominant"] == "queue_wait"
    assert timeline.cohort_dominant([], []) is None


def test_requeue_event_restarts_queue_segment():
    """An allocation-race bounce (`request_requeued`) must restart the
    waterfall's queue segment — the renewed wait can't vanish."""
    recs = [
        {"run": "r", "kind": "event", "name": "request_admitted",
         "request": "a", "t_mono": 1.0, "prompt_len": 4},
        {"run": "r", "kind": "event", "name": "request_scheduled",
         "request": "a", "t_mono": 2.0, "queue_wait_s": 1.0,
         "gate_wait_s": 0.0, "replay_wait_s": 0.0},
        {"run": "r", "kind": "event", "name": "request_requeued",
         "request": "a", "t_mono": 2.0, "reason": "alloc_race"},
        {"run": "r", "kind": "event", "name": "request_scheduled",
         "request": "a", "t_mono": 5.0, "queue_wait_s": 3.0,
         "gate_wait_s": 0.0, "replay_wait_s": 0.0},
    ]
    (rt,) = timeline.requests_from_records(recs)
    queue_segs = [s for s in rt.segments if s[0] == "queue"]
    assert len(queue_segs) == 2
    assert queue_segs[1][1] == pytest.approx(2.0)   # restarts at bounce
    assert queue_segs[1][2] == pytest.approx(3.0)   # renewed wait visible
    assert ("requeued", 2.0) in rt.marks


def test_loadgen_request_ids_seed_derived():
    from hyperion_tpu.serve.loadgen import request_id

    assert request_id(0, 3) == "load_s0_003"
    assert request_id(7, 3) != request_id(0, 3)
    # stable across calls — the property fixtures and bench rows need
    assert request_id(5, 11) == request_id(5, 11)
