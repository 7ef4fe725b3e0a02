"""Crash-safe serving, host half: the request journal's WAL + replay
semantics, the poison-pill rule, the brownout governor's hysteresis,
the drain door, deadline-aware shedding, the new serve-scoped chaos
clauses, and the serve supervisor loop — all jax-free and fast.

The engine-integrated halves (bit-identical replay, drain under load,
shed/clamp through a live engine, the supervised SIGKILL subprocess
round trip) live in tests/test_serve.py, where the compiled tiny-llama
shapes are shared with the rest of the suite.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from hyperion_tpu.serve.journal import RequestJournal
from hyperion_tpu.serve.queue import (
    REJECT_DRAINING,
    AdmissionQueue,
    BrownoutGovernor,
    Request,
)
from hyperion_tpu.testing import chaos


def _req(n=4, rid="", **kw):
    kw.setdefault("max_new_tokens", 4)
    return Request(prompt_ids=np.arange(1, n + 1, dtype=np.int32),
                   id=rid, **kw)


# ------------------------------------------------------------- journal


class TestJournal:
    def test_round_trip_resumes_unfinished_in_admit_order(self, tmp_path):
        """Admitted-but-unfinished requests come back with their
        journaled tokens riding along (the recompute-resume payload),
        sampling params intact, in original admit order."""
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        a = _req(5, "a", max_new_tokens=8, temperature=0.7, top_k=5,
                 top_p=0.9, seed=42)
        b = _req(3, "b", max_new_tokens=6)
        j.admit(a)
        j.admit(b)
        j.token("a", 17)
        j.token("a", 21)
        j.close()

        resume, finished, poisoned, clean = RequestJournal(jp).recover()
        assert not clean and not finished and not poisoned
        assert [r.id for r in resume] == ["a", "b"]
        ra, rb = resume
        assert ra.tokens == [17, 21] and rb.tokens == []
        assert ra.prompt_ids.tolist() == a.prompt_ids.tolist()
        assert (ra.max_new_tokens, ra.temperature, ra.top_k, ra.top_p,
                ra.seed) == (8, 0.7, 5, 0.9, 42)
        assert ra.replays == 1  # this recovery marked itself

    def test_finished_requests_never_replayed(self, tmp_path):
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        j.admit(_req(4, "a"))
        j.token("a", 9)
        j.finish("a", "done")
        j.close()
        resume, finished, poisoned, clean = RequestJournal(jp).recover()
        assert resume == [] and finished == [] and poisoned == []

    def test_clean_close_means_empty_replay_set(self, tmp_path):
        """The drain contract: a cleanly closed journal owes nothing,
        even if (pathologically) records precede the close marker."""
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        j.admit(_req(4, "a"))
        j.token("a", 9)
        j.close_clean()
        assert j.clean_closed
        resume, finished, poisoned, clean = RequestJournal(jp).recover()
        assert clean and resume == [] and poisoned == []
        assert RequestJournal(jp).pending_count() == 0

    def test_torn_tail_tolerated(self, tmp_path):
        """The record a SIGKILL'd process never finished writing must
        not abort recovery — it IS the crash signature."""
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        j.admit(_req(4, "a"))
        j.token("a", 7)
        j.close()
        with jp.open("a") as f:
            f.write('{"k":"tok","id":"a","to')  # torn mid-write
        resume, _, _, _ = RequestJournal(jp).recover()
        assert [r.id for r in resume] == ["a"]
        assert resume[0].tokens == [7]

    def test_complete_output_recovers_as_finished_not_resumed(
            self, tmp_path):
        """All budgeted tokens journaled but the terminal record lost:
        nothing to compute — re-prefilling would sample an EXTRA token
        past the budget. The request lands in `finished` (the client is
        owed only its done line) and gets its terminal record now."""
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        j.admit(_req(4, "a", max_new_tokens=3))
        for t in (5, 6, 7):
            j.token("a", t)
        j.close()
        resume, finished, _, _ = RequestJournal(jp).recover()
        assert resume == [] and [r.id for r in finished] == ["a"]
        assert finished[0].tokens == [5, 6, 7]
        # the terminal record was backfilled: the next recovery owes nothing
        assert RequestJournal(jp).pending_count() == 0

    def test_eos_terminated_output_recovers_as_finished(self, tmp_path):
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        j.admit(_req(4, "a", max_new_tokens=10))
        j.token("a", 5)
        j.token("a", 2)  # eos
        j.close()
        resume, finished, _, _ = RequestJournal(jp).recover(eos_id=2)
        assert resume == [] and [r.id for r in finished] == ["a"]

    def test_poison_rule_quarantines_after_max_replays(self, tmp_path):
        """Three recoveries with the same unfinished request: replay,
        replay, POISON — the adversarial request stops crash-looping
        the replica, and later recoveries skip it permanently."""
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        j.admit(_req(4, "evil"))
        j.close()
        r1, _, p1, _ = RequestJournal(jp).recover(max_replays=2)
        assert [r.id for r in r1] == ["evil"] and p1 == []
        r2, _, p2, _ = RequestJournal(jp).recover(max_replays=2)
        assert [r.id for r in r2] == ["evil"] and p2 == []
        r3, _, p3, _ = RequestJournal(jp).recover(max_replays=2)
        assert r3 == [] and [r.id for r in p3] == ["evil"]
        assert p3[0].replays == 2
        # permanently: the fourth recovery does not resurrect it
        r4, _, p4, _ = RequestJournal(jp).recover(max_replays=2)
        assert r4 == [] and p4 == []

    def test_io_failure_disables_never_raises(self, tmp_path):
        fails = {"n": 0}

        def fault(tag):
            fails["n"] += 1
            raise OSError("disk on fire")

        j = RequestJournal(tmp_path / "j.jsonl", fault=fault)
        j.admit(_req(4, "a"))  # must not raise
        assert not j.enabled and "disk on fire" in (j.error or "")
        j.token("a", 1)  # disabled: silent no-op, no second fault call
        assert fails["n"] == 1

    def test_journal_io_fail_chaos_clause(self, tmp_path):
        plan = chaos.ChaosPlan(chaos.parse_plan("journal_io_fail@p=1.0"))
        j = RequestJournal(tmp_path / "j.jsonl", fault=plan.journal_io)
        j.admit(_req(4, "a"))
        assert not j.enabled and "journal_io_fail" in (j.error or "")
        # p=0 never fires
        plan0 = chaos.ChaosPlan(chaos.parse_plan("journal_io_fail@p=0.0"))
        j0 = RequestJournal(tmp_path / "j0.jsonl", fault=plan0.journal_io)
        j0.admit(_req(4, "a"))
        assert j0.enabled

    def test_records_after_close_start_a_new_life(self, tmp_path):
        """A journal reused after a clean close (same path, next serve
        run) replays the NEW run's unfinished work — including when a
        client REUSES a request id: the old life's done marker must not
        skip the new life's replay, and the old life's tokens must not
        leak into the resume payload."""
        jp = tmp_path / "j.jsonl"
        j = RequestJournal(jp)
        j.admit(_req(4, "old"))
        j.finish("old", "done")
        j.admit(_req(4, "reused"))
        j.token("reused", 99)  # old life's token: settled history
        j.finish("reused", "done")
        j.close_clean()
        j2 = RequestJournal(jp)
        j2.admit(_req(4, "new"))
        j2.admit(_req(5, "reused"))  # same id, new life, unfinished
        j2.token("reused", 7)
        j2.close()
        resume, _, _, clean = RequestJournal(jp).recover()
        assert not clean
        assert [r.id for r in resume] == ["new", "reused"]
        (reused,) = [r for r in resume if r.id == "reused"]
        assert reused.tokens == [7]  # not [99, 7]
        assert reused.prompt_len == 5  # the NEW life's admit record


# ----------------------------------------------------- router journal


class TestRouterJournal:
    """serve/router_journal.py at the file level: the dispatch/hwm/done
    vocabulary, orphan recovery, re-open-after-terminal, and the tail
    reader the doctor's post-mortem cites."""

    @staticmethod
    def _wire(rid, n=8):
        return json.dumps({"id": rid, "prompt_ids": [1, 2],
                           "max_new_tokens": n})

    def test_orphan_carries_line_replica_session_hwm(self, tmp_path):
        from hyperion_tpu.serve.router_journal import RouterJournal

        jp = tmp_path / "rj.jsonl"
        j = RouterJournal(jp)
        j.dispatch("a", line=self._wire("a"), replica=1, session="s1")
        j.hwm("a", 2)
        j.hwm("a", 3)
        j.dispatch("b", line=self._wire("b"), replica=0, session=None)
        j.done("b", "done")
        j.close()
        orphans, clean = RouterJournal(jp).recover()
        assert not clean and [o.id for o in orphans] == ["a"]
        (o,) = orphans
        assert o.line == self._wire("a")  # wire line verbatim
        assert o.doc["max_new_tokens"] == 8
        assert (o.replica, o.session, o.hwm, o.dispatches) == (1, "s1",
                                                               3, 1)

    def test_redispatch_keeps_first_line_counts_placements(self,
                                                           tmp_path):
        """Failovers journal a dispatch per placement but the wire line
        rides only the first record — the WAL must not grow by the
        prompt on every failover."""
        from hyperion_tpu.serve.router_journal import RouterJournal

        jp = tmp_path / "rj.jsonl"
        j = RouterJournal(jp)
        j.dispatch("a", line=self._wire("a"), replica=0, session=None)
        j.dispatch("a", line=self._wire("a"), replica=1, session=None,
                   n=1)
        j.close()
        recs = [json.loads(line) for line in
                jp.read_text().splitlines()]
        assert recs[0]["line"] is not None and recs[1]["line"] is None
        orphans, _ = RouterJournal(jp).recover()
        assert orphans[0].dispatches == 2
        assert orphans[0].line == self._wire("a")
        assert orphans[0].replica == 1  # the LAST placement is evidence

    def test_dispatch_after_done_reopens(self, tmp_path):
        """A same-life resume after a client_gone terminal re-dispatches
        the id; a router death after that must recover it — the done
        marker is history, not a tombstone."""
        from hyperion_tpu.serve.router_journal import RouterJournal

        jp = tmp_path / "rj.jsonl"
        j = RouterJournal(jp)
        j.dispatch("a", line=self._wire("a"), replica=0, session=None)
        j.hwm("a", 2)
        j.done("a", "client_gone")
        j.dispatch("a", line=self._wire("a"), replica=1, session=None,
                   n=1)
        j.hwm("a", 5)
        j.close()
        orphans, _ = RouterJournal(jp).recover()
        assert [o.id for o in orphans] == ["a"]
        assert orphans[0].hwm == 5
        # ...and a terminal AFTER the re-open settles it again
        j2 = RouterJournal(jp)
        j2.done("a", "done")
        j2.close()
        orphans, _ = RouterJournal(jp).recover()
        assert orphans == []

    def test_clean_close_and_pending_count(self, tmp_path):
        from hyperion_tpu.serve.router_journal import RouterJournal

        jp = tmp_path / "rj.jsonl"
        j = RouterJournal(jp)
        j.dispatch("a", line=self._wire("a"), replica=0, session=None)
        assert RouterJournal(jp).pending_count() == 1
        j.done("a", "done")
        assert RouterJournal(jp).pending_count() == 0
        j.close_clean()
        orphans, clean = RouterJournal(jp).recover()
        assert clean and orphans == []
        assert RouterJournal(jp).pending_count() == 0

    def test_torn_tail_and_tail_reader(self, tmp_path):
        from hyperion_tpu.serve.router_journal import RouterJournal

        jp = tmp_path / "rj.jsonl"
        j = RouterJournal(jp)
        j.dispatch("a", line=self._wire("a"), replica=0, session=None)
        j.hwm("a", 1)
        j.close()
        with jp.open("a") as f:
            f.write('{"k":"hwm","id":"a","i')  # torn mid-write
        tail = RouterJournal(jp).tail(2)
        assert [r["k"] for r in tail] == ["dispatch", "hwm"]  # torn skipped
        orphans, _ = RouterJournal(jp).recover()
        assert orphans[0].hwm == 1

    def test_recovery_compacts_terminal_majority(self, tmp_path):
        """The compaction satellite on the router WAL: terminal streams
        drop out at recovery when they dominate the file; the orphan's
        records survive byte-exactly."""
        from hyperion_tpu.serve.router_journal import RouterJournal

        jp = tmp_path / "rj.jsonl"
        j = RouterJournal(jp)
        for i in range(8):
            j.dispatch(f"d{i}", line=self._wire(f"d{i}"), replica=0,
                       session=None)
            j.hwm(f"d{i}", 8)
            j.done(f"d{i}", "done")
        j.dispatch("live", line=self._wire("live"), replica=1,
                   session="sx")
        j.hwm("live", 4)
        j.close()
        before = jp.stat().st_size
        live_lines = [line for line in jp.read_text().splitlines()
                      if '"live"' in line]
        orphans, _ = RouterJournal(jp).recover()
        assert [o.id for o in orphans] == ["live"]
        after = jp.read_text()
        assert jp.stat().st_size < before
        assert "d0" not in after and "d7" not in after
        for line in live_lines:  # pending work preserved byte-exactly
            assert line in after


# --------------------------------------------- WAL byte-boundary fuzz


class TestWalByteFuzz:
    """The property satellite: a WAL truncated at EVERY byte boundary
    (any crash point) must recover to exactly the state its complete-
    line prefix describes — no phantom request, no duplicate or phantom
    token, hwm never past what was durably written — for BOTH the
    replica journal and the router WAL."""

    @staticmethod
    def _complete_lines(prefix: bytes):
        """The records recovery may legally see: every newline-
        terminated line, plus the torn last line iff it parses — a
        strict prefix of a JSON dict is only valid at its final `}`, so
        this admits exactly the case where the crash ate only the
        trailing newline."""
        segs = prefix.split(b"\n")
        out = []
        for raw in segs[:-1]:
            if raw.strip():
                out.append(json.loads(raw))
        if segs[-1].strip():
            try:
                out.append(json.loads(segs[-1]))
            except ValueError:
                pass
        return out

    def test_replica_journal_recovers_exact_prefix(self, tmp_path):
        import random

        rng = random.Random(7)
        jp = tmp_path / "full.jsonl"
        j = RequestJournal(jp)
        live: list[str] = []
        nxt = iter(f"r{i}" for i in range(99))
        for _ in range(18):
            roll = rng.random()
            if roll < 0.3 or not live:
                rid = next(nxt)
                j.admit(_req(3, rid, max_new_tokens=50))
                live.append(rid)
            elif roll < 0.85:
                j.token(rng.choice(live), rng.randrange(1000))
            else:
                j.finish(live.pop(rng.randrange(len(live))), "done")
        j.close()
        blob = jp.read_bytes()

        for cut in range(len(blob) + 1):
            tp = tmp_path / "t.jsonl"
            tp.write_bytes(blob[:cut])
            admits, toks, dones = [], {}, set()
            for rec in self._complete_lines(blob[:cut]):
                if rec["k"] == "admit":
                    admits.append(rec["id"])
                elif rec["k"] == "tok":
                    toks.setdefault(rec["id"], []).append(rec["tok"])
                elif rec["k"] == "done":
                    dones.add(rec["id"])
            resume, finished, poisoned, clean = \
                RequestJournal(tp).recover()
            assert not clean and not finished and not poisoned, cut
            want = [rid for rid in admits if rid not in dones]
            assert [r.id for r in resume] == want, cut
            for r in resume:  # prefix-consistent payload, no dup/phantom
                assert r.tokens == toks.get(r.id, []), (cut, r.id)

    def test_router_journal_recovers_exact_prefix(self, tmp_path):
        import random

        from hyperion_tpu.serve.router_journal import RouterJournal

        rng = random.Random(11)
        jp = tmp_path / "full.jsonl"
        j = RouterJournal(jp)
        live: list[str] = []
        nxt = iter(f"q{i}" for i in range(99))
        for _ in range(18):
            roll = rng.random()
            if roll < 0.3 or not live:
                rid = next(nxt)
                j.dispatch(rid, line=json.dumps({"id": rid}),
                           replica=rng.randrange(2), session=None)
                live.append(rid)
            elif roll < 0.85:
                j.hwm(rng.choice(live), rng.randrange(12))
            else:
                j.done(live.pop(rng.randrange(len(live))), "done")
        j.close()
        blob = jp.read_bytes()

        for cut in range(len(blob) + 1):
            tp = tmp_path / "t.jsonl"
            tp.write_bytes(blob[:cut])
            order, lines, hwms, dones = [], {}, {}, set()
            for rec in self._complete_lines(blob[:cut]):
                if rec["k"] == "dispatch":
                    if rec["id"] not in lines:
                        order.append(rec["id"])
                        lines[rec["id"]] = rec["line"]
                    dones.discard(rec["id"])  # re-open semantics
                elif rec["k"] == "hwm":
                    hwms[rec["id"]] = max(hwms.get(rec["id"], 0),
                                          rec["i"])
                elif rec["k"] == "done":
                    dones.add(rec["id"])
            orphans, clean = RouterJournal(tp).recover()
            assert not clean, cut
            want = [rid for rid in order if rid not in dones]
            assert [o.id for o in orphans] == want, cut
            for o in orphans:
                assert o.line == lines[o.id], cut  # no phantom payload
                assert o.hwm == hwms.get(o.id, 0), (cut, o.id)


# ---------------------------------------------------- brownout governor


class TestBrownoutGovernor:
    def test_depth_hysteresis_no_flap(self):
        g = BrownoutGovernor(depth_high=8)  # low defaults to 4
        assert g.update(7) is None and not g.active
        assert g.update(8) == "enter" and g.active
        # between the watermarks: stays active, no transition spam
        for d in (7, 6, 5):
            assert g.update(d) is None and g.active
        assert g.update(4) == "exit" and not g.active
        # between the watermarks from below: stays OFF — the half the
        # hysteresis exists for
        for d in (5, 6, 7):
            assert g.update(d) is None and not g.active
        assert g.update(9) == "enter"

    def test_wait_watermark_enters_and_exits(self):
        g = BrownoutGovernor(depth_high=0, wait_high_s=1.0)
        for _ in range(10):
            g.observe_wait(2.0)
        assert g.update(0) == "enter"
        # exit clears the stale window, so recovery is immediate once
        # the observed waits are gone
        assert g.update(0) is None  # p95 still 2.0 > low 0.5
        g._waits.clear()
        g.observe_wait(0.1)
        assert g.update(0) == "exit"
        assert g.update(0) is None

    def test_both_signals_must_clear_to_exit(self):
        g = BrownoutGovernor(depth_high=4, wait_high_s=1.0)
        for _ in range(5):
            g.observe_wait(2.0)
        assert g.update(10) == "enter"
        assert g.update(0) is None  # depth fine, wait p95 still high
        g._waits.clear()
        g.observe_wait(0.0)
        assert g.update(10) is None  # wait fine, depth still high
        assert g.update(0) == "exit"

    def test_needs_a_watermark(self):
        with pytest.raises(ValueError):
            BrownoutGovernor(depth_high=0)


# ----------------------------------------------------- drain + shedding


class TestDrainDoor:
    def test_closed_queue_rejects_with_draining(self):
        q = AdmissionQueue(4, max_total_tokens=64)
        assert q.submit(_req(4)) == (True, None)
        q.close()
        ok, reason = q.submit(_req(4))
        assert not ok and reason == REJECT_DRAINING
        assert q.closed
        # already-accepted work still pops: drain finishes what it owes
        admit, _ = q.pop_ready(2)
        assert len(admit) == 1

    def test_shed_doomed_is_deadline_aware(self):
        q = AdmissionQueue(8, max_total_tokens=64)
        doomed = _req(4, "doomed", deadline_s=0.05)
        winner = _req(4, "winner", deadline_s=60.0)
        no_slo = _req(4, "no_slo")  # no deadline: never shed
        for r in (doomed, winner, no_slo):
            q.submit(r)
        now = time.monotonic()
        # est wait 1 s: doomed (50 ms headroom) cannot win; winner can
        shed = q.shed_doomed(now, est_wait_s=1.0)
        assert [r.id for r in shed] == ["doomed"]
        assert doomed.status == "rejected"
        assert len(q) == 2

    def test_shed_orders_most_doomed_first(self):
        q = AdmissionQueue(8, max_total_tokens=64)
        late = _req(4, "late", deadline_s=0.08)
        soon = _req(4, "soon", deadline_s=0.01)
        q.submit(late)
        q.submit(soon)
        shed = q.shed_doomed(time.monotonic(), est_wait_s=5.0)
        assert [r.id for r in shed] == ["soon", "late"]


# ------------------------------------------------------- chaos grammar


class TestServeChaosGrammar:
    def test_new_clauses_parse_with_keys(self):
        faults = chaos.parse_plan(
            "crash@tick=3,journal_io_fail@p=0.25,poison_request@id=req_7")
        assert [f.key for f in faults] == [
            "crash@tick=3", "journal_io_fail@p=0.25",
            "poison_request@id=req_7"]
        assert faults[0].unit == "tick"
        assert faults[2].rid == "req_7"

    def test_crash_is_tick_scoped_only(self):
        with pytest.raises(ValueError, match="unknown chaos clause"):
            chaos.parse_plan("crash@step=3")

    def test_crash_dispatch_clause_parses_router_scoped(self):
        (f,) = chaos.parse_plan("crash@dispatch=3")
        assert (f.kind, f.unit, f.step) == ("crash", "dispatch", 3)
        assert f.key == "crash@dispatch=3"
        # dispatch-scoped: the serve tick hook must NOT fire it (it
        # would os._exit — surviving the call IS the assertion)
        plan = chaos.ChaosPlan([f])
        plan.on_tick(3)
        plan.on_step(3)
        plan.on_dispatch(2)  # wrong count: no fire
        assert not plan._fired

    def test_conn_reset_clause_validates_and_draws_own_stream(self):
        with pytest.raises(ValueError, match="outside"):
            chaos.parse_plan("conn_reset@p=1.5")
        plan = chaos.ChaosPlan(chaos.parse_plan("conn_reset@p=1.0"))
        with pytest.raises(ConnectionResetError):
            plan.conn_reset("route_client_write")
        never = chaos.ChaosPlan(chaos.parse_plan("conn_reset@p=0.0"))
        for _ in range(64):
            never.conn_reset("route_client_write")
        # its own RNG stream: adding a reset plan must not shift the
        # io_fail draw sequence other tests pinned
        a = chaos.ChaosPlan(chaos.parse_plan("io_fail@p=0.5"), seed=3)
        b = chaos.ChaosPlan(
            chaos.parse_plan("io_fail@p=0.5,conn_reset@p=0.5"), seed=3)
        seq_a, seq_b = [], []
        for _ in range(32):
            for plan, seq in ((a, seq_a), (b, seq_b)):
                try:
                    plan.io_fail("t")
                    seq.append(0)
                except OSError:
                    seq.append(1)
            try:
                b.conn_reset("t")
            except ConnectionResetError:
                pass
        assert seq_a == seq_b

    def test_crash_dispatch_fires_once_per_lineage(self, tmp_path):
        """The supervised-router contract: a restarted life (same state
        path) passing the same dispatch count again must NOT re-die —
        proven in-process via the fire record, since the fire itself is
        os._exit."""
        state = tmp_path / "chaos_state.json"
        plan = chaos.ChaosPlan(chaos.parse_plan("crash@dispatch=3"),
                               state_path=state)
        plan._mark(plan.faults[0])  # what the dying life wrote
        life2 = chaos.ChaosPlan(chaos.parse_plan("crash@dispatch=3"),
                                state_path=state)
        life2.on_dispatch(3)  # surviving the call IS the assertion
        assert "crash@dispatch=3" in life2._fired

    def test_journal_p_validated(self):
        with pytest.raises(ValueError, match="outside"):
            chaos.parse_plan("journal_io_fail@p=1.5")

    def test_poison_only_fires_on_matching_request(self):
        """Unit isolation: poison_request must not fire from step/tick
        hooks nor for other request ids (on a match it would SIGKILL —
        reaching the assertion IS the test)."""
        plan = chaos.ChaosPlan(chaos.parse_plan("poison_request@id=evil"))
        plan.on_step(0)
        plan.on_tick(0)
        plan.on_request("innocent")
        assert not plan._fired  # poison is exempt from the fire record

    def test_journal_io_uses_its_own_rng_stream(self):
        """Adding a journal clause must not shift the io_fail@p draw
        sequence the checkpoint-retry tests pinned."""
        a = chaos.ChaosPlan(chaos.parse_plan("io_fail@p=0.5"), seed=3)
        b = chaos.ChaosPlan(
            chaos.parse_plan("io_fail@p=0.5,journal_io_fail@p=0.5"),
            seed=3)
        seq_a, seq_b = [], []
        for _ in range(32):
            for plan, seq in ((a, seq_a), (b, seq_b)):
                try:
                    plan.io_fail("t")
                    seq.append(0)
                except OSError:
                    seq.append(1)
            try:
                b.journal_io("t")  # interleave journal draws into b
            except OSError:
                pass
        assert seq_a == seq_b


# --------------------------------------------------- supervisor (serve)


class TestServeSupervisor:
    def test_loop_restarts_on_crash_and_gives_up(self):
        from hyperion_tpu.supervisor import (
            EXIT_GAVE_UP,
            Decision,
            supervise_loop,
        )

        rcs = [70, 70, 70, 70]
        attempts = []

        def child(argv, env):
            attempts.append(env["HYPERION_ATTEMPT"])
            return rcs.pop(0)

        rc = supervise_loop(["serve"], decide=lambda rc: Decision.restart(),
                            max_restarts=2, run_child=child,
                            sleep=lambda s: None, label="serve-supervisor")
        assert rc == EXIT_GAVE_UP
        assert attempts == ["0", "1", "2"]

    def test_loop_stops_on_success_and_usage(self):
        from hyperion_tpu.supervisor import Decision, supervise_loop

        assert supervise_loop(
            ["x"], decide=lambda rc: Decision.restart(), max_restarts=5,
            run_child=lambda a, e: 0, sleep=lambda s: None) == 0
        assert supervise_loop(
            ["x"], decide=lambda rc: Decision.restart(), max_restarts=5,
            run_child=lambda a, e: 2, sleep=lambda s: None) == 2

    def test_heartbeat_watchdog_kills_stale_child(self, tmp_path):
        """A child that never beats (wedged before its first beat) is
        SIGKILLed once the stale window passes and reported as hung."""
        from hyperion_tpu.supervisor import RC_HUNG, heartbeat_watchdog

        runner = heartbeat_watchdog(tmp_path / "heartbeat.json",
                                    stale_s=0.5, poll_s=0.05)
        t0 = time.monotonic()
        rc = runner([sys.executable, "-c", "import time; time.sleep(60)"],
                    None)
        assert rc == RC_HUNG
        assert time.monotonic() - t0 < 30

    def test_heartbeat_watchdog_fresh_child_exits_normally(self, tmp_path):
        from hyperion_tpu.supervisor import heartbeat_watchdog

        hb = tmp_path / "heartbeat.json"
        hb.write_text("{}")
        runner = heartbeat_watchdog(hb, stale_s=30.0, poll_s=0.05)
        assert runner([sys.executable, "-c", "raise SystemExit(7)"],
                      None) == 7

    def test_serve_strip_supervise_flags(self):
        from hyperion_tpu.serve.server import _strip_supervise_flags

        argv = ["--ckpt", "m.npz", "--supervise", "--max-restarts", "3",
                "--hang-timeout", "5", "--journal", "j.jsonl"]
        assert _strip_supervise_flags(argv) == [
            "--ckpt", "m.npz", "--journal", "j.jsonl"]
        assert _strip_supervise_flags(
            ["--max-restarts=3", "--hang-timeout=5", "--supervise"]) == []


# ------------------------------------------- socket-path crash handling


class TestStaleSocket:
    def test_stale_socket_unlinked_live_socket_refused(self, tmp_path):
        import socket as socket_mod

        from hyperion_tpu.serve.server import prepare_socket_path

        # nonexistent: no-op
        prepare_socket_path(str(tmp_path / "none.sock"))

        # stale file a crashed server left behind: unlinked
        stale = tmp_path / "stale.sock"
        s = socket_mod.socket(socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
        s.bind(str(stale))
        s.close()  # bound then closed without listen: connect refuses
        assert stale.exists()
        prepare_socket_path(str(stale))
        assert not stale.exists()

        # live listener: refused loudly, file untouched
        live = tmp_path / "live.sock"
        srv = socket_mod.socket(socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
        srv.bind(str(live))
        srv.listen(1)
        try:
            with pytest.raises(RuntimeError, match="live server"):
                prepare_socket_path(str(live))
            assert live.exists()
        finally:
            srv.close()


# ------------------------------------------------ doctor + diff (files)


class TestObsIntegration:
    def _stream(self, tmp_path, counters, gauges=None, events=()):
        run = "serve_rb"
        recs = [
            {"v": 1, "kind": "event", "name": "serve_start", "run": run,
             "proc": 0, "t_wall": 100.0, "t_mono": 1.0},
            {"v": 1, "kind": "span", "name": "serve_tick", "run": run,
             "proc": 0, "step": 1, "t_wall": 100.5, "t_mono": 1.5,
             "dur_ms": 2.0},
        ]
        for name, attrs in events:
            recs.append({"v": 1, "kind": "event", "name": name,
                         "run": run, "proc": 0, "t_wall": 101.0,
                         "t_mono": 2.0, **attrs})
        recs.append({
            "v": 1, "kind": "snapshot", "name": "metrics", "run": run,
            "proc": 0, "t_wall": 102.0, "t_mono": 3.0,
            "metrics": {"counters": {"serve_ticks": 5, **counters},
                        "gauges": {"queue_depth": 0.0, **(gauges or {})},
                        "histograms": {}},
        })
        recs.append({"v": 1, "kind": "event", "name": "serve_end",
                     "run": run, "proc": 0, "t_wall": 103.0,
                     "t_mono": 4.0, "completed": 3})
        p = tmp_path / "telemetry.jsonl"
        p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        return tmp_path

    def test_doctor_names_brownout_overload(self, tmp_path):
        from hyperion_tpu.obs import doctor

        d = doctor.diagnose(self._stream(
            tmp_path, {"serve_shed": 4, "serve_brownout_clamped": 2},
            gauges={"serve_brownout_active": 1.0}))
        assert d["verdict"] == "healthy"
        assert d["overload"], "brownout left no named incident"
        assert any("shed 4" in o for o in d["overload"])
        assert any("clamped" in o for o in d["overload"])
        assert any("ACTIVE" in o for o in d["overload"])
        assert "serving robustness" in d["reason"]
        md = doctor.render_markdown(d)
        assert "serve robustness" in md and "overload" in md

    def test_doctor_names_poisoned_request_and_journal_error(
            self, tmp_path):
        from hyperion_tpu.obs import doctor

        d = doctor.diagnose(self._stream(
            tmp_path,
            {"serve_poisoned": 1, "serve_journal_errors": 1,
             "serve_replayed": 2},
            events=[("request_poisoned",
                     {"request": "evil_1", "replays": 2})]))
        assert d["poisoned_requests"] == ["evil_1"]
        assert any("poison pill" in o and "evil_1" in o
                   for o in d["overload"])
        assert any("journal" in o for o in d["overload"])

    def test_smoke_script_has_kill_and_resume_round_trip(self):
        """The CI satellite: serve_smoke.sh must carry the supervised
        kill-and-resume leg (its flags are drift-guarded by
        test_serve.py's parser check like every other invocation)."""
        script = (Path(__file__).resolve().parents[1] / "scripts"
                  / "serve_smoke.sh").read_text()
        assert "--supervise" in script and "crash@tick" in script
        assert "--journal" in script


# -------------------------------------------- flight-record post-mortem


class TestFlightPostMortem:
    """Flight recorder × doctor: a serve loop that died without a
    terminal event must have its verdict cite the flight record's
    final ticks — the only evidence of what the loop was doing."""

    def _dead_stream(self, tmp_path, run="serve_fl"):
        recs = [
            {"v": 1, "kind": "event", "name": "serve_start", "run": run,
             "proc": 0, "t_wall": 100.0, "t_mono": 1.0},
        ]
        for i in range(6):
            recs.append({"v": 1, "kind": "span", "name": "serve_tick",
                         "run": run, "proc": 0, "step": i,
                         "t_wall": 100.0 + 0.1 * i,
                         "t_mono": 1.0 + 0.1 * i, "dur_ms": 2.0})
        # no serve_end: the loop died mid-flight
        (tmp_path / "telemetry.jsonl").write_text(
            "\n".join(json.dumps(r) for r in recs) + "\n")
        return tmp_path

    def test_hung_verdict_cites_flight_final_tick(self, tmp_path):
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.tickprof import FLIGHT_NAME, FLIGHT_SCHEMA

        run = "serve_fl"
        self._dead_stream(tmp_path, run)
        flight = {
            "v": FLIGHT_SCHEMA, "run": run, "pid": 4242,
            "t_wall": 100.6, "reason": "periodic", "tick": 41,
            "spills": 3, "active": 2, "queue": 5, "events": [],
            "ticks": [{"tick": 40, "total": 0.002},
                      {"tick": 41, "total": 0.002}],
            "tickprof": {"dominant": "journal", "dominant_frac": 0.61,
                         "ticks": 2},
        }
        (tmp_path / FLIGHT_NAME).write_text(json.dumps(flight))

        d = doctor.diagnose(tmp_path, now=100.6 + 10_000)
        assert d["verdict"] in ("hung", "crashed"), d["reason"]
        fl = d["flight"]
        assert fl and fl["final_tick"] == 41 and fl["spills"] == 3
        assert "flight record: last spill at tick 41" in d["reason"]
        assert "2 active + 5 queued" in d["reason"]
        assert "dominant segment journal 61%" in d["reason"]
        md = doctor.render_markdown(d)
        assert "| flight record |" in md and "`journal`" in md

    def test_other_runs_flight_record_is_ignored(self, tmp_path):
        """A stale flight.json from an earlier run in the same dir must
        not pollute this run's verdict (same run-filter contract as the
        heartbeat)."""
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.tickprof import FLIGHT_NAME

        self._dead_stream(tmp_path, "serve_fl")
        (tmp_path / FLIGHT_NAME).write_text(json.dumps(
            {"v": 1, "run": "somebody_else", "tick": 9, "reason": "x"}))
        d = doctor.diagnose(tmp_path, now=110_000.0)
        assert d["flight"] is None
        assert "flight record" not in d["reason"]

    def test_smoke_script_asserts_flight_and_dominant_segment(self):
        """The CI satellite: serve_smoke.sh's kill drill must assert
        flight.json lands, and its obs-top leg must check the
        dominant-segment column."""
        script = (Path(__file__).resolve().parents[1] / "scripts"
                  / "serve_smoke.sh").read_text()
        assert "flight.json" in script
        assert "flight_final_tick" in script
        assert "dominant_segment" in script
