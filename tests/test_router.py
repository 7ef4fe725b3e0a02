"""Replica-tier router: dispatch policy as pure host logic, the
ejection/readmission state machine, failover dedup, client reconnect,
the socket load driver, fleet doctor/diff integration — and the
subprocess acceptance drill (2 supervised replicas, one SIGKILLed
mid-stream, client output bit-identical to a single engine).

Everything except the acceptance class runs with ZERO jit compiles:
the router runtime itself is jax-free, so its tests drive it over
fake replicas that speak the wire protocol (tokens derived
deterministically from prompt+seed, exactly like the real engine's
guarantee) — the dispatch/failover/affinity machinery is exercised end
to end in a few seconds.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from hyperion_tpu.serve.replica import (
    EJECTED,
    READY,
    STARTING,
    ReplicaHandle,
)
from hyperion_tpu.serve.router import (
    Router,
    RouterPolicy,
    StreamDedup,
    build_parser,
)

REPO = Path(__file__).resolve().parents[1]


def beat(t, phase="serve", active=0, queue=0, pid=1):
    return {"v": 1, "run": "x", "pid": pid, "phase": phase,
            "t_wall": t, "t_mono": t, "beats": 1,
            "active": active, "queue": queue}


def mkreps(tmp_path, n):
    return [ReplicaHandle.under(tmp_path, i) for i in range(n)]


# --------------------------------------------------- state machine


class TestReplicaStateMachine:
    def test_only_serve_phase_beats_admit(self, tmp_path):
        rep = mkreps(tmp_path, 1)[0]
        assert rep.state == STARTING
        assert rep.observe_beat(beat(10.0, phase="load"), 10.0) is None
        assert rep.observe_beat(beat(11.0, phase="warmup"), 11.0) is None
        assert rep.state == STARTING
        assert rep.observe_beat(beat(12.0, phase="serve"), 12.0) == "ready"
        assert rep.state == READY

    def test_stale_ejects_and_only_newer_beat_readmits(self, tmp_path):
        rep = mkreps(tmp_path, 1)[0]
        rep.observe_beat(beat(10.0), 10.0)
        assert rep.check_stale(15.0, stale_s=10.0) is None
        reason = rep.check_stale(25.0, stale_s=10.0)
        assert reason and "stale" in reason
        assert rep.state == EJECTED and rep.ejected_at == 25.0
        # the crashed child's old heartbeat file is still on disk: a
        # re-read of the SAME beat must not readmit
        assert rep.observe_beat(beat(10.0), 26.0) is None
        assert rep.state == EJECTED
        # a beat newer than the file's last but OLDER than the ejection
        # must not readmit either
        assert rep.observe_beat(beat(20.0), 27.0) is None
        assert rep.state == EJECTED
        # only a genuinely fresh serve beat readmits
        assert rep.observe_beat(beat(28.0), 28.0) == "ready"
        assert rep.state == READY and rep.ejected_at is None

    def test_draining_replica_is_ejected_not_dispatched(self, tmp_path):
        """A replica that is still BEATING but has left the serve
        phases (graceful drain, done) must stop receiving dispatches —
        its queue rejects everything, and forwarding those rejections
        while healthy peers idle would be self-inflicted downtime."""
        rep = mkreps(tmp_path, 1)[0]
        assert rep.observe_beat(beat(1.0), 1.0) == "ready"
        assert rep.observe_beat(beat(2.0, phase="drain"), 2.0) == "ejected"
        assert rep.state == EJECTED
        assert "serve phase" in rep.eject_reason
        # a done beat while already ejected: no transition
        assert rep.observe_beat(beat(3.0, phase="done"), 3.0) is None
        # ... but a fresh serve beat (a restarted child) readmits
        assert rep.observe_beat(beat(4.0), 4.0) == "ready"

    def test_first_eject_reason_sticks(self, tmp_path):
        rep = mkreps(tmp_path, 1)[0]
        rep.observe_beat(beat(1.0), 1.0)
        assert rep.eject(2.0, "connection error") == "connection error"
        assert rep.eject(3.0, "child exit 70") == "connection error"
        assert rep.ejected_at == 2.0

    def test_load_score_adds_unseen_dispatches(self, tmp_path):
        rep = mkreps(tmp_path, 1)[0]
        rep.observe_beat(beat(1.0, active=2, queue=3), 1.0)
        assert rep.load_score() == 5
        rep.dispatched_since_beat += 4
        assert rep.load_score() == 9
        # a fresh beat folds them into its own active/queue
        rep.observe_beat(beat(2.0, active=4, queue=1), 2.0)
        assert rep.load_score() == 5


# ----------------------------------------------------- dispatch policy


def _ready_policy(tmp_path, n=3, **kw):
    pol = RouterPolicy(mkreps(tmp_path, n), **kw)
    pol.observe_beats(lambda p: beat(1.0), now=1.0)
    return pol


class TestRouterPolicy:
    def test_least_loaded_with_index_tiebreak(self, tmp_path):
        pol = _ready_policy(tmp_path)
        pol.replicas[0].hb_active = 2
        pol.replicas[1].hb_queue = 1
        rep, _ = pol.choose({"prompt_ids": [1, 2]})
        assert rep.index == 2
        # tie between 1 (score 1+1 dispatch... ) — reset and check tie
        pol2 = _ready_policy(tmp_path)
        rep, _ = pol2.choose({"prompt_ids": [1, 2]})
        assert rep.index == 0  # all zero: lowest index wins

    def test_choose_accounts_dispatches(self, tmp_path):
        pol = _ready_policy(tmp_path, n=2)
        seen = [pol.choose({"prompt_ids": [i]})[0].index
                for i in range(4)]
        # with no affinity key (short prompts), dispatch alternates by
        # the since-beat counter
        assert seen == [0, 1, 0, 1]

    def test_session_affinity_sticks(self, tmp_path):
        pol = _ready_policy(tmp_path)
        doc = {"session_id": "alice", "prompt_ids": [1]}
        first, m1 = pol.choose(doc)
        second, m2 = pol.choose(doc)
        assert first.index == second.index
        assert not m1["affinity_hit"] and m2["affinity_hit"]

    def test_prefix_affinity_needs_long_prefix(self, tmp_path):
        pol = _ready_policy(tmp_path, prefix_tokens=8)
        short = {"prompt_ids": list(range(4))}
        assert pol.affinity_key(short) is None
        long_a = {"prompt_ids": list(range(8)) + [99]}
        long_b = {"prompt_ids": list(range(8)) + [42]}
        assert pol.affinity_key(long_a) == pol.affinity_key(long_b)

    def test_affinity_yields_under_load_slack(self, tmp_path):
        pol = _ready_policy(tmp_path, n=2, affinity_slack=2)
        doc = {"session_id": "hot", "prompt_ids": [1]}
        target, _ = pol.choose(doc)
        # pile load onto the sticky target beyond the slack
        target.hb_active = 10
        other, meta = pol.choose(doc)
        assert other.index != target.index
        assert not meta["affinity_hit"]
        # ... and the key is REMAPPED to the new replica
        again, meta2 = pol.choose(doc)
        assert again.index == other.index and meta2["affinity_hit"]

    def test_affinity_skips_ejected_target(self, tmp_path):
        pol = _ready_policy(tmp_path, n=2)
        doc = {"session_id": "s", "prompt_ids": [1]}
        target, _ = pol.choose(doc)
        pol.eject(target, "crashed", now=2.0)
        rep, meta = pol.choose(doc)
        assert rep.index != target.index and not meta["affinity_hit"]

    def test_affinity_map_is_lru_bounded(self, tmp_path):
        pol = _ready_policy(tmp_path, affinity_cap=4)
        for i in range(10):
            pol.choose({"session_id": f"s{i}", "prompt_ids": [1]})
        assert len(pol._affinity) == 4

    def test_exclude_and_exhaustion(self, tmp_path):
        pol = _ready_policy(tmp_path, n=2)
        rep, _ = pol.choose({"prompt_ids": [1]}, exclude={0})
        assert rep.index == 1
        none, _ = pol.choose({"prompt_ids": [1]}, exclude={0, 1})
        assert none is None

    def test_observe_beats_full_cycle(self, tmp_path):
        pol = RouterPolicy(mkreps(tmp_path, 2))
        trs = pol.observe_beats(lambda p: beat(1.0), now=1.0)
        assert [t[0] for t in trs] == ["ready", "ready"]
        trs = pol.observe_beats(lambda p: beat(1.0), now=50.0,
                                stale_s=10.0)
        assert [t[0] for t in trs] == ["ejected", "ejected"]
        assert pol.ready_count == 0
        trs = pol.observe_beats(lambda p: beat(60.0), now=60.0)
        assert [t[0] for t in trs] == ["readmitted", "readmitted"]
        assert pol.ready_count == 2


# ------------------------------------------- cache-aware steering


def _advertise(pol, index, digest, t=2.0):
    """Deliver a fresh heartbeat carrying a hot-prefix advertisement
    (`prefix_roots`) to one replica, exactly as the engine's beat
    extra_fn publishes it."""
    b = beat(t)
    b["prefix_roots"] = [digest]
    pol.replicas[index].observe_beat(b, t)


class TestCacheAwareSteering:
    """The tiered-KV fleet half (serve/hostcache.py): replicas
    advertise hot prefix roots on heartbeats and the dispatch policy
    steers matching no-session requests there — pure host logic over
    fabricated beats, zero jit compiles."""

    # short prompt: BELOW prefix_tokens (32), so affinity_key() is None
    # — only the cache-aware term can see the shared prefix
    IDS = [7, 8, 9, 7]

    def test_heartbeat_advertises_and_clears_roots(self, tmp_path):
        from hyperion_tpu.serve.hostcache import prefix_root_digest

        rep = mkreps(tmp_path, 1)[0]
        d = prefix_root_digest(self.IDS)
        b = beat(1.0)
        b["prefix_roots"] = [d]
        assert rep.observe_beat(b, 1.0) == "ready"
        assert rep.hb_prefix_roots == (d,)
        # a later beat WITHOUT the key clears the advertisement — a
        # restarted (cold) engine must not keep attracting traffic on
        # its dead predecessor's word
        rep.observe_beat(beat(2.0), 2.0)
        assert rep.hb_prefix_roots == ()

    def test_no_session_burst_lands_on_advertiser(self, tmp_path):
        from hyperion_tpu.serve.hostcache import prefix_root_digest

        pol = _ready_policy(tmp_path)
        _advertise(pol, 2, prefix_root_digest(self.IDS))
        rep, meta = pol.choose({"prompt_ids": list(self.IDS)})
        assert rep.index == 2  # NOT the least-loaded tiebreak (0)
        assert meta["cache_hit"] and not meta["affinity_hit"]
        assert not meta["had_key"]  # steered purely by advertisement

    def test_degrades_to_least_loaded_past_slack(self, tmp_path):
        from hyperion_tpu.serve.hostcache import prefix_root_digest

        pol = _ready_policy(tmp_path, affinity_slack=2)
        _advertise(pol, 1, prefix_root_digest(self.IDS))
        pol.replicas[1].hb_active = 10  # advertiser is overloaded
        rep, meta = pol.choose({"prompt_ids": list(self.IDS)})
        assert rep.index == 0 and not meta["cache_hit"]

    def test_no_advertiser_degrades_to_least_loaded(self, tmp_path):
        pol = _ready_policy(tmp_path)
        rep, meta = pol.choose({"prompt_ids": list(self.IDS)})
        assert rep.index == 0 and not meta["cache_hit"]

    def test_steer_seeds_affinity_for_the_burst(self, tmp_path):
        from hyperion_tpu.serve.hostcache import prefix_root_digest

        pol = _ready_policy(tmp_path)
        _advertise(pol, 1, prefix_root_digest(self.IDS))
        doc = {"session_id": "burst", "prompt_ids": list(self.IDS)}
        first, m1 = pol.choose(doc)
        assert first.index == 1 and m1["cache_hit"]
        # the advertisement goes stale (next beat omits it) — the rest
        # of the burst STICKS via the affinity map the steer seeded
        pol.replicas[1].observe_beat(beat(3.0), 3.0)
        second, m2 = pol.choose(doc)
        assert second.index == 1
        assert m2["affinity_hit"] and not m2["cache_hit"]

    def test_affinity_hit_pre_empts_cache_term(self, tmp_path):
        from hyperion_tpu.serve.hostcache import prefix_root_digest

        pol = _ready_policy(tmp_path)
        doc = {"session_id": "s", "prompt_ids": list(self.IDS)}
        target, _ = pol.choose(doc)
        # a DIFFERENT replica starts advertising the same root: the
        # established session must not bounce off its sticky target
        _advertise(pol, (target.index + 1) % 3,
                   prefix_root_digest(self.IDS))
        rep, meta = pol.choose(doc)
        assert rep.index == target.index
        assert meta["affinity_hit"] and not meta["cache_hit"]

    def test_cache_aware_off_disables_the_term(self, tmp_path):
        from hyperion_tpu.serve.hostcache import prefix_root_digest

        pol = _ready_policy(tmp_path, cache_aware=False)
        _advertise(pol, 2, prefix_root_digest(self.IDS))
        rep, meta = pol.choose({"prompt_ids": list(self.IDS)})
        assert rep.index == 0 and not meta["cache_hit"]

    def test_metrics_count_cache_steers(self):
        from hyperion_tpu.serve.metrics import RouterMetrics

        m = RouterMetrics()
        m.on_dispatch(0, affinity_hit=False, had_key=False)
        m.on_dispatch(1, affinity_hit=False, had_key=False,
                      cache_hit=True)
        assert m.summary()["cache_steered"] == 1


class TestReplicaArgvDrift:
    """The child command `replica_argv` builds from the ROUTE parser's
    namespace must parse against the SERVE parser it targets — a flag
    present on one surface but not the other fails here in tier-1, not
    at replica spawn time inside a live fleet."""

    def test_child_argv_parses_against_serve_surface(self, tmp_path):
        from hyperion_tpu.serve.router import replica_argv
        from hyperion_tpu.serve.server import build_parser as serve_parser

        args = build_parser().parse_args(
            ["--ckpt", "m.npz", "--replicas", "2",
             "--base-dir", str(tmp_path), "--host-cache-mb", "8"])
        rep = mkreps(tmp_path, 1)[0]
        argv = replica_argv(args, rep)
        assert argv[:4] == [sys.executable, "-m",
                            "hyperion_tpu.cli.main", "serve"]
        a = serve_parser().parse_args(argv[4:])
        assert a.slots == args.slots
        assert a.queue_capacity == args.queue_capacity
        assert a.host_cache_mb == 8

    def test_tier_off_route_spawns_tier_off_replicas(self, tmp_path):
        from hyperion_tpu.serve.router import replica_argv
        from hyperion_tpu.serve.server import build_parser as serve_parser

        args = build_parser().parse_args(
            ["--ckpt", "m.npz", "--base-dir", str(tmp_path)])
        a = serve_parser().parse_args(
            replica_argv(args, mkreps(tmp_path, 1)[0])[4:])
        assert a.host_cache_mb == 0


# ------------------------------------------------------------- dedup


class TestStreamDedup:
    def test_exactly_once_across_redispatch(self):
        d = StreamDedup()
        # first stream delivers 0..2 then dies
        for i in range(3):
            assert d.admit({"event": "token", "token": i, "i": i})
        # failover stream recomputes from 0: dups dropped, rest pass
        admitted = [i for i in range(6)
                    if d.admit({"event": "token", "token": i, "i": i})]
        assert admitted == [3, 4, 5]
        assert d.delivered == 6

    def test_terminals_always_pass(self):
        d = StreamDedup()
        assert d.admit({"event": "done"})
        assert d.admit({"event": "rejected", "reason": "x"})

    def test_missing_index_falls_back_to_counting(self):
        d = StreamDedup()
        assert d.admit({"event": "token", "token": 7})
        assert d.admit({"event": "token", "token": 8})
        assert d.delivered == 2


# ---------------------------------------------------- client reconnect


class TestClientReconnect:
    def test_connect_rides_through_late_bind(self, tmp_path):
        """The satellite: a server whose socket comes up LATE (a
        supervised restart) must be reconnectable, not fatal."""
        from hyperion_tpu.serve.client import ServeClient

        path = str(tmp_path / "late.sock")

        def bind_late():
            time.sleep(0.5)
            srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            srv.bind(path)
            srv.listen(1)
            conn, _ = srv.accept()
            conn.close()
            srv.close()

        t = threading.Thread(target=bind_late, daemon=True)
        t0 = time.monotonic()
        t.start()
        c = ServeClient(path, timeout_s=5.0).connect()
        assert time.monotonic() - t0 >= 0.4  # it actually waited
        c.close()
        t.join(timeout=5)

    def test_no_retry_fails_immediately(self, tmp_path):
        from hyperion_tpu.serve.client import ServeClient

        with pytest.raises(FileNotFoundError):
            ServeClient(str(tmp_path / "absent.sock"),
                        retry=None).connect()

    def test_retry_is_bounded(self, tmp_path):
        from hyperion_tpu.serve.client import ServeClient
        from hyperion_tpu.utils.retry import RetryPolicy

        t0 = time.monotonic()
        with pytest.raises(FileNotFoundError):
            ServeClient(str(tmp_path / "absent.sock"),
                        retry=RetryPolicy(tries=3, base_delay_s=0.01,
                                          max_delay_s=0.02,
                                          deadline_s=1.0)).connect()
        assert time.monotonic() - t0 < 2.0

    @staticmethod
    def _cutting_server(path, cut_at=2, n=6):
        """A serve-wire server whose FIRST connection dies after
        `cut_at` tokens; a reconnect speaking the resume verb gets the
        suffix. Returns the thread (daemon, serves two connections)."""

        def serve():
            srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            srv.bind(path)
            srv.listen(2)
            for life in range(2):
                conn, _ = srv.accept()
                f = conn.makefile("rb")
                doc = json.loads(f.readline())
                if doc.get("kind") == "resume":
                    rid = doc["request_id"]
                    start = int(doc["next_index"])
                else:
                    rid, start = doc["id"], 0
                stop = cut_at if life == 0 else n
                for i in range(start, stop):
                    conn.sendall((json.dumps(
                        {"id": rid, "event": "token", "token": 100 + i,
                         "i": i}) + "\n").encode())
                if life == 1:
                    conn.sendall((json.dumps(
                        {"id": rid, "event": "done",
                         "n_tokens": n}) + "\n").encode())
                conn.close()
            srv.close()

        t = threading.Thread(target=serve, daemon=True)
        t.start()
        return t

    def test_mid_stream_cut_raises_stream_interrupted(self, tmp_path):
        """The satellite bugfix: a wire death mid-stream must never
        read as a short-but-clean stream — without resume the client
        raises StreamInterrupted carrying the next index owed."""
        from hyperion_tpu.serve.client import ServeClient, StreamInterrupted

        path = str(tmp_path / "cut.sock")
        self._cutting_server(path, cut_at=2)
        got = []
        with pytest.raises(StreamInterrupted) as ei:
            with ServeClient(path, timeout_s=5.0) as c:
                for rec in c.stream(id="r1", prompt_ids=[1],
                                    max_new_tokens=6):
                    got.append(rec)
        assert [r["token"] for r in got] == [100, 101]
        assert ei.value.request_id == "r1"
        assert ei.value.next_index == 2
        assert isinstance(ei.value, ConnectionError)  # failover classifiable

    def test_resume_reconnects_and_dedups_to_one_stream(self, tmp_path):
        """resume=True: the same cut turns into reconnect + resume verb
        + index dedup — the caller sees one gapless stream and a real
        terminal event."""
        from hyperion_tpu.serve.client import ServeClient

        path = str(tmp_path / "res.sock")
        self._cutting_server(path, cut_at=2, n=6)
        with ServeClient(path, timeout_s=5.0, resume=True) as c:
            recs = list(c.stream(id="r2", prompt_ids=[1],
                                 max_new_tokens=6))
        toks = [r for r in recs if r.get("event") == "token"]
        assert [r["i"] for r in toks] == list(range(6))
        assert [r["token"] for r in toks] == [100 + i for i in range(6)]
        assert recs[-1]["event"] == "done"


# ------------------------------------------------- fake-replica fleet

# A wire-protocol replica with NO jax: tokens derive deterministically
# from (prompt, seed, index) — the same any-replica-same-stream
# guarantee the real engine gets from seeded sampling — so failover
# dedup is testable at full speed. Writes real heartbeat files.
FAKE_REPLICA = r'''
import json, os, socket, socketserver, sys, threading, time

sock_path, hb_path = sys.argv[1], sys.argv[2]
die_after = int(sys.argv[3]) if len(sys.argv) > 3 else -1
attempt = int(os.environ.get("HYPERION_ATTEMPT", "0") or 0)
# FAKE_ALERT=1: report a firing SLO alert on every beat, the way a
# real engine's obs/slo.py monitor would — exercises the router's
# fleet-alert tally without a real overload
alerts = ["ttft_p99"] if os.environ.get("FAKE_ALERT") else []

def beats():
    n = 0
    while True:
        n += 1
        tmp = hb_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"v": 1, "schema": 1, "run": "fake",
                       "pid": os.getpid(),
                       "phase": "serve", "t_wall": time.time(),
                       "t_mono": time.monotonic(), "beats": n,
                       "active": 0, "queue": 0, "alerts": alerts}, f)
        os.replace(tmp, hb_path)
        time.sleep(0.1)

threading.Thread(target=beats, daemon=True).start()

# inline exposition socket speaking the obs/export.py one-line wire
# protocol (the fake stays import-free): obs.sock next to the
# heartbeat, one JSON snapshot per connection — `obs top` reads the
# fleet through these
def expo():
    obs_path = os.path.join(os.path.dirname(hb_path), "obs.sock")
    class E(socketserver.StreamRequestHandler):
        def handle(self):
            self.wfile.write((json.dumps({
                "v": 1, "kind": "exposition", "pid": os.getpid(),
                "t_wall": time.time(), "role": "engine",
                "phase": "serve", "tick": 7, "active": 1, "slots": 2,
                "occupancy": 0.5, "queue": 0, "draining": False,
                "brownout": False, "blocks_in_use": 3,
                "alerts": alerts,
                "metrics": {"gauges": {"tokens_per_s": 42.0}},
                "windows": {"window_s": 60.0,
                            "histograms": {"ttft_ms": {"count": 5,
                                                       "p99": 12.5}},
                            "counters": {"tokens": {"delta": 60,
                                                    "per_s": 1.0}}},
            }) + "\n").encode())
    class ES(socketserver.ThreadingMixIn, socketserver.UnixStreamServer):
        daemon_threads = True
    if os.path.exists(obs_path):
        os.unlink(obs_path)
    ES(obs_path, E).serve_forever()

threading.Thread(target=expo, daemon=True).start()

def tok(psum, seed, i):
    return (psum * 31 + seed * 7 + i * 13) % 1000

class H(socketserver.StreamRequestHandler):
    def handle(self):
        for raw in self.rfile:
            doc = json.loads(raw)
            start = 0
            if doc.get("kind") == "resume":
                # the wire protocol's resume verb: recompute the SAME
                # deterministic stream, emit only the suffix the client
                # is owed (the real server drops i < next_index the
                # same way)
                req = doc.get("request") or {}
                rid = doc.get("request_id") or doc.get("id")
                start = int(doc.get("next_index", 0))
                doc = dict(req, id=rid)
            rid = doc["id"]; n = int(doc.get("max_new_tokens", 4))
            psum = sum(doc.get("prompt_ids", [])); seed = int(doc.get("seed", 0))
            for i in range(start, n):
                if die_after >= 0 and attempt == 0 \
                        and rid.startswith("kill") and i == die_after:
                    os._exit(1)
                self.wfile.write((json.dumps(
                    {"id": rid, "event": "token",
                     "token": tok(psum, seed, i), "i": i}) + "\n").encode())
                self.wfile.flush()
                time.sleep(0.02)
            self.wfile.write((json.dumps(
                {"id": rid, "event": "done", "n_tokens": n}) + "\n").encode())
            self.wfile.flush()

class S(socketserver.ThreadingMixIn, socketserver.UnixStreamServer):
    daemon_threads = True

if os.path.exists(sock_path):
    os.unlink(sock_path)
S(sock_path, H).serve_forever()
'''


@pytest.fixture()
def fake_replica_script(tmp_path):
    p = tmp_path / "fake_replica.py"
    p.write_text(FAKE_REPLICA)
    return p


class _Recorder:
    def __init__(self):
        self.records = []
        self._lock = threading.Lock()

    def write(self, rec):
        with self._lock:
            self.records.extend(rec if isinstance(rec, list) else [rec])


def _mk_router(tmp_path, script, n=2, die_after=-1, **over):
    from hyperion_tpu.obs.heartbeat import null_heartbeat
    from hyperion_tpu.obs.trace import null_tracer

    argv = ["--ckpt", "unused.npz", "--replicas", str(n),
            "--base-dir", str(tmp_path / "fleet"), "--no-tokenizer",
            "--dispatch-timeout", "20", "--stream-timeout", "30",
            "--stale-s", "2.0", "--hang-timeout", "0",
            "--drain-timeout", "5"]
    for k, v in over.items():
        argv += [f"--{k.replace('_', '-')}", str(v)]
    args = build_parser().parse_args(argv)

    def child_argv(a, rep):
        cmd = [sys.executable, str(script), rep.socket_path,
               rep.heartbeat_path]
        if rep.index == 0 and die_after >= 0:
            cmd.append(str(die_after))
        return cmd

    return Router(args, null_tracer(), null_heartbeat(),
                  child_argv_fn=child_argv)


def _by_request(records):
    toks, dones = {}, {}
    for r in records:
        if r.get("event") == "token":
            toks.setdefault(r["id"], []).append((r["i"], r["token"]))
        elif r.get("event") == "done":
            dones[r["id"]] = dones.get(r["id"], 0) + 1
    return toks, dones


class TestRouterRuntime:
    """The full router runtime — supervision, monitor, dispatch, relay,
    failover — over jax-free fake replicas. Zero jit compiles."""

    def test_dispatch_completes_and_spreads(self, tmp_path,
                                            fake_replica_script):
        router = _mk_router(tmp_path, fake_replica_script, n=2)
        try:
            router.start()
            assert router.wait_ready(2, timeout_s=20)
            out = _Recorder()
            threads = [router.submit_line(json.dumps(
                {"id": f"q{i}", "prompt_ids": [i, i + 1],
                 "max_new_tokens": 3, "seed": i}), out)
                for i in range(4)]
            for t in threads:
                t.join(timeout=20)
            toks, dones = _by_request(out.records)
            assert set(dones) == {f"q{i}" for i in range(4)}
            assert all(v == 1 for v in dones.values())
            share = router.metrics.summary()["per_replica_dispatched"]
            assert set(share) == {"0", "1"}  # both replicas served
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_failover_is_exactly_once_and_identical(self, tmp_path,
                                                    fake_replica_script):
        """Replica 0 dies after 3 tokens of the victim stream; the
        relay fails over to replica 1, which recomputes the SAME
        deterministic stream — the client sees indices 0..n-1 exactly
        once, matching an undisturbed request's values."""
        router = _mk_router(tmp_path, fake_replica_script, n=2,
                            die_after=3)
        try:
            router.start()
            assert router.wait_ready(2, timeout_s=20)
            out = _Recorder()
            # pin the victim to replica 0 via session affinity, then a
            # control request with the same payload on replica 1
            t1 = router.submit_line(json.dumps(
                {"id": "kill_1", "session_id": "a",
                 "prompt_ids": [5, 6], "max_new_tokens": 8,
                 "seed": 3}), out)
            t1.join(timeout=30)
            toks, dones = _by_request(out.records)
            assert dones.get("kill_1") == 1
            idx = [i for i, _ in toks["kill_1"]]
            assert idx == list(range(8)), idx  # no dup, no gap
            # deterministic contract: values match the fake's formula
            psum, seed = 5 + 6, 3
            assert [t for _, t in toks["kill_1"]] == [
                (psum * 31 + seed * 7 + i * 13) % 1000 for i in range(8)]
            s = router.metrics.summary()
            assert s["redispatched"] >= 1 and s["ejections"] >= 1
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_draining_router_rejects_new_work(self, tmp_path,
                                              fake_replica_script):
        router = _mk_router(tmp_path, fake_replica_script, n=1)
        try:
            router.start()
            assert router.wait_ready(1, timeout_s=20)
            router.begin_drain()
            out = _Recorder()
            assert router.submit_line(json.dumps(
                {"id": "late", "prompt_ids": [1],
                 "max_new_tokens": 2}), out) is None
            assert out.records[0]["event"] == "rejected"
            assert out.records[0]["reason"] == "draining"
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_malformed_line_rejected_not_fatal(self, tmp_path,
                                               fake_replica_script):
        router = _mk_router(tmp_path, fake_replica_script, n=1)
        try:
            router.start()
            out = _Recorder()
            assert router.submit_line("{not json", out) is None
            assert out.records[0]["event"] == "error"
            assert router.metrics.summary()["rejected"] == 1
        finally:
            router._hard_stop.set()
            router.shutdown()


# ---------------------------------------------- router WAL + resume


class TestRouterWal:
    """The dispatch WAL and the resume verb over the jax-free runtime:
    what a router life journals, what the next life recovers, and how a
    client's resume replays exactly the suffix owed."""

    def test_dispatch_hwm_done_journaled_and_clean_close(self, tmp_path,
                                                         fake_replica_script):
        from hyperion_tpu.serve.router_journal import RouterJournal

        router = _mk_router(tmp_path, fake_replica_script, n=1)
        jpath = tmp_path / "fleet" / "router_journal.jsonl"
        try:
            router.start()
            assert router.wait_ready(1, timeout_s=20)
            out = _Recorder()
            t = router.submit_line(json.dumps(
                {"id": "w1", "prompt_ids": [2, 3], "max_new_tokens": 3,
                 "seed": 1}), out)
            t.join(timeout=20)
            recs = [json.loads(line) for line in
                    jpath.read_text().splitlines()]
            kinds = [(r["k"], r.get("id")) for r in recs]
            assert ("dispatch", "w1") in kinds
            assert ("done", "w1") in kinds
            hwms = [r["i"] for r in recs
                    if r["k"] == "hwm" and r["id"] == "w1"]
            assert hwms and hwms[-1] == 3  # every forwarded token marked
            disp = next(r for r in recs if r["k"] == "dispatch")
            assert json.loads(disp["line"])["id"] == "w1"  # wire line rides
        finally:
            router._hard_stop.set()
            router.shutdown()
        # the idle drain close-cleans: nothing for a next life to recover
        orphans, clean = RouterJournal(jpath).recover()
        assert clean and orphans == []

    def test_resume_verb_replays_suffix_exactly_once(self, tmp_path,
                                                     fake_replica_script):
        """A client that received 4 tokens resumes {request_id,
        next_index=4}: the router re-dispatches through the resume verb
        with the dedup floored there — the writer sees ONLY the suffix,
        bit-identical to the deterministic stream."""
        router = _mk_router(tmp_path, fake_replica_script, n=2)
        try:
            router.start()
            assert router.wait_ready(2, timeout_s=20)
            out = _Recorder()
            t = router.submit_line(json.dumps(
                {"id": "v1", "prompt_ids": [5, 6], "max_new_tokens": 8,
                 "seed": 3}), out)
            t.join(timeout=20)
            res = _Recorder()
            t = router.submit_line(json.dumps(
                {"kind": "resume", "request_id": "v1",
                 "next_index": 4}), res)
            assert t is not None
            t.join(timeout=20)
            toks, dones = _by_request(res.records)
            assert dones.get("v1") == 1
            psum, seed = 5 + 6, 3
            assert toks["v1"] == [
                (i, (psum * 31 + seed * 7 + i * 13) % 1000)
                for i in range(4, 8)]
            assert router.metrics.summary()["resumes"] == 1
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_resume_of_unknown_request_rejected(self, tmp_path,
                                                fake_replica_script):
        router = _mk_router(tmp_path, fake_replica_script, n=1)
        try:
            router.start()
            out = _Recorder()
            assert router.submit_line(json.dumps(
                {"kind": "resume", "request_id": "ghost",
                 "next_index": 2}), out) is None
            assert out.records[0]["event"] == "rejected"
            assert out.records[0]["reason"] == "unknown_request"
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_resume_falls_back_to_client_carried_request(self, tmp_path,
                                                         fake_replica_script):
        """A router life that never saw the request (fresh process, no
        WAL record) still answers a resume that carries the original
        request body — the client's copy is the source of last resort."""
        router = _mk_router(tmp_path, fake_replica_script, n=1)
        try:
            router.start()
            assert router.wait_ready(1, timeout_s=20)
            out = _Recorder()
            t = router.submit_line(json.dumps(
                {"kind": "resume", "request_id": "c1", "next_index": 2,
                 "request": {"prompt_ids": [7, 8], "max_new_tokens": 5,
                             "seed": 2}}), out)
            assert t is not None
            t.join(timeout=20)
            toks, dones = _by_request(out.records)
            assert dones.get("c1") == 1
            psum, seed = 7 + 8, 2
            assert toks["c1"] == [
                (i, (psum * 31 + seed * 7 + i * 13) % 1000)
                for i in range(2, 5)]
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_next_life_recovers_orphans_from_wal(self, tmp_path,
                                                 fake_replica_script):
        """A WAL a dead router life left behind (dispatch, hwm 3, no
        terminal) re-dispatches in jsonl mode floored at the journaled
        hwm — the union across lives is gapless and duplicate-free."""
        from hyperion_tpu.serve.router_journal import RouterJournal

        jpath = tmp_path / "fleet" / "router_journal.jsonl"
        jpath.parent.mkdir(parents=True)
        dead = RouterJournal(jpath)
        line = json.dumps({"id": "o1", "prompt_ids": [5, 6],
                           "max_new_tokens": 8, "seed": 3})
        dead.dispatch("o1", line=line, replica=0, session=None)
        dead.hwm("o1", 3)
        dead.close()  # handle closed, NO clean marker — the crash shape
        router = _mk_router(tmp_path, fake_replica_script, n=1)
        try:
            router.start()
            assert router.wait_ready(1, timeout_s=20)
            out = _Recorder()
            assert router.recover_journal(out) == 1
            deadline = time.monotonic() + 20
            while not any(r.get("event") == "done"
                          for r in out.records):
                assert time.monotonic() < deadline
                time.sleep(0.05)
            toks, dones = _by_request(out.records)
            assert dones.get("o1") == 1
            psum, seed = 5 + 6, 3
            assert toks["o1"] == [
                (i, (psum * 31 + seed * 7 + i * 13) % 1000)
                for i in range(3, 8)]
            s = router.metrics.summary()
            assert s["orphans_recovered"] == 1
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_socket_mode_parks_orphans_for_client_resume(self, tmp_path,
                                                         fake_replica_script):
        """Socket-mode recovery must NOT pre-emptively re-dispatch (it
        would race the reconnecting client): orphans park until the
        client's resume verb names them, and the client's own index —
        not the journaled hwm — floors the replay."""
        from hyperion_tpu.serve.router_journal import RouterJournal

        jpath = tmp_path / "fleet" / "router_journal.jsonl"
        jpath.parent.mkdir(parents=True)
        dead = RouterJournal(jpath)
        line = json.dumps({"id": "p1", "prompt_ids": [4, 4],
                           "max_new_tokens": 6, "seed": 1})
        dead.dispatch("p1", line=line, replica=0, session=None)
        dead.hwm("p1", 4)  # hwm may run one AHEAD of the client
        dead.close()
        router = _mk_router(tmp_path, fake_replica_script, n=1)
        try:
            router.start()
            assert router.wait_ready(1, timeout_s=20)
            assert router.recover_journal(None) == 1  # socket mode: park
            out = _Recorder()
            t = router.submit_line(json.dumps(
                {"kind": "resume", "request_id": "p1",
                 "next_index": 3}), out)  # client is BEHIND the hwm
            assert t is not None
            t.join(timeout=20)
            toks, dones = _by_request(out.records)
            assert dones.get("p1") == 1
            assert [i for i, _ in toks["p1"]] == [3, 4, 5]
        finally:
            router._hard_stop.set()
            router.shutdown()


# ------------------------------------------------- socket load driver


class TestLoadgenSocket:
    def test_workload_is_shared_with_inprocess_driver(self):
        from hyperion_tpu.serve.loadgen import LoadSpec, build_workload

        spec = LoadSpec(n_requests=6, seed=4, shared_prefix_tokens=8)
        a_arr, a_reqs = build_workload(spec)
        b_arr, b_reqs = build_workload(spec)
        assert list(a_arr) == list(b_arr)
        for x, y in zip(a_reqs, b_reqs):
            assert x.id == y.id and x.seed == y.seed
            assert x.max_new_tokens == y.max_new_tokens
            assert x.prompt_ids.tolist() == y.prompt_ids.tolist()
        # shared prefix really is shared
        p0 = a_reqs[0].prompt_ids[:8].tolist()
        assert all(r.prompt_ids[:8].tolist() == p0 for r in a_reqs)

    def test_socket_mode_drives_a_live_wire(self, tmp_path,
                                            fake_replica_script):
        """The satellite: loadgen's socket-target mode against a real
        unix-socket server (the fake replica speaks the exact serve
        wire protocol)."""
        from hyperion_tpu.serve.loadgen import LoadSpec, run_load_socket

        sock = str(tmp_path / "lg.sock")
        hb = str(tmp_path / "lg_hb.json")
        proc = subprocess.Popen(
            [sys.executable, str(fake_replica_script), sock, hb])
        try:
            t0 = time.monotonic()
            while not os.path.exists(sock):
                assert proc.poll() is None
                assert time.monotonic() - t0 < 10
                time.sleep(0.05)
            spec = LoadSpec(n_requests=5, rate_hz=50.0,
                            prompt_lens=(2, 3), max_new=(2, 3), seed=1)
            rep = run_load_socket(sock, spec, request_timeout_s=30)
            assert rep["mode"] == "socket"
            assert rep["completed"] == 5 and rep["rejected"] == 0
            assert rep["tokens"] > 0 and rep["tokens_per_s"] > 0
            assert rep["ttft_p50_ms"] is not None
        finally:
            proc.terminate()
            proc.wait(timeout=10)


# ----------------------------------------------- obs integration


class TestObsIntegration:
    def _fleet_dir(self, tmp_path, stale_age=400.0):
        base = tmp_path / "fleet"
        now = time.time()
        (base / "replica_0").mkdir(parents=True)
        (base / "replica_1").mkdir(parents=True)
        (base / "replica_0" / "heartbeat.json").write_text(json.dumps(
            {"v": 1, "run": "serve_r0_1", "pid": 11, "phase": "serve",
             "t_wall": now - stale_age, "t_mono": 0.0, "beats": 5,
             "active": 2, "queue": 1, "attempt": 0, "replica": 0}))
        (base / "replica_1" / "heartbeat.json").write_text(json.dumps(
            {"v": 1, "run": "serve_r1_1", "pid": 12, "phase": "done",
             "t_wall": now - 1.0, "t_mono": 0.0, "beats": 9,
             "active": 0, "queue": 0, "attempt": 0, "replica": 1}))
        recs = [
            {"kind": "event", "name": "router_start", "run": "route_1",
             "t_wall": now - 500.0, "t_mono": 0.0, "replicas": 2},
            {"kind": "event", "name": "replica_ejected", "run": "route_1",
             "t_wall": now - stale_age, "t_mono": 1.0, "replica": 0,
             "reason": "heartbeat stale"},
            {"kind": "event", "name": "router_end", "run": "route_1",
             "t_wall": now - 0.5, "t_mono": 2.0, "dispatched": 7,
             "completed": 7},
        ]
        (base / "telemetry.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in recs))
        return base

    def test_doctor_renders_fleet_and_names_dead_replica(self, tmp_path):
        from hyperion_tpu.obs.doctor import diagnose, render_markdown

        base = self._fleet_dir(tmp_path)
        d = diagnose(base)
        assert d["verdict"] == "healthy"  # the ROUTER drained cleanly
        states = {r["replica"]: r["state"] for r in d["fleet"]}
        assert states == {"0": "dead", "1": "done"}
        assert d["fleet_incidents"] and "replica 0" in d["fleet_incidents"][0]
        assert "fleet: replica 0 DEAD" in d["reason"]
        row0 = next(r for r in d["fleet"] if r["replica"] == "0")
        assert row0["active"] == 2 and row0["queue"] == 1
        assert row0["ejections"] == 1
        md = render_markdown(d)
        assert "| replica 0 |" in md and "**dead**" in md
        assert "| replica 1 |" in md

    def test_doctor_quiet_when_fleet_healthy(self, tmp_path):
        from hyperion_tpu.obs.doctor import diagnose

        base = self._fleet_dir(tmp_path, stale_age=1.0)
        d = diagnose(base)
        assert not d["fleet_incidents"]
        assert all(r["state"] in ("beating", "done") for r in d["fleet"])

    def test_timeline_tags_replica_runs(self):
        from hyperion_tpu.obs.timeline import replica_of_run

        assert replica_of_run("serve_r3_1754000000") == 3
        assert replica_of_run("serve_1754000000") is None
        assert replica_of_run("route_1754000000") is None

    def test_smoke_script_route_invocation_parses(self):
        """Flag-drift guard (the capture-script pattern): the smoke
        script's `hyperion route` invocation must parse against the
        real router arg surface."""
        import re
        import shlex

        script = (REPO / "scripts" / "serve_smoke.sh").read_text()
        script = re.sub(r"\\\n\s*", " ", script)
        calls = re.findall(
            r"python -m hyperion_tpu\.cli\.main route\s+(.*)", script)
        assert len(calls) >= 2, (
            "serve_smoke.sh lost a router invocation (expected the "
            "crash drill AND the live obs top fleet)")
        parsed = []
        for call in calls:
            # strip shell artifacts: stderr redirects (` 2> file`),
            # stdout redirects, pipes, backgrounding
            call = re.split(r"\s2>", call)[0].split(">")[0]
            toks = [t for t in shlex.split(call) if t not in ("|", "&")]
            args = build_parser().parse_args(
                [re.sub(r"\$\{?\w+\}?", "x", t) for t in toks])
            assert args.replicas >= 2
            parsed.append(args)
        # the crash drill still carries its chaos plan, and the live
        # fleet probe carries an SLO target for the alert plane
        assert any(a.replica_chaos for a in parsed)
        assert any(a.slo_ttft_p99_ms > 0 for a in parsed)


# ------------------------------------------------- acceptance drill


class TestRouteAcceptance:
    @pytest.mark.slow
    def test_route_kill_one_replica_bit_identical(self, tmp_path):
        """The PR-9 acceptance subprocess test: `hyperion route` over 2
        supervised replicas under seeded load, replica 0 hard-crashed
        (os._exit via chaos crash@tick) mid-stream. Every admitted
        request completes with temp-0 output bit-identical to an
        uninterrupted single-engine run, no client stream carries a
        duplicate token, and the dead replica's restart shows journal
        replay on its telemetry.

        Marked slow: the supervised-ROUTER drill below kills a layer
        ABOVE this one and exercises the same replica failover + journal
        machinery on its way; this drill stays for `-m slow` depth."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from hyperion_tpu.checkpoint.io import export_gathered
        from hyperion_tpu.infer.generate import generate
        from hyperion_tpu.models.llama import Llama, llama_tiny_config

        model = Llama(llama_tiny_config(max_len=64))
        variables = {"params": model.init_params(jax.random.key(0),
                                                 seq=8)}
        ckpt = tmp_path / "llama.npz"
        export_gathered(ckpt, variables["params"])
        prompts = [np.asarray([3 + i, 4, 5, 6, 7, 8], np.int32)
                   for i in range(6)]
        budget = 10
        lines = "".join(
            json.dumps({"id": f"a{i}", "prompt_ids": p.tolist(),
                        "max_new_tokens": budget}) + "\n"
            for i, p in enumerate(prompts))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.pop("HYPERION_TELEMETRY", None)
        base = tmp_path / "fleet"
        # --min-ready 2: dispatch must spread over BOTH replicas before
        # the drill fires, so replica 0 always holds streams when it
        # dies; the short stdin tail keeps EOF from racing the crash
        r = subprocess.run(
            ["bash", "-c",
             f"(cat; sleep 2) | {sys.executable} -m "
             "hyperion_tpu.cli.main route --replicas 2 --min-ready 2 "
             f"--ckpt {ckpt} --no-tokenizer --base-dir {base} "
             "--max-len 64 --slots 2 --warmup-lens 8 "
             "--replica-heartbeat-every 1 "
             "--replica-chaos 0:crash@tick=2"],
            input=lines, env=env, capture_output=True, text=True,
            timeout=360, cwd=str(REPO),
        )
        assert r.returncode == 0, r.stderr[-3000:]

        toks: dict[str, list] = {}
        dones: dict[str, int] = {}
        for line in r.stdout.splitlines():
            rec = json.loads(line)  # router stdout carries ONLY wire
            if rec.get("event") == "token":
                toks.setdefault(rec["id"], []).append(
                    (rec["i"], rec["token"]))
            elif rec.get("event") == "done":
                dones[rec["id"]] = dones.get(rec["id"], 0) + 1
        # every admitted request: exactly one done, gapless dup-free
        # indices, tokens bit-identical to the single-engine oracle
        assert set(dones) == {f"a{i}" for i in range(6)}
        assert all(v == 1 for v in dones.values())
        for i, p in enumerate(prompts):
            got = toks[f"a{i}"]
            assert [ix for ix, _ in got] == list(range(budget)), got
            ref = np.asarray(generate(
                model, variables, jnp.asarray(p)[None],
                budget))[0].tolist()
            assert [t for _, t in got] == ref, f"a{i} diverged"
        # the crash really happened, and failover is on the router's
        # own stream
        assert "crash@tick" in r.stderr
        route = (base / "telemetry.jsonl").read_text()
        assert '"route_redispatch"' in route
        # the dead replica's journal still owes its in-flight requests
        # (failover delivered them, but ITS WAL cannot know): drain it
        # exactly as a supervised restart would — deterministic replay
        # evidence on the replica's own telemetry stream, independent
        # of how the in-run restart raced the router's drain window
        env2 = dict(env,
                    HYPERION_TELEMETRY=str(
                        base / "replica_0" / "telemetry.jsonl"))
        r2 = subprocess.run(
            [sys.executable, "-m", "hyperion_tpu.cli.main", "serve",
             "--ckpt", str(ckpt), "--no-tokenizer",
             "--max-len", "64", "--slots", "2", "--warmup-lens", "8",
             "--journal", str(base / "replica_0" / "journal.jsonl")],
            stdin=subprocess.DEVNULL, env=env2, capture_output=True,
            text=True, timeout=240, cwd=str(REPO))
        assert r2.returncode == 0, r2.stderr[-2000:]
        r0 = (base / "replica_0" / "telemetry.jsonl").read_text()
        recs = [json.loads(line) for line in r0.splitlines()
                if line.strip()]
        assert any(rec.get("name") == "journal_replayed"
                   and rec.get("resumed", 0) >= 1 for rec in recs)
        assert any(rec.get("name") == "serve_prefill"
                   and rec.get("resumed") for rec in recs)
        # ... and the drained journal owes nothing for a third life
        from hyperion_tpu.serve.journal import RequestJournal

        assert RequestJournal(
            base / "replica_0" / "journal.jsonl").pending_count() == 0

    def test_route_supervised_router_crash_resume(self, tmp_path):
        """THE acceptance drill for the router-SPOF tentpole:
        `hyperion route --supervise` over 2 REAL replicas, the router
        itself hard-exited mid-stream by chaos `crash@dispatch=3` while
        4 auto-resuming clients hold streams. The supervisor restarts
        the router; the new life re-adopts the still-live replicas
        (no respawn, no recompile), recovers the dispatch WAL, and
        answers the clients' resume verbs — every stream completes
        temp-0 bit-identical to the lone-engine `generate` oracle with
        gapless, duplicate-free indices across both router lives."""
        import signal as signal_mod

        import jax
        import jax.numpy as jnp
        import numpy as np

        from hyperion_tpu.checkpoint.io import export_gathered
        from hyperion_tpu.infer.generate import generate
        from hyperion_tpu.models.llama import Llama, llama_tiny_config
        from hyperion_tpu.serve.client import ServeClient

        model = Llama(llama_tiny_config(max_len=64))
        variables = {"params": model.init_params(jax.random.key(0),
                                                 seq=8)}
        ckpt = tmp_path / "llama.npz"
        export_gathered(ckpt, variables["params"])
        prompts = [np.asarray([3 + i, 4, 5, 6, 7, 8], np.int32)
                   for i in range(4)]
        budget = 10
        oracle = {
            f"s{i}": np.asarray(generate(
                model, variables, jnp.asarray(p)[None],
                budget))[0].tolist()
            for i, p in enumerate(prompts)}

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.pop("HYPERION_TELEMETRY", None)
        base = tmp_path / "fleet"
        sock = str(tmp_path / "route.sock")
        out_log = open(tmp_path / "route.out", "wb")
        err_log = open(tmp_path / "route.err", "wb")
        proc = subprocess.Popen(
            [sys.executable, "-m", "hyperion_tpu.cli.main", "route",
             "--supervise", "--replicas", "2", "--min-ready", "2",
             "--ckpt", str(ckpt), "--no-tokenizer",
             "--base-dir", str(base), "--max-len", "64", "--slots", "2",
             "--warmup-lens", "8", "--replica-heartbeat-every", "1",
             "--socket", sock, "--chaos", "crash@dispatch=3"],
            env=env, cwd=str(REPO), stdout=out_log, stderr=err_log,
            start_new_session=True)
        try:
            t0 = time.monotonic()
            while True:
                probe = socket.socket(socket.AF_UNIX,
                                      socket.SOCK_STREAM)
                probe.settimeout(1.0)
                try:
                    probe.connect(sock)
                    probe.close()
                    break
                except OSError:
                    probe.close()
                    assert proc.poll() is None, "supervisor died early"
                    assert time.monotonic() - t0 < 240, \
                        "router socket never came up"
                    time.sleep(0.2)

            results: dict[str, dict] = {}
            errors: list[str] = []

            def drive(i):
                try:
                    with ServeClient(sock, timeout_s=120.0,
                                     resume=True) as c:
                        results[f"s{i}"] = c.generate(
                            id=f"s{i}",
                            prompt_ids=prompts[i].tolist(),
                            max_new_tokens=budget)
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(f"s{i}: {e!r}")

            threads = [threading.Thread(target=drive, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            assert not errors, f"streams failed: {errors}"
            assert not any(t.is_alive() for t in threads), \
                "a resuming client hung"
            for rid, ref in oracle.items():
                res = results[rid]
                assert res["final"]["event"] == "done", (rid, res)
                assert res["tokens"] == ref, (
                    f"{rid} diverged across router lives")

            # the drill really happened: chaos fired (router stdout),
            # the supervisor restarted the router (its stderr), and the
            # new life ADOPTED the surviving replicas and answered
            # resumes (control-plane telemetry)
            deadline = time.monotonic() + 30
            while True:
                out_txt = (tmp_path / "route.out").read_text(
                    errors="replace")
                err_txt = (tmp_path / "route.err").read_text(
                    errors="replace")
                if "crash@dispatch=3" in out_txt \
                        and "route-supervisor] router exit" in err_txt:
                    break
                assert time.monotonic() < deadline, (
                    f"no crash/restart evidence:\n{err_txt[-2000:]}")
                time.sleep(0.5)
            names = []
            for line in (base / "telemetry.jsonl").read_text() \
                    .splitlines():
                try:
                    names.append(json.loads(line).get("name"))
                except json.JSONDecodeError:
                    pass
            assert names.count("replica_adopted") >= 2, (
                "restarted router respawned instead of adopting: "
                f"{names.count('replica_adopted')}")
            assert names.count("route_resume") >= 1, names
            assert "route_orphan_recovered" in names, names

            # graceful drain: TERM the router CHILD (heartbeat pid);
            # exit 0 stops the supervisor loop
            hb = json.loads((base / "heartbeat.json").read_text())
            os.kill(int(hb["pid"]), signal_mod.SIGTERM)
            assert proc.wait(timeout=60) == 0
        finally:
            out_log.close()
            err_log.close()
            try:
                os.killpg(proc.pid, signal_mod.SIGKILL)
            except (OSError, ProcessLookupError):
                pass


# ------------------------------------------- live fleet observability


class TestLiveFleetObservability:
    """`obs top` + fleet alert surfacing over the REAL router runtime
    (fake replicas speaking the exposition wire protocol) — zero jit
    compiles, like the rest of the runtime tests."""

    def test_obs_top_reads_running_fleet_sockets(self, tmp_path,
                                                 fake_replica_script):
        from hyperion_tpu.obs.top import sample_all

        router = _mk_router(tmp_path, fake_replica_script, n=2)
        try:
            router.start()
            assert router.wait_ready(2, timeout_s=20)
            deadline = time.monotonic() + 20
            while True:
                rows = sample_all(tmp_path / "fleet")
                live = [r for r in rows if r["state"] == "live"]
                if len(live) == 2:
                    break
                assert time.monotonic() < deadline, rows
                time.sleep(0.2)
            for r in live:
                # the live columns come off the exposition socket, not
                # the heartbeat file
                assert r["source"] == "socket"
                assert r["occupancy"] == 0.5
                assert r["ttft_p99_ms"] == 12.5
                assert r["tokens_per_s"] == 1.0
                assert r["blocks_in_use"] == 3
                assert r["alerts"] == []
        finally:
            router._hard_stop.set()
            router.shutdown()
        # fleet stopped: the sockets stop answering and the SAME
        # sampler degrades every row to its heartbeat file
        rows = sample_all(tmp_path / "fleet", stale_s=3600.0)
        assert rows and all(r["source"] != "socket" for r in rows)

    def test_router_tallies_fleet_alerts(self, tmp_path,
                                         fake_replica_script,
                                         monkeypatch):
        monkeypatch.setenv("FAKE_ALERT", "1")
        router = _mk_router(tmp_path, fake_replica_script, n=2)
        try:
            router.start()
            assert router.wait_ready(2, timeout_s=20)
            deadline = time.monotonic() + 10
            while router.metrics.summary()["fleet_alerts_raised"] < 2:
                assert time.monotonic() < deadline, \
                    router.metrics.summary()
                time.sleep(0.1)
            s = router.metrics.summary()
            assert s["fleet_alerts_raised"] == 2   # one raise per replica
            assert s["fleet_alerts_active"] == 2
            exp = router.exposition()
            assert exp["role"] == "router"
            assert sorted(exp["alerts"]) == ["r0:ttft_p99",
                                             "r1:ttft_p99"]
            assert all(r["alerts"] == ["ttft_p99"]
                       for r in exp["replicas"])
            # a PERSISTING alert never re-counts on later beats — the
            # tally is raises, not beat-observations
            time.sleep(0.5)
            assert router.metrics.summary()["fleet_alerts_raised"] == 2
        finally:
            router._hard_stop.set()
            router.shutdown()

    def test_dead_replica_alert_stops_counting(self, tmp_path):
        """A ghost must not page: an ejected/dead replica's
        last-reported alert leaves the live fleet tally (the dead
        replica itself is the incident), and a readmitted replica
        still alerting counts as a NEW raise."""
        router = _mk_router(tmp_path, tmp_path / "unused.py", n=2)
        r0, r1 = router.replicas
        r0.state = READY
        r0.hb_alerts = ("ttft_p99",)
        r1.state = READY
        assert router._sweep_fleet_alerts() == ["r0:ttft_p99"]
        assert router.metrics.summary()["fleet_alerts_raised"] == 1
        assert router.exposition()["alerts"] == ["r0:ttft_p99"]
        # the replica dies: its stale alarm stops counting fleet-wide
        r0.state = EJECTED
        assert router._sweep_fleet_alerts() == []
        assert router.exposition()["alerts"] == []
        # ...but the per-replica evidence row keeps the last word
        row0 = router.exposition()["replicas"][0]
        assert row0["state"] == EJECTED
        assert row0["alerts"] == ["ttft_p99"]
        # readmitted and still alerting: a new observation epoch —
        # honestly re-raised, not deduped against the old life
        r0.state = READY
        assert router._sweep_fleet_alerts() == ["r0:ttft_p99"]
        assert router.metrics.summary()["fleet_alerts_raised"] == 2

    def test_route_slo_monitor_fires_on_fleet_rejects(self, tmp_path):
        """The router-level burn-rate monitor (route_ prefix) over its
        own windowed relay outcomes — pure host logic, no children."""
        from hyperion_tpu.obs.registry import MetricsRegistry
        from hyperion_tpu.obs.slo import SLOMonitor, SLOTarget
        from hyperion_tpu.serve.router import _route_window_value

        reg = MetricsRegistry()
        mon = SLOMonitor(
            (SLOTarget("route_reject_rate", "reject_rate", 0.1),),
            reg, fast_s=10.0, slow_s=30.0, eval_every_s=0.0,
            value_fn=_route_window_value)
        for _ in range(8):
            reg.counter("route_completed").inc()
        assert mon.evaluate() == []          # 0% rejects: quiet
        for _ in range(4):
            reg.counter("route_rejected").inc()
        (tr,) = mon.evaluate()
        assert tr["kind"] == "raised" and tr["alert"] == "route_reject_rate"
        assert tr["fast"] == pytest.approx(1 / 3)

# ------------------------------------------------- acting on alerts


class TestActingRouter:
    """PR 14: the router ACTS on the alerts it tallies — steers
    interactive traffic off TTFT-burning replicas, orders batch-class
    brownouts, and scales standbys — all as pure host logic over
    fabricated heartbeats. Zero jit compiles, zero child processes."""

    def test_steered_replica_skipped_for_interactive_only(self, tmp_path):
        pol = _ready_policy(tmp_path, n=2)
        pol.set_steered(pol.replicas[0], True)
        rep, meta = pol.choose({"prompt_ids": [1]})
        assert rep.index == 1 and meta["steered_away"]
        # batch traffic still flows to the steered replica (it is the
        # least-loaded one — interactive was just moved off it)
        rep_b, meta_b = pol.choose({"class": "batch",
                                    "prompt_ids": [1]})
        assert rep_b.index == 0 and not meta_b["steered_away"]
        # every replica steered: interactive falls back to the full
        # ready set rather than refusing service
        pol.set_steered(pol.replicas[1], True)
        rep2, meta2 = pol.choose({"prompt_ids": [2]})
        assert rep2 is not None and not meta2["steered_away"]

    def test_sweep_steers_on_ttft_alert_with_hysteresis(self, tmp_path):
        router = _mk_router(tmp_path, tmp_path / "unused.py", n=2)
        r0, r1 = router.replicas
        r0.state = READY
        r1.state = READY
        r0.hb_alerts = ("ttft_p99",)
        assert router._sweep_actions() == 1
        assert r0.steered and not r1.steered
        s = router.metrics.summary()
        assert s["steers"] == 1 and s["steered_now"] == 1
        assert s["class_brownouts"] == 1  # ordered (no ack — no child)
        assert router.exposition()["act"]["steered"] == [0]
        # still burning: steering is idempotent, no double count
        assert router._sweep_actions() == 1
        assert router.metrics.summary()["steers"] == 1
        # alert clears: unsteer only after N CONSECUTIVE clean sweeps
        r0.hb_alerts = ()
        router._sweep_actions()
        router._sweep_actions()
        assert r0.steered  # 2 of 3
        r0.hb_alerts = ("ttft_p99",)  # relapse resets the count
        router._sweep_actions()
        r0.hb_alerts = ()
        router._sweep_actions()
        router._sweep_actions()
        assert r0.steered
        router._sweep_actions()  # third consecutive clean sweep
        assert not r0.steered
        s = router.metrics.summary()
        assert s["unsteers"] == 1 and s["steered_now"] == 0

    def test_ejected_silence_is_not_recovery(self, tmp_path):
        router = _mk_router(tmp_path, tmp_path / "unused.py", n=2,
                            steer_clear_sweeps=1)
        r0, _ = router.replicas
        r0.state = READY
        r0.hb_alerts = ("ttft_p99",)
        router._sweep_actions()
        assert r0.steered
        # the replica dies with the alert latched: its silence must
        # not count toward unsteering
        r0.state = EJECTED
        r0.hb_alerts = ()
        for _ in range(3):
            router._sweep_actions()
        assert r0.steered
        r0.state = READY  # readmitted and clean: NOW it unsteers
        router._sweep_actions()
        assert not r0.steered

    def test_scale_governor_spawns_and_retires_standby(self, tmp_path):
        router = _mk_router(tmp_path, tmp_path / "unused.py", n=2,
                            max_replicas=3)
        router._supervise_one = lambda rep: None  # no real children
        r0, r1 = router.replicas
        r0.state = READY
        r1.state = READY
        assert router._scale_gov is not None
        r0.hb_alerts = ("ttft_p99",)
        router._sweep_actions()  # burning=1: governor enters, scale up
        assert len(router.replicas) == 3
        standby = router.replicas[2]
        assert standby.standby and not standby.retiring
        s = router.metrics.summary()
        assert s["scale_up"] == 1 and s["scale_down"] == 0
        assert router.exposition()["act"]["fleet"] == 3
        # burn persists: no second spawn (governor already entered)
        router._sweep_actions()
        assert len(router.replicas) == 3
        assert router.metrics.summary()["scale_up"] == 1
        # burn clears: governor exits, the standby retires
        r0.hb_alerts = ()
        router._sweep_actions()
        assert standby.retiring
        assert standby.state == EJECTED
        s = router.metrics.summary()
        assert s["scale_down"] == 1

    def test_no_act_flag_disables_the_acting_half(self, tmp_path):
        router = _mk_router(tmp_path, tmp_path / "unused.py", n=2)
        router._act = False
        r0, _ = router.replicas
        r0.state = READY
        r0.hb_alerts = ("ttft_p99",)
        assert router._sweep_actions() == 0
        assert not r0.steered
        assert router.metrics.summary()["steers"] == 0
