#!/usr/bin/env bash
# Serve-path smoke: tiny checkpoint -> `hyperion serve` over stdin ->
# three JSONL requests -> assert three clean `done` events and a clean
# drain (exit 0). Chip-free (host backend) and fast (<1 min): the
# cheapest end-to-end proof that the engine, the admission queue, the
# JSONL transport, and the tokenizer round-trip compose.
#
#   scripts/serve_smoke.sh [workdir]
set -euo pipefail

WORK="${1:-$(mktemp -d /tmp/serve_smoke.XXXXXX)}"
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"
export JAX_PLATFORMS=cpu

echo "[serve_smoke] workdir: $WORK"

# 1. tiny tokenizer + tiny random-init Llama export (the same recipe
#    the generation-CLI tests use)
python - "$WORK" <<'PY'
import sys

import jax

from hyperion_tpu.checkpoint.io import export_gathered
from hyperion_tpu.data.bpe import train_bpe
from hyperion_tpu.models.llama import Llama, llama_tiny_config

work = sys.argv[1]
tok = train_bpe(["the quick brown fox jumps over the lazy dog"] * 4,
                vocab_size=256, verbose=False)
tok.save(f"{work}/tok")
cfg = llama_tiny_config(vocab_size=tok.vocab_size, max_len=64)
export_gathered(f"{work}/llama.npz",
                Llama(cfg).init_params(jax.random.key(0), seq=8))
print(f"[serve_smoke] wrote {work}/llama.npz + tokenizer")
PY

# 2. three JSONL requests through the stdin transport; the server
#    drains on EOF and must exit 0
printf '%s\n' \
  '{"id":"a","prompt":"the quick","max_new_tokens":6}' \
  '{"id":"b","prompt":"lazy dog","max_new_tokens":4,"temperature":0.8,"top_k":8,"seed":7}' \
  '{"id":"c","prompt":"fox jumps over","max_new_tokens":5}' \
  | python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --tokenizer-dir "$WORK/tok" \
      --max-len 64 --slots 2 --warmup-lens 8 \
      > "$WORK/responses.jsonl"

# 3. assert: one `done` per request, no errors, drain was clean
python - "$WORK/responses.jsonl" <<'PY'
import json
import sys

lines = [json.loads(line) for line in open(sys.argv[1])]
dones = {r["id"] for r in lines if r.get("event") == "done"}
bad = [r for r in lines if r.get("event") in ("error", "rejected",
                                              "timed_out")]
assert dones == {"a", "b", "c"}, f"expected a/b/c done, got {dones}"
assert not bad, f"unexpected failure events: {bad}"
tokens = sum(1 for r in lines if r.get("event") == "token")
print(f"[serve_smoke] OK: 3 requests done, {tokens} tokens streamed, "
      "clean drain")
PY

# 4. shared-prefix round trip: two prompt_ids requests with a common
#    12-token prefix through a small-block paged cache, telemetry on —
#    the second request must HIT the radix prefix cache (counter > 0
#    on the stream), proving the paged reuse path end to end
printf '%s\n' \
  '{"id":"p1","prompt_ids":[3,4,5,6,7,8,9,10,11,12,13,14,20,21],"max_new_tokens":4}' \
  '{"id":"p2","prompt_ids":[3,4,5,6,7,8,9,10,11,12,13,14,30,31],"max_new_tokens":4}' \
  | env HYPERION_TELEMETRY="$WORK/tele.jsonl" \
    python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --tokenizer-dir "$WORK/tok" \
      --max-len 64 --slots 2 --warmup-lens 8 --block-size 4 \
      --prefix-cache \
      > "$WORK/prefix_responses.jsonl"

python - "$WORK/prefix_responses.jsonl" "$WORK/tele.jsonl" <<'PY'
import json
import sys

lines = [json.loads(line) for line in open(sys.argv[1])]
dones = {r["id"] for r in lines if r.get("event") == "done"}
assert dones == {"p1", "p2"}, f"expected p1/p2 done, got {dones}"
hits = saved = 0
for line in open(sys.argv[2]):
    rec = json.loads(line)
    if rec.get("kind") == "snapshot":
        c = rec.get("metrics", {}).get("counters", {})
        hits = max(hits, c.get("serve_prefix_hits", 0))
        saved = max(saved, c.get("serve_prefill_tokens_saved", 0))
assert hits >= 1, f"shared-prefix request never hit the prefix cache"
assert saved > 0, "prefix hit saved zero prefill tokens"
print(f"[serve_smoke] OK: prefix round trip — {hits} hit(s), "
      f"{saved} prefill tokens saved")
PY

# 5. `obs trace` round trip on the run we just produced: the trace
#    consumer must reconstruct every request, export a non-empty Chrome
#    trace, and attribute the tail — the observability half of the
#    serve path proven against a real stream, not a fixture
python -m hyperion_tpu.cli.main obs trace "$WORK/tele.jsonl" \
  --export "$WORK/trace.json" --top 3 > "$WORK/trace.md"

python - "$WORK/trace.json" "$WORK/trace.md" <<'PY'
import json
import sys

doc = json.load(open(sys.argv[1]))
evs = doc.get("traceEvents", [])
assert evs, "obs trace exported an empty Chrome trace"
xs = [e for e in evs if e.get("ph") == "X"]
assert xs, "no complete (X) events in the export"
assert all("ts" in e and e.get("dur", 0) >= 0 for e in xs)
reqs = {e["args"]["request"] for e in evs
        if e.get("args", {}).get("request")}
assert {"p1", "p2"} <= reqs, f"missing request rows: {reqs}"
md = open(sys.argv[2]).read()
assert "Tail attribution" in md and "dominant" in md
print(f"[serve_smoke] OK: obs trace — {len(evs)} trace events, "
      f"{len(reqs)} request rows, attribution table rendered")
PY

# 6. kill-and-resume round trip: a supervised server crashes HARD
#    (chaos crash@tick=2 is os._exit — no handlers, no flushes) mid-
#    decode; the supervisor restarts it and the request journal replays
#    the in-flight request. The client — this script's single stdout
#    capture across both process lives — receives the complete
#    continuation exactly once, bit-identical to an uninterrupted run.
KILLREQ='{"id":"k1","prompt_ids":[3,4,5,6,7,8],"max_new_tokens":10}'

printf '%s\n' "$KILLREQ" \
  | python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8,32 \
      > "$WORK/ref_responses.jsonl"

printf '%s\n' "$KILLREQ" \
  | env HYPERION_TELEMETRY="$WORK/kill_tele.jsonl" \
    python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8,32 \
      --journal "$WORK/kill_journal.jsonl" \
      --supervise --max-restarts 2 --hang-timeout 0 \
      --chaos crash@tick=2 \
      > "$WORK/kill_responses.jsonl"

python - "$WORK/ref_responses.jsonl" "$WORK/kill_responses.jsonl" \
         "$WORK/kill_tele.jsonl" <<'PY'
import json
import sys


def stream(path):
    toks, dones = [], 0
    for line in open(path):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue  # chaos chatter shares the child's stdout
        if rec.get("id") != "k1":
            continue
        if rec.get("event") == "token" and rec.get("token") is not None:
            toks.append(rec["token"])
        elif rec.get("event") == "done":
            dones += 1
    return toks, dones


ref, ref_dones = stream(sys.argv[1])
got, dones = stream(sys.argv[2])
assert ref_dones == 1 and len(ref) == 10, (ref_dones, ref)
assert dones == 1, f"expected exactly one done across both lives, got {dones}"
assert got == ref, f"continuation mismatch: {got} != {ref}"
resumed = any(
    rec.get("name") == "serve_prefill" and rec.get("resumed")
    for rec in (json.loads(l) for l in open(sys.argv[3]) if l.strip()))
assert resumed, "telemetry shows no resumed prefill — did the replay run?"
print(f"[serve_smoke] OK: kill-and-resume — {len(got)} tokens exactly "
      "once across 2 process lives, bit-identical to the uninterrupted "
      "run, replay visible on the stream")
PY

# the crash drill must leave a flight record next to the heartbeat:
# the crashed life spilled its tick ring periodically (os._exit gives
# no exit hook), and the replay life closed with a serve_end spill —
# either way the post-mortem artifact exists and is well-formed
python - "$WORK/flight.json" <<'PY'
import json
import sys

from hyperion_tpu.obs.tickprof import FLIGHT_SCHEMA, flight_final_tick

flight = json.load(open(sys.argv[1]))
assert flight.get("v") == FLIGHT_SCHEMA, flight.get("v")
assert flight.get("reason"), "flight record carries no spill reason"
assert isinstance(flight.get("ticks"), list), "flight record has no tick ring"
final = flight_final_tick(flight)
assert final is not None, "flight record names no final tick"
print(f"[serve_smoke] OK: flight record after crash drill — last spill "
      f"reason={flight['reason']!r} at tick {final}")
PY

# 7. replica-tier round trip: `hyperion route` over 2 supervised
#    replicas; replica 0 crashes HARD mid-stream (chaos crash@tick=2)
#    while requests are in flight. The router fails over in-flight
#    streams to replica 1 (seed-deterministic recompute + token-index
#    dedup), the supervisor restarts replica 0, and its journal replays
#    the owed work sink-less. The combined client stream must be
#    complete (every request exactly one done) and duplicate-free
#    (token indices strictly increasing per request), bit-identical to
#    the single-engine run of the same prompts.
ROUTEREQS="$WORK/route_reqs.jsonl"
python - "$ROUTEREQS" <<'PY'
import json
import sys

with open(sys.argv[1], "w") as f:
    for i in range(8):
        f.write(json.dumps({"id": f"m{i}",
                            "prompt_ids": [3 + i, 4, 5, 6, 7, 8],
                            "max_new_tokens": 10}) + "\n")
PY

# single-engine reference for bit-identity
cat "$ROUTEREQS" \
  | python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8 \
      > "$WORK/route_ref.jsonl"

# the fleet run: --min-ready 2 so dispatch spreads over both replicas
# before the drill fires (replica 0 must hold streams when it dies);
# stdin stays open a beat so the EOF drain never races the crash
(cat "$ROUTEREQS"; sleep 2) \
  | python -m hyperion_tpu.cli.main route \
      --replicas 2 --min-ready 2 --ckpt "$WORK/llama.npz" --no-tokenizer \
      --base-dir "$WORK/fleet" --max-len 64 --slots 2 --warmup-lens 8 \
      --replica-heartbeat-every 1 --replica-chaos 0:crash@tick=2 \
      > "$WORK/route_responses.jsonl"

# the dead replica's journal still owes its in-flight requests (the
# router delivered them via failover, but THIS replica's WAL doesn't
# know that): drain it the way a restarted replica would — the journal
# replay and its resumed prefills land on the replica's own telemetry
# stream, deterministically, however the in-run restart raced the
# router's drain window
cat /dev/null \
  | env HYPERION_TELEMETRY="$WORK/fleet/replica_0/telemetry.jsonl" \
    python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8 \
      --journal "$WORK/fleet/replica_0/journal.jsonl" \
      > /dev/null

python - "$WORK/route_ref.jsonl" "$WORK/route_responses.jsonl" \
         "$WORK/fleet" <<'PY'
import json
import sys
from pathlib import Path


def streams(path):
    toks, dones = {}, {}
    for line in open(path):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("event") == "token" and rec.get("token") is not None:
            toks.setdefault(rec["id"], []).append(
                (rec.get("i"), rec["token"]))
        elif rec.get("event") == "done":
            dones[rec["id"]] = dones.get(rec["id"], 0) + 1
    return toks, dones


ref_toks, ref_dones = streams(sys.argv[1])
got_toks, got_dones = streams(sys.argv[2])
ids = {f"m{i}" for i in range(8)}
assert set(got_dones) == ids and all(v == 1 for v in got_dones.values()), \
    f"expected one done per request, got {got_dones}"
for rid in ids:
    idx = [i for i, _ in got_toks[rid]]
    assert idx == sorted(set(idx)) == list(range(len(idx))), \
        f"{rid}: duplicate or gapped token indices {idx}"
    assert [t for _, t in got_toks[rid]] == [t for _, t in ref_toks[rid]], \
        f"{rid}: fleet tokens diverge from single-engine reference"
fleet = Path(sys.argv[3])
replayed = any(
    json.loads(line).get("name") == "journal_replayed"
    for line in (fleet / "replica_0" / "telemetry.jsonl").read_text()
    .splitlines() if line.strip())
assert replayed, "dead replica's journal never replayed its owed work"
router_end = [json.loads(line)
              for line in (fleet / "telemetry.jsonl").read_text()
              .splitlines()
              if '"router_end"' in line][-1]
assert router_end.get("redispatched", 0) >= 1, router_end
print("[serve_smoke] OK: router round trip — 8 requests exactly once "
      "across a mid-stream replica kill, bit-identical to the "
      "single-engine run; journal replay recovered the owed work "
      f"(redispatched={router_end['redispatched']})")
PY

# 8. live observability probe: a RESIDENT 2-replica fleet behind the
#    router's socket front-end; concurrent traffic warms both replicas'
#    windowed rings, then `obs top --once --json` must render the
#    router row plus both replica rows LIVE — state/occupancy/windowed
#    TTFT p99 sourced from the exposition sockets (obs/export.py), not
#    from post-hoc files — before a SIGTERM drains the fleet.
python -m hyperion_tpu.cli.main route \
    --replicas 2 --min-ready 2 --ckpt "$WORK/llama.npz" --no-tokenizer \
    --base-dir "$WORK/fleet_live" --max-len 64 --slots 2 \
    --warmup-lens 8 --replica-heartbeat-every 1 \
    --socket "$WORK/route_live.sock" --slo-ttft-p99-ms 60000 \
    2> "$WORK/route_live.log" &
ROUTE_PID=$!
# under `set -e`, a failed assertion below would otherwise leak the
# backgrounded fleet (supervisors keep restarting children) — always
# drain it on the way out, however this script exits
trap 'kill -TERM "$ROUTE_PID" 2>/dev/null || true' EXIT

python - "$WORK" <<'PY'
import sys
import threading
import time
from pathlib import Path

from hyperion_tpu.obs.top import sample_all
from hyperion_tpu.serve.client import ServeClient

work = Path(sys.argv[1])
sock = work / "route_live.sock"
t0 = time.monotonic()
while not sock.exists():
    assert time.monotonic() - t0 < 240, "router socket never appeared"
    time.sleep(0.2)

# concurrent requests so least-loaded dispatch spreads over BOTH
# replicas and each engine's windowed TTFT ring has samples; worker
# failures are COLLECTED — an assertion inside a thread would
# otherwise print and vanish while the script sails on to OK
errors = []

def drive(i):
    try:
        with ServeClient(str(sock)) as c:
            res = c.generate(id=f"live{i}", prompt_ids=[3 + i, 4, 5, 6],
                             max_new_tokens=3)
            assert res["final"]["event"] == "done", res
    except Exception as e:  # noqa: BLE001 — surfaced below
        errors.append(f"live{i}: {e!r}")

threads = [threading.Thread(target=drive, args=(i,)) for i in range(4)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=120)
assert not errors, f"warm-up requests failed: {errors}"
assert not any(t.is_alive() for t in threads), "a warm-up request hung"

# settle until both replicas answer their sockets with warm TTFT
# rings — the CLI probe below is the single asserted frame
deadline = time.monotonic() + 60
while True:
    rows = sample_all(work / "fleet_live")
    live = [r for r in rows if r["name"].startswith("replica")
            and r["state"] == "live" and r["ttft_p99_ms"] is not None]
    if len(live) == 2:
        break
    assert time.monotonic() < deadline, f"fleet never fully live: {rows}"
    time.sleep(0.5)
PY

python -m hyperion_tpu.cli.main obs top "$WORK/fleet_live" \
    --once --json > "$WORK/top.json"

python - "$WORK/top.json" <<'PY'
import json
import sys

doc = json.loads(open(sys.argv[1]).read())
rows = {r["name"]: r for r in doc["rows"]}
live = [r for n, r in rows.items()
        if n.startswith("replica") and r["state"] == "live"]
assert len(live) == 2, f"expected both replica rows live: {rows}"
assert rows["router"]["source"] == "socket", rows["router"]
for r in live:
    assert r["source"] == "socket" and r["occupancy"] is not None, r
    assert r["ttft_p99_ms"] is not None, r
# the introspection-plane columns ride the stable row schema: every
# row carries the keys, and a live engine row's dominant segment (when
# present) must use the tickprof vocabulary — drift-guarded against
# the module, not a string copy
from hyperion_tpu.obs.tickprof import SEGMENTS
for r in doc["rows"]:
    assert "dominant_segment" in r and "rss_mb" in r, r
for r in live:
    assert r["dominant_segment"] in (None, "other", *SEGMENTS), r
    assert isinstance(r["rss_mb"], (int, float)), r
print("[serve_smoke] OK: obs top — router + 2 replica rows live off "
      "the exposition sockets (windowed ttft p99s "
      f"{[r['ttft_p99_ms'] for r in live]} ms, dominant segments "
      f"{[r['dominant_segment'] for r in live]})")
PY

kill -TERM "$ROUTE_PID" 2>/dev/null || true
wait "$ROUTE_PID" || true
trap - EXIT

# 9. speculative round trip: the SAME request leg 6 decoded
#    sequentially (ref_responses.jsonl) now runs with the n-gram
#    self-draft verifying 4 tokens per tick — the stream must be
#    bit-identical (the accept rule is exact at temperature 0), and
#    the telemetry stream must show the draft/verify loop actually ran
printf '%s\n' "$KILLREQ" \
  | env HYPERION_TELEMETRY="$WORK/spec_tele.jsonl" \
    python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8,32 \
      --spec-k 4 --draft ngram \
      > "$WORK/spec_responses.jsonl"

python - "$WORK/ref_responses.jsonl" "$WORK/spec_responses.jsonl" \
         "$WORK/spec_tele.jsonl" <<'PY'
import json
import sys


def stream(path):
    return [rec["token"] for rec in map(json.loads, open(path))
            if rec.get("id") == "k1" and rec.get("event") == "token"
            and rec.get("token") is not None]


ref, got = stream(sys.argv[1]), stream(sys.argv[2])
assert len(ref) == 10 and got == ref, (
    f"speculative stream diverges from sequential: {got} != {ref}")
drafted = 0
for line in open(sys.argv[3]):
    rec = json.loads(line)
    if rec.get("kind") == "snapshot":
        c = rec.get("metrics", {}).get("counters", {})
        drafted = max(drafted, c.get("serve_spec_drafted", 0))
assert drafted > 0, "spec run never drafted — did --spec-k reach the engine?"
print(f"[serve_smoke] OK: speculative round trip — {len(got)} tokens "
      f"bit-identical to the sequential run ({drafted} drafted)")
PY

# 10. adversarial tenants + the acting router: a 2-replica fleet with a
#     1ms TTFT objective (guaranteed to burn), a slowloris tenant whose
#     chaos stall ties up engine ticks, and a batch-tenant flood riding
#     along. The interactive stream must stay bit-identical to a quiet
#     single-engine run; the router must ACT (>=1 router_steer and >=1
#     class_brownout on its stream); `obs doctor` must name the
#     adversarial tenants and narrate the router's actions.
printf '%s\n' \
  '{"id":"int0","prompt_ids":[3,4,5,6],"max_new_tokens":4}' \
  '{"id":"int1","prompt_ids":[4,4,5,6],"max_new_tokens":4}' \
  '{"id":"int2","prompt_ids":[5,4,5,6],"max_new_tokens":4}' \
  '{"id":"int3","prompt_ids":[6,4,5,6],"max_new_tokens":4}' \
  '{"id":"int4","prompt_ids":[7,4,5,6],"max_new_tokens":4}' \
  '{"id":"int5","prompt_ids":[8,4,5,6],"max_new_tokens":4}' \
  '{"id":"int6","prompt_ids":[9,4,5,6],"max_new_tokens":4}' \
  '{"id":"int7","prompt_ids":[10,4,5,6],"max_new_tokens":4}' \
  | python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8 \
      > "$WORK/adv_ref.jsonl"

python -m hyperion_tpu.cli.main route \
    --replicas 2 --min-ready 2 --ckpt "$WORK/llama.npz" --no-tokenizer \
    --base-dir "$WORK/fleet_adv" --max-len 64 --slots 2 \
    --warmup-lens 8 --replica-heartbeat-every 1 \
    --socket "$WORK/route_adv.sock" \
    --prefill-chunk 16 --interactive-weight 3 --batch-weight 1 \
    --slo-ttft-p99-ms 1 --slo-fast-s 30 \
    --steer-clear-sweeps 3 \
    --replica-chaos '0:slowloris@tenant=adv_slow:0.05' \
    2> "$WORK/route_adv.log" &
ROUTE_ADV_PID=$!
trap 'kill -TERM "$ROUTE_ADV_PID" 2>/dev/null || true' EXIT

python - "$WORK" <<'PY'
import json
import sys
import time
from pathlib import Path

from hyperion_tpu.serve.client import ServeClient

work = Path(sys.argv[1])
sock = work / "route_adv.sock"
t0 = time.monotonic()
while not sock.exists():
    assert time.monotonic() - t0 < 240, "router socket never appeared"
    time.sleep(0.2)


def ask(doc):
    with ServeClient(str(sock)) as c:
        return c.generate(**doc)


# the hostile co-tenants: a batch flood from one tenant, a slowloris
# tenant whose deliveries stall replica 0's engine ticks (chaos)
for i in range(6):
    res = ask({"id": f"adv{i}", "prompt_ids": [5 + i, 6, 7, 8],
               "max_new_tokens": 6, "class": "batch",
               "tenant": "adv_burst"})
    assert res["final"]["event"] == "done", res
res = ask({"id": "slow0", "prompt_ids": [9, 6, 7, 8],
           "max_new_tokens": 3, "tenant": "adv_slow"})
assert res["final"]["event"] == "done", res

# the interactive tier, same docs as the quiet single-engine reference
got = {}
for i in range(8):
    res = ask({"id": f"int{i}", "prompt_ids": [3 + i, 4, 5, 6],
               "max_new_tokens": 4, "tenant": "alice"})
    assert res["final"]["event"] == "done", res
    got[f"int{i}"] = res["tokens"]

ref = {}
for line in open(work / "adv_ref.jsonl"):
    rec = json.loads(line)
    if rec.get("event") == "token" and rec.get("token") is not None:
        ref.setdefault(rec["id"], []).append(rec["token"])
assert got == ref, (
    f"interactive stream diverged under hostile co-tenancy: "
    f"{got} != {ref}")

# the router must ACT: steer + class-brownout events on its stream
tele = work / "fleet_adv" / "telemetry.jsonl"
deadline = time.monotonic() + 120
while True:
    names = []
    if tele.exists():
        for line in tele.read_text().splitlines():
            try:
                names.append(json.loads(line).get("name"))
            except json.JSONDecodeError:
                pass
    if "router_steer" in names and "class_brownout" in names:
        break
    assert time.monotonic() < deadline, (
        f"router never acted on the TTFT burn: events={set(names)}")
    time.sleep(0.5)
print("[serve_smoke] adversarial drive done: interactive bit-identical, "
      "router_steer + class_brownout observed")
PY

kill -TERM "$ROUTE_ADV_PID" 2>/dev/null || true
wait "$ROUTE_ADV_PID" || true
trap - EXIT

python -m hyperion_tpu.cli.main obs doctor "$WORK/fleet_adv" --json \
  > "$WORK/adv_router_doctor.json"
python -m hyperion_tpu.cli.main obs doctor "$WORK/fleet_adv/replica_0" \
  --json > "$WORK/adv_rep0_doctor.json"
python -m hyperion_tpu.cli.main obs doctor "$WORK/fleet_adv/replica_1" \
  --json > "$WORK/adv_rep1_doctor.json"

python - "$WORK" <<'PY'
import json
import sys
from pathlib import Path

work = Path(sys.argv[1])
router = json.loads((work / "adv_router_doctor.json").read_text())
acts = router.get("router_actions") or []
assert any("steered" in a for a in acts), (
    f"doctor narrated no steering: {acts} / {router['reason']}")
assert any("brownout" in a for a in acts), (
    f"doctor narrated no brownout order: {acts}")
tenants = set()
for name in ("adv_rep0_doctor.json", "adv_rep1_doctor.json"):
    d = json.loads((work / name).read_text())
    tenants |= {t["tenant"] for t in d.get("tenants") or []}
assert "adv_burst" in tenants and "adv_slow" in tenants, (
    f"doctor never named the adversarial tenants: {tenants}")
print(f"[serve_smoke] OK: acting router — doctor narrates "
      f"{len(acts)} action line(s) and names tenants "
      f"{sorted(tenants)}")
PY

# 11. the router itself is no longer the SPOF. Phase A: an
#     UNSUPERVISED router over 2 replicas with router-scoped chaos
#     (`--chaos crash@dispatch=2`) hard-exits after journaling its 2nd
#     placement — the client holding that stream gets StreamInterrupted
#     (never a silent half stream) and `obs doctor` must name the
#     router crash citing the dispatch WAL's owed stream. Phase B: the
#     SAME base dir relaunches under `route --supervise` with the same
#     chaos; the new life re-adopts the surviving (orphaned) replicas
#     without respawning them, recovers the WAL, and answers a bare
#     resume verb for phase A's cut stream FROM THE WAL ALONE; then the
#     chaos fires again mid-leg and an auto-resuming client rides the
#     supervised restart — every stream bit-identical to the lone-
#     engine reference, gapless and duplicate-free across three router
#     lives.
printf '%s\n' \
  '{"id":"pm0","prompt_ids":[11,4,5,6],"max_new_tokens":8}' \
  '{"id":"pm1","prompt_ids":[12,4,5,6],"max_new_tokens":8}' \
  '{"id":"pm2","prompt_ids":[13,4,5,6],"max_new_tokens":8}' \
  '{"id":"pm3","prompt_ids":[14,4,5,6],"max_new_tokens":8}' \
  | python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8 \
      > "$WORK/pm_ref.jsonl"

# failure backstop: TERM whatever the drill left alive (supervisor,
# router child, adopted replicas) via their heartbeat pids — a failed
# assertion must not leak a self-restarting fleet
cleanup_pm() {
  [ -n "${SUP_PID:-}" ] && kill -TERM "$SUP_PID" 2>/dev/null || true
  for hb in "$WORK"/fleet_pm/heartbeat.json \
            "$WORK"/fleet_pm/replica_*/heartbeat.json; do
    [ -f "$hb" ] || continue
    pid=$(python -c \
      "import json,sys; print(json.load(open(sys.argv[1])).get('pid', 0))" \
      "$hb" 2>/dev/null || echo 0)
    [ "${pid:-0}" -gt 0 ] 2>/dev/null && kill -TERM "$pid" 2>/dev/null \
      || true
  done
}
trap cleanup_pm EXIT

# phase A: unsupervised, chaos armed — dispatch 2 kills the router
python -m hyperion_tpu.cli.main route \
    --replicas 2 --min-ready 2 --ckpt "$WORK/llama.npz" --no-tokenizer \
    --base-dir "$WORK/fleet_pm" --max-len 64 --slots 2 \
    --warmup-lens 8 --replica-heartbeat-every 1 \
    --socket "$WORK/route_pm.sock" --chaos crash@dispatch=2 \
    > "$WORK/route_pm.out" 2> "$WORK/route_pm.log" &
PM_PID=$!

python - "$WORK" <<'PY'
import json
import sys
import time
from pathlib import Path

from hyperion_tpu.serve.client import ServeClient, StreamInterrupted

work = Path(sys.argv[1])
sock = work / "route_pm.sock"
t0 = time.monotonic()
while not sock.exists():
    assert time.monotonic() - t0 < 240, "router socket never appeared"
    time.sleep(0.2)

with ServeClient(str(sock)) as c:
    res = c.generate(id="pm0", prompt_ids=[11, 4, 5, 6],
                     max_new_tokens=8)
    assert res["final"]["event"] == "done", res
    pm0 = res["tokens"]

# pm1 is the router's 2nd dispatch: the chaos clause journals the
# placement, then os._exit()s the router before a single token flows
cut = None
try:
    with ServeClient(str(sock)) as c:
        c.generate(id="pm1", prompt_ids=[12, 4, 5, 6],
                   max_new_tokens=8)
except StreamInterrupted as e:
    cut = e
assert cut is not None and cut.request_id == "pm1", (
    f"expected StreamInterrupted for pm1, got {cut!r}")
(work / "pm_state.json").write_text(json.dumps(
    {"pm0": pm0, "next_index": cut.next_index}))
print(f"[serve_smoke] router died owing pm1 "
      f"(StreamInterrupted at next_index={cut.next_index})")
PY
wait "$PM_PID" || true

# the post-mortem: doctor must cite the WAL's owed stream by name
python -m hyperion_tpu.cli.main obs doctor "$WORK/fleet_pm" --json \
  > "$WORK/pm_doctor.json"
python - "$WORK/pm_doctor.json" <<'PY'
import json
import sys

doc = json.loads(open(sys.argv[1]).read())
wal = doc.get("router_wal")
assert wal and wal.get("pending", 0) >= 1, (
    f"doctor read no pending dispatch from the router WAL: {wal}")
inc = wal.get("incident") or ""
assert "router_journal.jsonl" in inc and "in-flight" in inc, (
    f"doctor incident does not cite the WAL: {inc!r}")
assert "pm1" in json.dumps(wal.get("tail", [])), (
    f"WAL tail does not name the owed request: {wal.get('tail')}")
print(f"[serve_smoke] OK: doctor post-mortem — {inc}")
PY

# phase B: same base dir, now SUPERVISED; attempt 0 re-arms the chaos
# clause, so this lineage crashes once more mid-leg and the supervisor
# restarts it immediately
python -m hyperion_tpu.cli.main route --supervise \
    --replicas 2 --min-ready 2 --ckpt "$WORK/llama.npz" --no-tokenizer \
    --base-dir "$WORK/fleet_pm" --max-len 64 --slots 2 \
    --warmup-lens 8 --replica-heartbeat-every 1 \
    --socket "$WORK/route_pm.sock" --chaos crash@dispatch=2 \
    > "$WORK/route_pm2.out" 2> "$WORK/route_pm2.log" &
SUP_PID=$!

python - "$WORK" <<'PY'
import json
import socket
import sys
import time
from pathlib import Path

from hyperion_tpu.serve.client import ServeClient

work = Path(sys.argv[1])
sock_path = str(work / "route_pm.sock")

# the stale socket FILE survived the phase A crash — wait until a
# router life actually answers it (the bind path's flock probe is what
# reclaims the stale file)
t0 = time.monotonic()
while True:
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.settimeout(1.0)
    try:
        s.connect(sock_path)
        s.close()
        break
    except OSError:
        s.close()
        assert time.monotonic() - t0 < 300, "supervised router never bound"
        time.sleep(0.2)

ref = {}
for line in open(work / "pm_ref.jsonl"):
    rec = json.loads(line)
    if rec.get("event") == "token" and rec.get("token") is not None:
        ref.setdefault(rec["id"], []).append(rec["token"])
state = json.loads((work / "pm_state.json").read_text())
assert state["pm0"] == ref["pm0"], "phase A pm0 diverged from reference"

# 1) a BARE resume verb — no request body attached: the new router
#    life must answer it from the recovered WAL alone
s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
s.settimeout(120.0)
s.connect(sock_path)
s.sendall((json.dumps({"kind": "resume", "request_id": "pm1",
                       "next_index": state["next_index"]}) + "\n")
          .encode())
toks, final = [], None
for raw in s.makefile("rb"):
    rec = json.loads(raw)
    if rec.get("event") == "token" and rec.get("token") is not None:
        toks.append((rec.get("i"), rec["token"]))
    if rec.get("event") in ("done", "rejected", "timed_out", "error"):
        final = rec
        break
s.close()
assert final and final["event"] == "done", (
    f"WAL resume of pm1 did not complete: {final}")
idx = [i for i, _ in toks]
assert idx == list(range(state["next_index"], len(ref["pm1"]))), (
    f"pm1 resume indices gapped/duplicated: {idx}")
assert [t for _, t in toks] == ref["pm1"][state["next_index"]:], (
    "pm1 resumed stream diverges from reference")

# 2) pm2 is this life's 2nd dispatch — the chaos kills the router
#    mid-request; the resuming client must ride the supervised restart
#    and still produce the reference stream exactly once
with ServeClient(sock_path, resume=True) as c:
    res = c.generate(id="pm2", prompt_ids=[13, 4, 5, 6],
                     max_new_tokens=8)
assert res["final"]["event"] == "done", res
assert res["tokens"] == ref["pm2"], (
    f"pm2 diverged across router lives: {res['tokens']} != {ref['pm2']}")

# 3) a fresh request on the restarted life — recovery left a working
#    router behind, not just a drained WAL
with ServeClient(sock_path, resume=True) as c:
    res = c.generate(id="pm3", prompt_ids=[14, 4, 5, 6],
                     max_new_tokens=8)
assert res["final"]["event"] == "done", res
assert res["tokens"] == ref["pm3"], "pm3 diverged after recovery"

# the control-plane record must show the whole story: replicas ADOPTED
# (not respawned) by the new lives, WAL orphans recovered, resumes
# answered
names = []
for line in (work / "fleet_pm" / "telemetry.jsonl").read_text() \
        .splitlines():
    try:
        names.append(json.loads(line).get("name"))
    except json.JSONDecodeError:
        pass
assert names.count("replica_adopted") >= 2, (
    f"expected both replicas adopted: {names.count('replica_adopted')}")
assert names.count("route_orphan_recovered") >= 2, (
    f"expected pm1+pm2 recovered from the WAL: "
    f"{names.count('route_orphan_recovered')}")
assert names.count("route_resume") >= 2, (
    f"expected >=2 answered resumes: {names.count('route_resume')}")
print("[serve_smoke] supervised drill done: pm0-pm3 bit-identical "
      "across three router lives")
PY

# the chaos clause and the supervised restart must both have left
# their fingerprints
grep -q "crash@dispatch" "$WORK/route_pm2.out" || {
  echo "[serve_smoke] FAIL: chaos clause never fired in phase B" >&2
  exit 1
}
grep -q "route-supervisor] router exit" "$WORK/route_pm2.log" || {
  echo "[serve_smoke] FAIL: no supervised restart in phase B" >&2
  exit 1
}

# graceful teardown: TERM the router CHILD (its drain writes router_end
# and close_clean()s the WAL); the supervisor reads exit 0 and stops
RPID=$(python -c \
  "import json,sys; print(json.load(open(sys.argv[1]))['pid'])" \
  "$WORK/fleet_pm/heartbeat.json")
kill -TERM "$RPID" 2>/dev/null || true
wait "$SUP_PID" || true
trap - EXIT

echo "[serve_smoke] OK: router SPOF drill — WAL post-mortem, replica "
echo "  re-adoption, and client resumes across supervised router lives"

# the crash story leg 11 just produced is exactly what the fleet join
# exists for: one router stream (two lives), two replica dirs, a
# mid-request router death, WAL recovery, and answered client resumes.
# `obs trace --fleet` must render ONE Chrome trace spanning all three
# processes, with dispatch→admit flow arrows surviving the chaos.
python -m hyperion_tpu.cli.main obs trace "$WORK/fleet_pm" \
    --fleet --export "$WORK/fleet_trace.json" \
    > "$WORK/fleet_trace.out"
python - "$WORK/fleet_trace.json" <<'PY'
import json
import sys

doc = json.load(open(sys.argv[1]))
evs = doc["traceEvents"]
assert evs, "obs trace --fleet exported an empty Chrome trace"
pids = {e["pid"] for e in evs if e.get("ph") == "X"}
assert len(pids) >= 3, (
    f"fleet trace spans {len(pids)} process track(s), want >=3 "
    "(router + both replicas)")
starts = {e["id"] for e in evs if e.get("ph") == "s"}
ends = {e["id"] for e in evs if e.get("ph") == "f"}
assert starts & ends, (
    "fleet trace has no paired dispatch/failover flow arrows")
print(f"[serve_smoke] OK: fleet trace — {len(evs)} events across "
      f"{len(pids)} process tracks, {len(starts & ends)} flow arrow(s)")
PY

# 12. paged-attention kernel round trip: leg 6's request decoded again
#     with --paged-attn pallas (the in-kernel block-table walk; the
#     kernel interprets on this host backend) — the client stream must
#     be bit-identical to the sequential gather reference
#     (ref_responses.jsonl), and the serve_end flight record's memory
#     ledger must show the per-tick gather copy GONE
#     (kv_gather_bytes_per_tick == 0)
printf '%s\n' "$KILLREQ" \
  | env HYPERION_TELEMETRY="$WORK/pa_tele.jsonl" \
    python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 64 --slots 2 --warmup-lens 8,32 \
      --paged-attn pallas \
      > "$WORK/pa_responses.jsonl"

python - "$WORK/ref_responses.jsonl" "$WORK/pa_responses.jsonl" \
         "$WORK/flight.json" <<'PY'
import json
import sys


def stream(path):
    return [rec["token"] for rec in map(json.loads, open(path))
            if rec.get("id") == "k1" and rec.get("event") == "token"
            and rec.get("token") is not None]


ref, got = stream(sys.argv[1]), stream(sys.argv[2])
assert len(ref) == 10 and got == ref, (
    f"pallas paged-attn stream diverges from gather: {got} != {ref}")
flight = json.load(open(sys.argv[3]))
gather_bytes = flight["memory"]["kv_gather_bytes_per_tick"]
assert gather_bytes == 0, (
    f"kernel run still reports a gather copy: {gather_bytes} B/tick")
print(f"[serve_smoke] OK: paged-attn kernel round trip — {len(got)} "
      "tokens bit-identical to the gather run, "
      "kv_gather_bytes_per_tick=0 on the flight record")
PY

# 13. tiered KV round trip: run A serves a shared-prefix request, then
#     three churn requests overflow the 10-block pool so the radix cache
#     EVICTS the shared chain — with --host-cache-mb on, eviction
#     demotes it to host RAM and the drain saves the store next to the
#     telemetry stream. Run B is a FRESH process: it loads the store,
#     and a same-prefix rehit restores the chain from host RAM (tier
#     hit on the serve_end terminal record). Run C decodes the same
#     rehit with the tier off — B's stream must be bit-identical.
printf '%s\n' \
  '{"id":"s1","prompt_ids":[3,4,5,6,7,8,9,10,11,12,13,14,20,21],"max_new_tokens":4}' \
  '{"id":"c1","prompt_ids":[40,41,42,43,44,45,46,47,48,49],"max_new_tokens":8}' \
  '{"id":"c2","prompt_ids":[50,51,52,53,54,55,56,57,58,59],"max_new_tokens":8}' \
  '{"id":"c3","prompt_ids":[60,61,62,63,64,65,66,67,68,69],"max_new_tokens":8}' \
  | env HYPERION_TELEMETRY="$WORK/tier_a.jsonl" \
    python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 32 --slots 2 --warmup-lens 8 --block-size 4 \
      --num-blocks 10 --host-cache-mb 16 \
      > "$WORK/tier_a_responses.jsonl"

REHIT='{"id":"r1","prompt_ids":[3,4,5,6,7,8,9,10,11,12,13,14,30,31],"max_new_tokens":4}'
printf '%s\n' "$REHIT" \
  | env HYPERION_TELEMETRY="$WORK/tier_b.jsonl" \
    python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 32 --slots 2 --warmup-lens 8 --block-size 4 \
      --num-blocks 10 --host-cache-mb 16 \
      > "$WORK/tier_b_responses.jsonl"

printf '%s\n' "$REHIT" \
  | python -m hyperion_tpu.cli.main serve \
      --ckpt "$WORK/llama.npz" --no-tokenizer \
      --max-len 32 --slots 2 --warmup-lens 8 --block-size 4 \
      --num-blocks 10 \
      > "$WORK/tier_ref_responses.jsonl"

python - "$WORK" <<'PY'
import json
import sys
from pathlib import Path

work = Path(sys.argv[1])


def records(name):
    out = []
    for line in (work / name).read_text().splitlines():
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            pass
    return out


def ev(recs, name):
    return [r for r in recs if r.get("name") == name]


# run A: the churn evicted the shared chain INTO the tier, and the
# drain serialized the store
a = records("tier_a.jsonl")
(end_a,) = ev(a, "serve_end")
assert end_a["host_spilled_blocks"] >= 3, (
    f"run A did not spill s1's whole chain to the host tier: {end_a}")
(saved,) = ev(a, "hostcache_saved")
assert saved["chains"] >= 1
assert (work / "hostcache" / "index.json").exists(), (
    "drain did not persist the host store next to the telemetry stream")

# run B: a fresh process loaded the store and fed the rehit from it
b = records("tier_b.jsonl")
assert ev(b, "hostcache_loaded"), "run B never loaded the saved store"
assert ev(b, "host_restore"), "run B never restored from the host tier"
(end_b,) = ev(b, "serve_end")
assert end_b["tier_hits_host"] >= 1, f"no host-tier hit on rehit: {end_b}"
assert end_b["host_restored_blocks"] >= 1


def stream(name):
    return [r["token"] for r in records(name)
            if r.get("id") == "r1" and r.get("event") == "token"
            and r.get("token") is not None]


got, ref = stream("tier_b_responses.jsonl"), stream("tier_ref_responses.jsonl")
assert len(ref) == 4 and got == ref, (
    f"host-tier restore diverged from the tier-off run: {got} != {ref}")
print(f"[serve_smoke] OK: tiered KV round trip — "
      f"{end_a['host_spilled_blocks']} block(s) spilled, store survived "
      f"the restart, rehit restored {end_b['host_restored_blocks']} "
      "block(s) bit-identically")
PY

echo "[serve_smoke] all legs passed"
