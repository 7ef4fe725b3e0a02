#!/usr/bin/env python
"""Telemetry event-vocabulary drift guard.

The serving tier's producers (`hyperion_tpu/serve/*.py`) emit events by
string literal at each `tracer.event("...")` call site; the offline
consumers — `obs trace` (timeline + fleet_trace) and `obs doctor` —
match those names by string literal too. There is no shared enum on
purpose (the stream format is the contract), which means a producer can
rename or add an event and every waterfall, fleet join, and incident
rule silently stops seeing it. The gap only surfaces when someone reads
a suspiciously empty trace months later.

This guard closes the loop: every event name emitted under serve/ must
appear somewhere in the consumer sources (obs/timeline.py,
obs/fleet_trace.py, obs/doctor.py — fleet_trace declares the full
consumed vocabulary explicitly). An orphaned producer name fails the
build with the file:line of the call site.

    python scripts/check_event_vocab.py

Exit 0: every emitted event is consumed. Exit 1: orphans named on
stderr. Pure source scan — no imports of jax, no devices; tier-1 runs
this via tests/test_obs_live.py.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRODUCER_DIR = os.path.join(REPO, "hyperion_tpu", "serve")
CONSUMERS = (
    os.path.join(REPO, "hyperion_tpu", "obs", "timeline.py"),
    os.path.join(REPO, "hyperion_tpu", "obs", "fleet_trace.py"),
    os.path.join(REPO, "hyperion_tpu", "obs", "doctor.py"),
)

# `.event("name"` — possibly with the name literal wrapped onto the
# next line, hence \s* spanning newlines on the whole-file text
_CALL = re.compile(r"\.event\(\s*\"([a-z0-9_]+)\"")


def emitted_events() -> dict[str, list[str]]:
    """Event name -> list of `file:line` call sites under serve/."""
    out: dict[str, list[str]] = {}
    for fname in sorted(os.listdir(PRODUCER_DIR)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(PRODUCER_DIR, fname)
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for m in _CALL.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            out.setdefault(m.group(1), []).append(
                f"hyperion_tpu/serve/{fname}:{line}")
    return out


def consumer_vocabulary() -> str:
    """The concatenated consumer sources; a name is "consumed" when it
    appears as a string anywhere in them (match rules, vocab tuples,
    incident messages all count — the point is a human landed it)."""
    chunks = []
    for path in CONSUMERS:
        with open(path, encoding="utf-8") as f:
            chunks.append(f.read())
    return "\n".join(chunks)


def orphans() -> dict[str, list[str]]:
    vocab = consumer_vocabulary()
    return {name: sites for name, sites in sorted(emitted_events().items())
            if f'"{name}"' not in vocab and f"'{name}'" not in vocab
            and name not in vocab}


def main(argv: list[str] | None = None) -> int:
    bad = orphans()
    n = len(emitted_events())
    if bad:
        for name, sites in bad.items():
            print(f"check_event_vocab: FAIL — event {name!r} emitted at "
                  f"{', '.join(sites)} but no consumer "
                  "(obs/timeline.py, obs/fleet_trace.py, obs/doctor.py) "
                  "knows the name — add it to the consumer vocabulary "
                  "or it vanishes from every trace and diagnosis",
                  file=sys.stderr)
        return 1
    print(f"check_event_vocab: OK — {n} event name(s) emitted under "
          "serve/, all present in the consumer vocabulary")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
