"""`obs diff <a> <b>` — did the second run regress against the first?

Two inputs, each a telemetry stream (summarized on the fly via
`obs/report.py`), an `obs summarize --json` document or a trainer
`*_summary.json`, are normalized onto one metric vocabulary and
compared with percent deltas. A metric that moved in its BAD direction
by more than the threshold (default 10%) is flagged as a regression and
the exit code says so — `obs diff a b || echo regressed` is the whole
CI hook. How fast the system is on the chip is `BENCHMARK.json`'s and
`benchmarks/run.py`'s to say, not this tool's.

Direction conventions: times and memory regress UP; throughput and MFU
regress DOWN.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

# Canonical metric vocabulary: name -> direction of GOODNESS ("higher" =
# bigger is better; regression is the other way). The rule: a gate
# exists only if a command in this repo emits it — a telemetry stream or
# `obs summarize --json` (every name below) or a trainer
# `*_summary.json` (step_time_mean_ms, tokens_per_s, hbm_peak_mb).
# `tests/test_obs_doctor.py::TestDiff` holds one producing shape per
# name and fails on a gate without one.
METRICS: dict[str, str] = {
    "step_time_p50_ms": "lower",
    "step_time_p99_ms": "lower",
    "step_time_mean_ms": "lower",
    "tokens_per_s": "higher",
    "samples_per_s": "higher",
    "mfu": "higher",
    "hbm_peak_mb": "lower",
}


def _num(v) -> float | None:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    v = float(v)
    return v if math.isfinite(v) else None


def normalize(doc: dict) -> dict[str, float]:
    """Map any known summary shape onto the canonical metric names,
    keeping only finite numbers. Unknown shapes yield {} rather than
    guessing."""
    out: dict[str, float] = {}
    st = doc.get("step_time_ms")
    if isinstance(st, dict):  # obs summarize --json
        for k, name in (("p50", "step_time_p50_ms"),
                        ("p99", "step_time_p99_ms"),
                        ("mean", "step_time_mean_ms")):
            v = _num(st.get(k))
            if v is not None:
                out[name] = v
    for k in ("tokens_per_s", "samples_per_s", "mfu", "hbm_peak_mb"):
        v = _num(doc.get(k))
        if v is not None:
            out[k] = v
    # trainer *_summary.json {"step_ms": ..., "peak_hbm_mb": ...}
    if "step_ms" in doc:
        v = _num(doc.get("step_ms"))
        if v is not None:
            out["step_time_mean_ms"] = v
    if "peak_hbm_mb" in doc and "hbm_peak_mb" not in out:
        v = _num(doc.get("peak_hbm_mb"))
        if v is not None:
            out["hbm_peak_mb"] = v
    return out


def load_summary(path: str | Path, run: str | None = None) -> dict:
    """{"label", "metrics", "error"?} for one input — a run dir, a
    telemetry JSONL, or a summary JSON file."""
    from hyperion_tpu.obs import report

    path = Path(path)
    label = path.name if path.name != "telemetry.jsonl" else path.parent.name
    if path.is_dir():
        path = path / "telemetry.jsonl"
        label = Path(label).name
    if not path.exists():
        return {"label": label, "metrics": {}, "error": f"no such file: {path}"}
    if path.suffix == ".jsonl":
        s = report.summarize(path, run=run)
        if s.get("error"):
            return {"label": label, "metrics": {}, "error": s["error"]}
        return {"label": s.get("run") or label, "metrics": normalize(s)}
    try:
        doc = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return {"label": label, "metrics": {},
                "error": f"unreadable summary: {e}"}
    if not isinstance(doc, dict):
        return {"label": label, "metrics": {},
                "error": "summary is not a JSON object"}
    return {"label": label, "metrics": normalize(doc)}


def diff(a: dict, b: dict, threshold: float = 0.10) -> dict:
    """Compare two normalized summaries; delta_pct is b vs a (positive =
    b larger). A regression is a move in the metric's bad direction
    strictly beyond `threshold`."""
    rows = []
    for name, direction in METRICS.items():
        va, vb = a["metrics"].get(name), b["metrics"].get(name)
        if va is None or vb is None or va == 0:
            continue  # a zero base has no percent delta
        delta = (vb - va) / abs(va)
        worse = delta > 0 if direction == "lower" else delta < 0
        rows.append({
            "metric": name, "a": va, "b": vb,
            "delta_pct": round(100 * delta, 2),
            "better": direction,
            "regression": bool(worse and abs(delta) > threshold),
        })
    return {
        "a": a["label"], "b": b["label"],
        "threshold_pct": round(100 * threshold, 1),
        "rows": rows,
        "regressions": [r["metric"] for r in rows if r["regression"]],
        "comparable_metrics": len(rows),
    }


def render_markdown(d: dict) -> str:
    lines = [
        f"## Run diff — `{d['a']}` → `{d['b']}`",
        "",
        f"regression threshold: {d['threshold_pct']}% "
        "(in each metric's bad direction)",
        "",
    ]
    if not d["rows"]:
        lines.append("no comparable metrics between the two summaries")
        return "\n".join(lines) + "\n"
    lines += ["| metric | a | b | Δ% | verdict |", "|---|---|---|---|---|"]
    for r in d["rows"]:
        verdict = "**REGRESSED**" if r["regression"] else "ok"
        lines.append(f"| {r['metric']} ({r['better']}=better) | "
                     f"{r['a']:.4g} | {r['b']:.4g} | "
                     f"{r['delta_pct']:+.1f}% | {verdict} |")
    if d["regressions"]:
        lines += ["", f"**{len(d['regressions'])} regression(s):** "
                  + ", ".join(d["regressions"])]
    else:
        lines += ["", "no regressions beyond threshold"]
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="hyperion obs diff",
        description="compare two run summaries (telemetry JSONL or "
                    "summary JSON) with a regression threshold",
    )
    p.add_argument("inputs", nargs=2, metavar="input",
                   help="the two inputs to diff (run dir, "
                        "telemetry.jsonl, or summary .json)")
    p.add_argument("--threshold", type=float, default=0.10,
                   help="regression threshold as a fraction (0.10 = 10%%)")
    p.add_argument("--run-a", default=None,
                   help="run id inside input A when it is a stream")
    p.add_argument("--run-b", default=None,
                   help="run id inside input B when it is a stream")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    a = load_summary(args.inputs[0], run=args.run_a)
    b = load_summary(args.inputs[1], run=args.run_b)
    for s in (a, b):
        if s.get("error"):
            print(f"{s['label']}: {s['error']}", file=sys.stderr)
            return 2
    d = diff(a, b, threshold=args.threshold)
    print(json.dumps(d, indent=2) if args.json else render_markdown(d),
          end="" if not args.json else "\n")
    if not d["rows"]:
        print("nothing comparable between the two inputs", file=sys.stderr)
        return 2
    return 1 if d["regressions"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
