#!/usr/bin/env python3
"""Consolidate the BENCH_*.json artifacts into one perf-trajectory table.

Seven PRs of benchmarks left ~30 ``BENCH_*.json`` files whose history is
only legible by diffing git. This script makes the trajectory a first-class
artifact:

- ``BENCH_TRAJECTORY.md`` — one markdown table per benchmark *series*
  (``BENCH_DIST_r03/r04/r05`` is the series ``DIST`` at rungs 3..5; files
  without a ``_rNN`` suffix are single-point series), newest rung last,
  with the delta vs the prior rung.
- ``BENCH_TRAJECTORY.json`` — the same, machine-readable (the next PR's
  rung appends instead of re-deriving).
- ``--check`` — exit non-zero when any series' newest rung regressed
  >``--threshold`` percent (default 20) against the prior rung. Direction
  comes from the unit: latency-like units (us/ms/ns) regress upward,
  rate-like units (q/s, rows/s) regress downward; unit-less series are
  reported but never fail the check.

Artifact shapes handled: headline files ({metric, value, unit, ...}),
wrapper files ({parsed: {…headline…}, tail, rc}), and composite
files without a scalar headline (listed, excluded from the check).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

RUNG_RE = re.compile(r"^(BENCH(?:_[A-Za-z0-9]+)*?)_r(\d+)$")

#: secondary headlines: (field, unit) pairs an artifact may carry IN
#: ADDITION to its primary headline; each present field becomes its own
#: `<series>.<field>` trend series (e.g. BENCH_CYCLIC's
#: pentagon_device_speedup — the device-vs-host WCOJ win on the shape
#: whose loss was closing-level intersection cost — trends next to the
#: triangle walk-vs-wcoj primary instead of displacing it)
SECONDARY_HEADLINES = (
    ("pentagon_device_speedup", "speedup"),
    # BENCH_TENANT's protected-tenant q/s under the 2x-capacity
    # admission overload drill — the throughput the plane preserves for
    # the top weight class while bulk is shed
    ("protected_qps", "q/s"),
    # BENCH_GRAPHRAG's pure-scan device-vs-host ratio on the >=100k x
    # 128d brute-force k-NN block (unit "x" is direction-less here: on a
    # CPU-emulated backend the drill self-gates on the measured-demotion
    # path instead, so the ratio is trended but never threshold-checked)
    ("scan_device_vs_host", "x"),
    # ...and the pure-graph q/s share of the same mixed GraphRAG loop,
    # trended beside the hybrid headline so a vector-plane tax on graph
    # traffic shows up as a divergence between the two series
    ("graph_qps", "q/s"),
    # BENCH_CYCLIC's compiled-template rung: device<->host round trips
    # per query, per-step device route over the whole-plan fused program
    # (min across the large cyclic shapes; deterministic — cyclic_main
    # self-gates it >= 5x, so unit "x" trends it without a second check)
    ("compiled_device_vs_host", "x"),
    # BENCH_SERVE's whole-plan-compiled vs host-walk wall ratio on the
    # live serving path (unit "x" is direction-less: on the CPU backend
    # the sync chain the program deletes is nearly free, so the ratio is
    # trended, while serve_main gates the structural facts — programs
    # staged, rows identical, route chooser zero-touch)
    ("device_compiled_template", "x"),
)

LOWER_BETTER = ("us", "ms", "ns", "sec")
HIGHER_BETTER = ("q/s", "qps", "/s", "speedup")


def _direction(unit: str) -> int:
    """-1 lower-better, +1 higher-better, 0 unknown (never checked)."""
    u = (unit or "").lower()
    if any(tok in u for tok in HIGHER_BETTER):
        return 1
    if any(u.startswith(tok) or f"{tok}/" in u or u == tok
           for tok in LOWER_BETTER):
        return -1
    return 0


def _headline(d: dict) -> dict | None:
    """{value, unit, metric} from one artifact, unwrapping
    {parsed: ...} wrappers; None when the file has no scalar headline."""
    if isinstance(d.get("parsed"), dict):
        d = d["parsed"]
    # hot-spot observatory drill: the heat plane's load-rate separation
    # (BENCH_HOTSPOT.json; unit "x" is direction-less — the scenario's
    # Zipf skew sets the number, so it is trended but never gated).
    # Checked BEFORE the generic value branch: the artifact also carries
    # a top-level "value", which would bury the short series name under
    # the long metric sentence
    if isinstance(d.get("hotspot_separation"), (int, float)):
        return {"value": float(d["hotspot_separation"]), "unit": "x",
                "metric": "hotspot_separation"}
    # rebalance drill: pre/post host load-rate imbalance across one
    # EXECUTED shard migration (BENCH_REBALANCE.json; unit "x" is
    # direction-less and the drill self-gates — bench.py --rebalance
    # exits non-zero unless post-move imbalance clears the threshold and
    # every mid-migration probe was byte-identical)
    if isinstance(d.get("rebalance_gain"), (int, float)):
        return {"value": float(d["rebalance_gain"]), "unit": "x",
                "metric": "rebalance_gain"}
    # read-mostly CACHED serving drill: real result-cache q/s with the
    # materialized-view plane armed (BENCH_READMOSTLY.json since PR 14;
    # the drill self-gates on byte-identity, real >= shadow hit rate,
    # >= 3x the PR 8 light-only baseline, and the flat write-rate
    # curve). Checked before predicted_hit_rate: the artifact still
    # carries the shadow ratio for the observe-only trend
    if isinstance(d.get("readmostly_qps"), (int, float)):
        return {"value": float(d["readmostly_qps"]), "unit": "q/s",
                "metric": "readmostly_qps"}
    # read-mostly serving-cache drill: the achievable version-keyed
    # result-cache hit rate on the Zipfian mix (BENCH_READMOSTLY.json;
    # unit "ratio" is direction-less — the drill self-gates at >= 0.5
    # with monotone write-rate degradation, so it is trended but never
    # threshold-checked here). Before the generic value branch for the
    # same reason as hotspot_separation
    if isinstance(d.get("predicted_hit_rate"), (int, float)):
        return {"value": float(d["predicted_hit_rate"]), "unit": "ratio",
                "metric": "predicted_hit_rate"}
    # device-observatory drill: whole-suite live/padded ratio over the
    # cyclic device route run twice (BENCH_DEVICE.json; unit "ratio" is
    # direction-less — the drill self-gates on cold amortization and
    # the residency budget, so it is trended but never threshold-checked
    # here). Before the generic value branch so the series keeps the
    # short name instead of the long metric sentence
    if isinstance(d.get("padding_efficiency"), (int, float)):
        return {"value": float(d["padding_efficiency"]), "unit": "ratio",
                "metric": "padding_efficiency"}
    # multi-process rung: serving qps over the worker pool's framed
    # socket transport (BENCH_PROC.json; the drill self-gates on
    # byte-identity with loopback and on landing within 2x of the
    # same-run in-proc number, so it is trended but never
    # threshold-checked here). Before the generic value branch so the
    # series keeps the short name instead of the long metric sentence
    if isinstance(d.get("proc_qps"), (int, float)):
        return {"value": float(d["proc_qps"]), "unit": "q/s",
                "metric": "proc_qps"}
    if isinstance(d.get("value"), (int, float)):
        return {"value": float(d["value"]), "unit": d.get("unit", ""),
                "metric": str(d.get("metric", ""))[:160]}
    # serving artifact: qps headline without a value field (mixed_qps:
    # the --serve-mixed light+heavy closed loop, BENCH_SERVE_MIXED.json;
    # tenant_qps: the --tenants multi-tenant SLO scenario, BENCH_TENANT.json)
    for key in ("batched_qps", "mixed_qps", "tenant_qps", "qps", "thpt_qps"):
        if isinstance(d.get(key), (int, float)):
            return {"value": float(d[key]), "unit": "q/s", "metric": key}
    # cyclic suite: the triangle walk-vs-wcoj ratio (BENCH_CYCLIC.json;
    # higher is better via the "speedup" unit)
    if isinstance(d.get("triangle_speedup"), (int, float)):
        return {"value": float(d["triangle_speedup"]), "unit": "speedup",
                "metric": "triangle_speedup"}
    return None


def collect(bench_dir: str) -> dict:
    """series -> {unit, metric, points: [{rung, file, value}] newest last,
    plus a list of headline-less composite files}."""
    series: dict[str, dict] = {}
    composites = []
    for path in sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json"))):
        base = os.path.splitext(os.path.basename(path))[0]
        if base == "BENCH_TRAJECTORY":
            continue  # this script's own output is not an input

        try:
            d = json.load(open(path))
        except (OSError, json.JSONDecodeError) as e:
            composites.append({"file": base, "note": f"unreadable: {e}"})
            continue
        m = RUNG_RE.match(base)
        name, rung = (m.group(1), int(m.group(2))) if m else (base, None)
        head = _headline(d)
        if head is None:
            composites.append({"file": base,
                               "note": "no scalar headline (composite)"})
            continue
        s = series.setdefault(name, {"unit": head["unit"],
                                     "metric": head["metric"], "points": []})
        s["points"].append({"rung": rung, "file": base,
                            "value": head["value"]})
        body = d["parsed"] if isinstance(d.get("parsed"), dict) else d
        for field, unit in SECONDARY_HEADLINES:
            if isinstance(body.get(field), (int, float)):
                s2 = series.setdefault(
                    f"{name}.{field}",
                    {"unit": unit, "metric": field, "points": []})
                s2["points"].append({"rung": rung, "file": base,
                                     "value": float(body[field])})
    for s in series.values():
        s["points"].sort(key=lambda p: (p["rung"] is not None, p["rung"]))
        s["direction"] = _direction(s["unit"])
    return {"series": series, "composites": composites}


def _delta_pct(prev: float, cur: float) -> float | None:
    if prev == 0:
        return None
    return (cur - prev) / prev * 100.0


def check(data: dict, threshold: float) -> list[str]:
    """Regression messages for series whose newest rung is worse than the
    prior rung by more than ``threshold`` percent."""
    bad = []
    for name, s in sorted(data["series"].items()):
        pts, d = s["points"], s["direction"]
        if len(pts) < 2 or d == 0:
            continue
        prev, cur = pts[-2], pts[-1]
        pct = _delta_pct(prev["value"], cur["value"])
        if pct is None:
            continue
        regressed = pct > threshold if d < 0 else pct < -threshold
        if regressed:
            bad.append(
                f"{name}: {prev['file']} -> {cur['file']} moved "
                f"{prev['value']:,.1f} -> {cur['value']:,.1f} {s['unit']} "
                f"({pct:+.1f}%, allowed ±{threshold:.0f}% "
                f"{'lower' if d < 0 else 'higher'}-is-better)")
    return bad


def render_md(data: dict, threshold: float) -> str:
    lines = [
        "# BENCH trajectory",
        "",
        "Consolidated view of every `BENCH_*.json` headline across PR "
        "rungs (`scripts/bench_report.py`; regenerate after adding a "
        "rung). `Δ%` compares each rung to the prior one; `--check` "
        f"fails the build past ±{threshold:.0f}% in the unit's regression "
        "direction.",
        "",
        "| series | unit | rung trail (oldest → newest) | latest | Δ% vs prior |",
        "|---|---|---|---:|---:|",
    ]
    for name, s in sorted(data["series"].items()):
        pts = s["points"]
        trail = " → ".join(
            (f"r{p['rung']:02d}:" if p["rung"] is not None else "")
            + f"{p['value']:,.1f}" for p in pts)
        latest = pts[-1]
        pct = (_delta_pct(pts[-2]["value"], latest["value"])
               if len(pts) >= 2 else None)
        arrow = "" if s["direction"] == 0 or pct is None else (
            " ⚠" if (pct > threshold if s["direction"] < 0
                     else pct < -threshold) else "")
        lines.append(
            f"| {name} | {s['unit'] or '-'} | {trail} "
            f"| {latest['value']:,.1f} "
            f"| {'-' if pct is None else f'{pct:+.1f}%'}{arrow} |")
    if data["composites"]:
        lines += ["", "Composite artifacts (no scalar headline, not "
                      "trended): "
                  + ", ".join(f"`{c['file']}`" for c in data["composites"])]
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="directory holding BENCH_*.json (default: repo root)")
    ap.add_argument("--out", default=None,
                    help="output directory (default: same as --dir)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on a >threshold%% regression vs the "
                         "newest prior rung")
    ap.add_argument("--threshold", type=float, default=20.0)
    ns = ap.parse_args(argv)
    bench_dir = ns.dir or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    out_dir = ns.out or bench_dir
    data = collect(bench_dir)
    data["threshold_pct"] = ns.threshold
    md = render_md(data, ns.threshold)
    with open(os.path.join(out_dir, "BENCH_TRAJECTORY.md"), "w") as f:
        f.write(md)
    with open(os.path.join(out_dir, "BENCH_TRAJECTORY.json"), "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    print(f"bench-report: {len(data['series'])} series, "
          f"{len(data['composites'])} composites -> "
          f"{os.path.join(out_dir, 'BENCH_TRAJECTORY.md')}")
    if ns.check:
        bad = check(data, ns.threshold)
        for b in bad:
            print(f"REGRESSION: {b}", file=sys.stderr)
        if bad:
            return 1
        print(f"bench-report: no series regressed past "
              f"{ns.threshold:.0f}% vs its prior rung")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
