#!/usr/bin/env python
"""Incident timeline report: what broke, when it deflected, and what
changed.

Input: a JSON artifact carrying incidents in any of the shapes the
stack produces —

* an artifact with an ``incidents`` list;
* a merged cluster view (``merge_cluster`` output) whose ``incidents``
  key holds the ``merge_incidents`` fold;
* a single ``Telemetry.payload()`` / ``IncidentEngine.snapshot()``
  dict (``open`` / ``recent`` lists).

For every finalized incident it renders the breach (rule, severity,
value), the estimated deflection onset vs. the firing edge, the
captured journal timeline (chaos injections flagged ``[GT]``), and the
ranked suspect list the blame engine produced.  ``--json`` prints the
normalized report instead (machine parity with the rendered view).

Usage:
    python tools/incident_report.py incidents.json
    python tools/incident_report.py cluster.json --json
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def load_incidents(path: str) -> list:
    """Normalize any supported artifact shape into one incident list
    (open incidents included, stamped by their ``status``)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return []
    if isinstance(data, list):
        return [i for i in data if isinstance(i, dict)]
    out = []
    snap = data
    # merged cluster view / payload: incidents section may be nested
    if isinstance(data.get("incidents"), dict):
        snap = data["incidents"]
    if isinstance(snap.get("incidents"), list):
        out.extend(snap["incidents"])
    for key in ("open", "recent"):
        if isinstance(snap.get(key), list):
            out.extend(snap[key])
    # bench artifact: per-scenario records each carrying an incident
    # (a name -> record dict; tolerate a list)
    scenarios = data.get("scenarios") or {}
    if isinstance(scenarios, dict):
        scenarios = [dict(sc, name=name)
                     for name, sc in sorted(scenarios.items())
                     if isinstance(sc, dict)]
    for sc in scenarios:
        if not isinstance(sc, dict):
            continue
        inc = sc.get("incident")
        if isinstance(inc, dict):
            out.append(dict(inc, scenario=sc.get("name")))
    seen = set()
    deduped = []
    for inc in out:
        key = (inc.get("id"), inc.get("host"), inc.get("opened_at"),
               inc.get("scenario"))
        if key in seen:
            continue
        seen.add(key)
        deduped.append(inc)
    deduped.sort(key=lambda i: (i.get("opened_at") or 0.0,
                                str(i.get("id"))))
    return deduped


def analyze(incidents: list) -> dict:
    """The normalized report: per-incident summary + totals."""
    rows = []
    gt_hits = 0
    finalized = 0
    for inc in incidents:
        suspects = inc.get("suspects") or []
        top = suspects[0] if suspects else None
        if inc.get("status") == "finalized":
            finalized += 1
            if top and top.get("ground_truth"):
                gt_hits += 1
        rows.append({
            "id": inc.get("id"),
            "host": inc.get("host"),
            "scenario": inc.get("scenario"),
            "rule": inc.get("rule"),
            "severity": inc.get("severity"),
            "status": inc.get("status"),
            "opened_at": inc.get("opened_at"),
            "onset_at": inc.get("onset_at"),
            "value": inc.get("value"),
            "labels": inc.get("labels") or {},
            "events": len(inc.get("events") or ()),
            "series": len(inc.get("series") or ()),
            "capture_latency_s": inc.get("capture_latency_s"),
            "top_suspect": (None if top is None else {
                "kind": top.get("kind"),
                "scope": top.get("scope") or {},
                "detail": top.get("detail"),
                "score": top.get("score"),
                "ground_truth": bool(top.get("ground_truth")),
            }),
            "suspects": suspects,
            "timeline": inc.get("events") or [],
        })
    return {"incidents": len(rows), "finalized": finalized,
            "top1_ground_truth": gt_hits, "rows": rows}


def _fmt_scope(scope: dict) -> str:
    return (",".join(f"{k}={v}" for k, v in sorted(scope.items()))
            or "fleet-wide")


def render(report: dict, events: int = 8) -> str:
    lines = ["================ incident report ================",
             "incidents: %d   finalized: %d   top-1 ground-truth: %d"
             % (report["incidents"], report["finalized"],
                report["top1_ground_truth"])]
    for r in report["rows"]:
        lines.append("")
        head = "-- %s  %s [%s] %s" % (
            r["id"], r["rule"], r["severity"], r["status"])
        if r.get("scenario"):
            head += "  (scenario: %s)" % r["scenario"]
        if r.get("host"):
            head += "  @%s" % r["host"]
        lines.append(head)
        onset = r.get("onset_at")
        opened = r.get("opened_at") or 0.0
        lead = ("%.2fs before the alert" % (opened - onset)
                if onset is not None and onset < opened
                else "at the alert edge")
        lines.append("   breach value=%s  scope %s  onset %s"
                     % (r.get("value"), _fmt_scope(r["labels"]), lead))
        lines.append("   black box: %d series, %d journal event(s), "
                     "capture %.3fms"
                     % (r["series"], r["events"],
                        1e3 * (r.get("capture_latency_s") or 0.0)))
        if r["suspects"]:
            lines.append("   suspects:")
            for s in r["suspects"]:
                lines.append(
                    "     %d. %-20s %-28s score %7.3f%s  %s"
                    % (s.get("rank", 0), s.get("kind"),
                       _fmt_scope(s.get("scope") or {}),
                       s.get("score") or 0.0,
                       "  [GT]" if s.get("ground_truth") else "",
                       s.get("detail") or ""))
        tl = r["timeline"]
        if tl:
            lines.append("   timeline (newest %d of %d):"
                         % (min(events, len(tl)), len(tl)))
            for ev in tl[-events:]:
                lines.append(
                    "     t=%-10s %-22s %-28s%s %s"
                    % (ev.get("at"), ev.get("kind"),
                       _fmt_scope(ev.get("scope") or {}),
                       " [GT]" if ev.get("ground_truth") else "",
                       ev.get("detail") or ""))
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path", help="incident artifact (bench INCIDENT_"
                                "r01.json, merged cluster view, or a "
                                "payload/engine snapshot)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.add_argument("--events", type=int, default=8,
                   help="timeline events rendered per incident "
                        "(default 8)")
    args = p.parse_args(argv)
    incidents = load_incidents(args.path)
    if not incidents:
        print(f"no incidents found at {args.path!r}", file=sys.stderr)
        return 1
    report = analyze(incidents)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(render(report, events=args.events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
