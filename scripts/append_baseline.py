#!/usr/bin/env python
"""Append a measured bench result to BASELINE.md (idempotent).

Kept for its record loader, which tests/test_bench_tools.py covers; the
`benchmark` PR that replaces bench.py decides whether any of it stays.
Usage:

    python scripts/append_baseline.py <tag> <artifact.json>
    python scripts/append_baseline.py --check <artifact.json>

The artifact is a captured bench stdout; its last JSON line is the
canonical `{"metric": ..., "value": ..., "detail": {...}}` record (parsed
with bench.py's own extractor, so the two cannot drift). ``--check`` exits
0 iff the artifact holds a real measurement (parseable and not an
``infrastructure_failure`` line). A row is appended at most once per
identical (tag, metric, value, unit, mfu, device, detail) tuple; only the
timestamp is excluded from the comparison, so re-runs with changed numbers
(including kernel-report rows) always record. BASELINE.md is created when
it is not there.
"""

from __future__ import annotations

import datetime
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from bench import _extract_json_line  # noqa: E402  (stdlib-only module)

BASELINE = os.path.join(HERE, "BASELINE.md")
SECTION = "## Measured results (appended by scripts/append_baseline.py)"
HEADER = (
    "\n" + SECTION + "\n\n"
    "One row per recorded bench run; `infrastructure_failure` rows are\n"
    "excluded at the source.\n\n"
    "| When (UTC) | Tag | Metric | Value | Unit | MFU | Device | Detail |\n"
    "|---|---|---|---|---|---|---|---|\n"
)


def load_record(path: str) -> dict | None:
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return None
    line = _extract_json_line(raw)
    return json.loads(line) if line else None


def main() -> int:
    if len(sys.argv) != 3:
        print(__doc__, file=sys.stderr)
        return 2
    tag, artifact = sys.argv[1], sys.argv[2]
    rec = load_record(artifact)
    if tag == "--check":
        ok = rec is not None and not (rec.get("detail") or {}).get(
            "infrastructure_failure"
        )
        return 0 if ok else 1
    if rec is None:
        print(f"append_baseline: no JSON line in {artifact}", file=sys.stderr)
        return 1
    detail = rec.get("detail", {}) or {}
    if detail.get("infrastructure_failure"):
        print(f"append_baseline: {tag} is an infrastructure-failure line; "
              "not a measurement — skipped", file=sys.stderr)
        return 0
    if "value" not in rec:
        # Free-form report (kernel_bench: has a metric but no scalar
        # value): stuff the whole JSON object into the detail column so
        # the timings/numerics land in BASELINE.md — and so re-runs with
        # changed numbers produce a different row (dedupe-visible).
        detail = {"report": rec, **detail} if detail else {"report": rec}
        rec = {"metric": rec.get("metric", tag), "value": "—",
               "unit": "see detail", "detail": detail}
    device = str(detail.get("device", "?"))
    extras = {
        k: detail[k]
        for k in ("batch_size", "step_time_mean_s", "tpu_unavailable",
                  "forced_cpu", "vs_baseline_kind", "report")
        if k in detail
    }
    if rec.get("vs_baseline") is not None:
        extras["vs_baseline"] = rec["vs_baseline"]
    extras_json = json.dumps(extras)
    if len(extras_json) > 700:
        extras_json = extras_json[:700] + "…"
    mfu = detail.get("mfu")
    # Everything but the timestamp participates in the dedupe comparison.
    body = (
        f"| {tag} | {rec.get('metric', '?')} | {rec.get('value')} | "
        f"{rec.get('unit', '?')} | {mfu if mfu is not None else '—'} | "
        f"{device} | {extras_json} |"
    )
    try:
        text = open(BASELINE).read()
    except OSError:
        text = ""
    for row in text.splitlines():
        row = row.strip()
        if row.startswith("|") and row.split("|", 2)[-1].strip() == body[2:]:
            print(f"append_baseline: identical {tag} row already recorded",
                  file=sys.stderr)
            return 0
    if SECTION not in text:
        text += HEADER
    when = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%d %H:%M"
    )
    with open(BASELINE, "w") as f:
        f.write(text if text.endswith("\n") or not text else text + "\n")
        f.write(f"| {when} {body}\n")
    print(f"append_baseline: recorded {tag} -> {rec.get('value')} ({device})",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
