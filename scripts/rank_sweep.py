"""Rank sweep_bench results and recommend shipping defaults.

  python scripts/rank_sweep.py chiprun_out/sweep_results.jsonl

Reads the JSONL a sweep run printed (one object per row, errors
included), groups rows by preset, ranks by tok_per_sec, and prints the
deltas vs each preset's first (baseline-config) row — the table that
drives the "flip the preset defaults" decision after a sweep.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import DEFAULT_PRESET  # noqa: E402  (single source of truth)


def main(path: str) -> int:
    rows, errors, truncated = [], [], 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                # a sweep killed mid-row leaves a partial trailing line;
                # rank what completed (the matrix is value-ordered)
                truncated += 1
                continue
            if "best" in r:
                continue
            (errors if "error" in r else rows).append(r)

    by_preset: dict[str, list[dict]] = {}
    anchored_ok: dict[str, bool] = {}
    for r in rows + errors:  # file order; errors only influence anchoring
        p = r.get("preset", DEFAULT_PRESET)
        by_preset.setdefault(p, [])
        if "error" in r:
            anchored_ok.setdefault(p, False)
        else:
            anchored_ok.setdefault(p, True)
            by_preset[p].append(r)

    for preset, group in by_preset.items():
        if not group:
            continue
        base = group[0]["tok_per_sec"]
        note = "" if anchored_ok[preset] else \
            "  [baseline row FAILED; anchored on first successful row]"
        print(f"== {preset} (first row {base:,.0f} tok/s = 1.00x){note}")
        for r in sorted(group, key=lambda r: -r["tok_per_sec"]):
            knobs = {k: v for k, v in r.items()
                     if k not in ("tok_per_sec", "mfu_model", "mfu_hw",
                                  "step_ms", "loss", "preset")}
            print(f"  {r['tok_per_sec']:>9,.0f} tok/s  x{r['tok_per_sec']/base:4.2f}"
                  f"  mfu_model {r.get('mfu_model', 0):.4f}  {knobs}")
        print()

    if errors:
        print(f"== {len(errors)} failed rows")
        for r in errors:
            spec = {k: v for k, v in r.items() if k != "error"}
            print(f"  {spec}\n    {r['error'][:160]}")
    if truncated:
        print(f"== {truncated} unparseable line(s) skipped (sweep killed "
              "mid-row?)")
    return 0 if rows else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1] if len(sys.argv) > 1 else
                          "chiprun_out/sweep_results.jsonl"))
