"""Merge per-model banked bench JSONs (one bench.py line each) into one
BENCH-format artifact: first model becomes the primary record, the rest go
to extra_metrics — the same shape bench.py emits for a multi-model run.

Usage: python tools/bank_merge.py /tmp/bank/*.json > merged.json
"""

from __future__ import annotations

import json
import sys


def main(paths):
    records = []
    for p in paths:
        try:
            with open(p) as f:
                text = f.read().strip()
            if not text:
                continue
            rec = json.loads(text.splitlines()[-1])
        except (OSError, json.JSONDecodeError) as e:
            print(f"# skip {p}: {e}", file=sys.stderr)
            continue
        if not isinstance(rec, dict):
            print(f"# skip {p}: not a JSON object", file=sys.stderr)
            continue
        if rec.get("error"):
            print(f"# skip {p}: error={rec['error']}", file=sys.stderr)
            continue
        rec["_source"] = p
        records.append(rec)
    if not records:
        raise SystemExit("no usable records")
    primary, extra = records[0], records[1:]
    if extra:
        primary = dict(primary, extra_metrics=extra)
    json.dump(primary, sys.stdout)
    print()


if __name__ == "__main__":
    main(sys.argv[1:])
