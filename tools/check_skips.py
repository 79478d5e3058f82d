#!/usr/bin/env python
"""CI skip-count gate: fail if pytest skipped more tests than the committed
baseline allows.

Usage: python tools/check_skips.py <pytest-output.txt> <baseline-file>

The baseline file holds one integer — the maximum allowed skip count in the
full-dependency CI environment (0: with hypothesis installed, every
property test runs; a rising skip count means a dependency or marker
silently regressed).
"""

from __future__ import annotations

import re
import sys


def skip_count(report: str) -> int:
    # the summary line looks like "282 passed, 9 skipped in 415.97s"
    m = re.findall(r"(\d+) skipped", report)
    return int(m[-1]) if m else 0


def main() -> int:
    if len(sys.argv) != 3:
        print(__doc__)
        return 2
    with open(sys.argv[1]) as f:
        report = f.read()
    with open(sys.argv[2]) as f:
        baseline = int(f.read().strip())
    n = skip_count(report)
    print(f"skipped: {n} (baseline allows {baseline})")
    if n > baseline:
        print("FAIL: skip count rose above the committed baseline — a "
              "dependency (hypothesis?) or marker regressed. If the new "
              "skips are intentional, update tests/skip_baseline.txt in "
              "the same PR and say why.")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
