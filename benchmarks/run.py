"""Benchmark runner: ``python -m benchmarks.run`` prints one CSV row per
measurement: ``name,us_per_call,derived``.

Covers every paper table/figure (PPA reproduction), timed on the host
CPU.  Device measurements on a TPU are the chip benchmark's
(``benchmarks/chip/``, ``BENCHMARK.json``).
"""

from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import ppa_figures

    print("name,us_per_call,derived")
    failures = 0
    for fn in ppa_figures.ALL:
        try:
            for row in fn():
                print(row)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{fn.__name__},0,ERROR:{type(e).__name__}:{e}",
                  file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
