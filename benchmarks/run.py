"""Benchmark entrypoint: `PYTHONPATH=src python -m benchmarks.run [--fast]`.

Runs every paper-table reproduction (with tolerance gates), the
beyond-paper policy study, the kernel microbenches, the live serving
bench, the fleet-orchestration bench, and renders the roofline table
from the dry-run results.  Ends with the machine-readable CSV
(name,us_per_call,derived).  ``--fast`` switches the fleet bench to its
smoke scenario (CI mode).
"""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (bench_archs, bench_beyond, bench_fleet,
                            bench_kernels, bench_paper_tables,
                            bench_roofline, bench_serving)
    from benchmarks.common import print_csv
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    fast = "--fast" in sys.argv
    print("#" * 72)
    print("# The Model Parking Tax -- reproduction + framework benchmarks")
    print("#" * 72)
    bench_paper_tables.run_all()
    bench_beyond.run_all()
    bench_archs.run_all()
    bench_kernels.run_all()
    bench_serving.run_all()
    bench_fleet.run_all(fast=fast)
    bench_roofline.run_all()
    print("#" * 72)
    print_csv()


if __name__ == "__main__":
    main()
