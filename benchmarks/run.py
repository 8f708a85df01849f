"""Benchmark harness: one module per paper table + kernel/roofline reports.

Prints ``name,us_per_call,derived`` CSV (harness contract).

  table1_transfers      — paper Table I   (hierarchy transfer counts)
  table2_mx_vs_baseline — paper Table II  (MX vs baseline traffic, TPU mapping)
  table3 (area)         — silicon-only; replaced by the VMEM-footprint
                          accounting in the tile rows (see DESIGN.md §7)
  table4_perf_energy    — paper Table IV + Fig. 3 (perf/energy reproduction)
  kernel_bench          — Pallas kernels (interpret) + XLA dispatch timings
  roofline_report       — §Roofline summary over the dry-run artifacts
"""
from __future__ import annotations


def main() -> None:
    from . import (
        kernel_bench, roofline_report, table1_transfers,
        table2_mx_vs_baseline, table3_area, table4_perf_energy,
    )

    print("name,us_per_call,derived")
    # a phase that raises fails the run (nonzero exit, traceback)
    for mod in (table1_transfers, table2_mx_vs_baseline, table3_area,
                table4_perf_energy, kernel_bench, roofline_report):
        for row_name, us, derived in mod.run():
            print(f"{row_name},{us:.2f},{derived}")


if __name__ == '__main__':
    main()
