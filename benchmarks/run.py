# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import sys


def main() -> None:
    from benchmarks import (fig2_sustainability, kernel_bench, roofline_table,
                            serve_bench, table1_gridmix, table2_embodied,
                            table3_efficiency)
    from benchmarks.bench_util import emit

    rows = []
    for mod in (table1_gridmix, table2_embodied, table3_efficiency,
                fig2_sustainability, kernel_bench, roofline_table,
                serve_bench):
        try:
            rows.extend(mod.run())
        except Exception as e:  # a missing artifact must not hide the rest
            rows.append((f"{mod.__name__}/ERROR", 0.0,
                         f"{type(e).__name__}: {e}"))
    emit(rows)


if __name__ == "__main__":
    main()
