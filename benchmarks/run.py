"""Benchmark harness: one function per paper table/figure + the roofline
report. ``PYTHONPATH=src python -m benchmarks.run [name ...]``"""

import sys

from benchmarks import paper_tables, roofline_report


def main() -> None:
    names = sys.argv[1:]
    table = {fn.__name__: fn for fn in paper_tables.ALL}
    table["roofline"] = roofline_report.report
    run = names or list(table)
    for name in run:
        if name not in table:
            print(f"unknown benchmark {name!r}; have {sorted(table)}")
            sys.exit(2)
        table[name]()


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    main()
