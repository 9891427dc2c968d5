"""Benchmark harness — one suite per paper claim/table (see DESIGN.md §6).

E1 blocking sweep (C1/C4)   E2 interconnect (C3)   E3 MOB overlap (C2)
E4 kernel microbench (C1)   E5 edge transformer    E6 roofline table

The serving engine's benchmark is ``bench/run.py`` (measured on the chip).
"""
import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (blocking_sweep, edge_transformer, interconnect,
                            kernel_bench, mob_overlap, roofline_table)
    suites = [("E1", blocking_sweep), ("E2", interconnect), ("E3", mob_overlap),
              ("E4", kernel_bench), ("E5", edge_transformer),
              ("E6", roofline_table)]
    if len(sys.argv) > 1:
        suites = [(n, m) for n, m in suites if n in sys.argv[1:]]
    for name, mod in suites:
        t0 = time.time()
        lines = mod.run()
        print("\n".join(lines))
        print(f"[{name} done in {time.time()-t0:.1f}s]\n", flush=True)


if __name__ == "__main__":
    main()
