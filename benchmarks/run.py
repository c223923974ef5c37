"""Benchmark driver — one module per paper table/figure (DESIGN.md §7).
Prints ``name,us_per_call,derived`` CSV. Scale with BENCH_SCALE (default
0.1 of the paper's corpus sizes, so the suite finishes on one CPU core).

Exits non-zero if any module fails (CI gates on this); the failure still
leaves a ``<module>_FAILED`` CSV row for postmortem parsing.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

# allow `python benchmarks/run.py` from anywhere (sys.path[0] is benchmarks/)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


MODULES = [
    "benchmarks.eager_update",      # Fig. 4(A)
    "benchmarks.lazy_all_members",  # Fig. 4(B)
    "benchmarks.single_entity",     # Fig. 5
    "benchmarks.hybrid_buffer",     # Fig. 6(B)
    "benchmarks.learning",          # Fig. 10
    "benchmarks.scalability",       # Fig. 11(A)
    "benchmarks.sensitivity",       # Fig. 12
    "benchmarks.waters",            # Fig. 13
    "benchmarks.multiclass",        # App. B.5.4 / C.3 (multi-view engine)
    "benchmarks.hybrid",            # §3.5.2 hybrid tier on the multi-view engine
    "benchmarks.storage",           # memory-budgeted buffer pool behind the probe
    "benchmarks.scale",             # paper-scale CS/FC on the multi-view engine
    "benchmarks.sql_serve",         # relational front-end overhead vs direct
    "benchmarks.serve_concurrent",  # concurrent wire-protocol serving swarm
    "benchmarks.fleet_lag",         # freshness scheduler: TARGET_LAG fleet
    "benchmarks.kernel_bench",      # framework kernels
]


def _selected(only: str, mod_name: str) -> bool:
    """Exact short-name match wins (``run.py hybrid`` must not also run
    ``hybrid_buffer``); otherwise substring, as before."""
    if only is None:
        return True
    shorts = {m.rsplit(".", 1)[-1] for m in MODULES}
    if only in shorts or only in MODULES:
        return only in (mod_name, mod_name.rsplit(".", 1)[-1])
    return only in mod_name


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failed = []
    for mod_name in MODULES:
        if not _selected(only, mod_name):
            continue
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            print(f"# {mod_name} FAILED", file=sys.stderr)
            traceback.print_exc()
            print(f"{mod_name}_FAILED,0,error")
            failed.append(mod_name)
    if failed:
        print(f"# failed modules: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
