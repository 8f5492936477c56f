"""Benchmark driver — one module per paper table/figure (DESIGN.md §6).
Prints ``name,us_per_call,derived`` CSV."""
import sys
import time

from . import (amg_levels, amg_scaling, comm_strategies, dist_setup,
               dist_solve, kernels, lm_roofline, pingpong_model, ptap_sweeps)
from repro.core.perf_model import BLUE_WATERS, QUARTZ
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("fig8_9", lambda: pingpong_model.rows()),
    ("machine_measured", lambda: pingpong_model.measured_rows(smoke=True)),
    ("fig14_15", lambda: comm_strategies.rows()),
    ("fig2_4", lambda: amg_levels.rows()),
    ("fig16_17_bw", lambda: amg_scaling.rows("graddiv", BLUE_WATERS)),
    ("fig18", lambda: amg_scaling.rows("laplace", BLUE_WATERS)),
    ("fig19_quartz", lambda: amg_scaling.rows("graddiv", QUARTZ)),
    ("fig20_weak", lambda: amg_scaling.rows("graddiv", BLUE_WATERS,
                                            weak=True)),
    ("fig21", lambda: ptap_sweeps.rows()),
    ("dist_solve", lambda: dist_solve.rows(smoke=True)),
    ("dist_solve_cycles", lambda: dist_solve.cycle_smoother_rows(smoke=True)),
    ("dist_solve_overlap", lambda: dist_solve.overlap_rows(smoke=True)),
    ("dist_solve_weak", lambda: dist_solve.weak_rows(smoke=True)),
    ("dist_solve_session", lambda: dist_solve.session_rows(smoke=True)),
    ("dist_solve_streaming", lambda: dist_solve.streaming_rows(smoke=True)),
    ("dist_solve_serving", lambda: dist_solve.serving_rows(smoke=True)),
    ("dist_setup", lambda: dist_setup.rows(smoke=True)),
    ("kernels", lambda: kernels.rows(smoke=True)),
    ("roofline", lambda: lm_roofline.rows()),
]


def main() -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    only = sys.argv[1] if len(sys.argv) > 1 else None
    for tag, fn in MODULES:
        if only and only not in tag:
            continue
        t0 = time.perf_counter()
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:  # keep the harness running
            print(f"{tag}_ERROR,0.0,{type(e).__name__}:{e}")
        print(f"# {tag} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)


if __name__ == '__main__':
    main()
