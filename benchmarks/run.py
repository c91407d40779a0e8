"""Benchmark harness: one module per paper theorem/figure + system benches.
Prints ``name,us_per_call,derived`` CSV rows (template contract)."""

import sys
import traceback


def main() -> None:
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        bench_cluster,
        bench_coding,
        bench_collectives,
        bench_fig2_spectrum,
        bench_gradient_coding,
        bench_multitenant,
        bench_planner,
        bench_roofline,
        bench_serving_latency,
        bench_sim_engine,
        bench_step_time,
        bench_sweep_kernel,
        bench_thm1_assignment,
        bench_thm2_exponential,
        bench_thm4_variance,
    )

    modules = [
        bench_sim_engine,
        bench_planner,
        bench_sweep_kernel,
        bench_thm1_assignment,
        bench_thm2_exponential,
        bench_fig2_spectrum,
        bench_thm4_variance,
        bench_step_time,
        bench_collectives,
        bench_serving_latency,
        bench_multitenant,
        bench_gradient_coding,
        bench_coding,
        bench_roofline,
        bench_cluster,
    ]
    print("name,us_per_call,derived")
    failures = 0
    for mod in modules:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}")
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
