"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Usage:

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig7 fig9  # a subset
"""
from __future__ import annotations

import sys
import time
import traceback


#: benches whose rows are also persisted as BENCH_<name>.json at the repo
#: root (machine-readable perf trajectory across PRs)
JSON_BENCHES = ("control", "multistream", "churn", "kernels", "loadtest",
                "obs", "multitask", "multitenant")


def main() -> None:
    from benchmarks import (churn, control, kernel_bench, loadtest,
                            multistream, multitask, multitenant,
                            obs_overhead, paper_figs, roofline)

    benches = {
        "control": control.run,
        "churn": churn.run,
        "loadtest": loadtest.run,
        "obs": obs_overhead.run,
        "multistream": multistream.run,
        "multitask": multitask.run,
        "multitenant": multitenant.run,
        "fig6": paper_figs.fig6_stability,
        "fig7": paper_figs.fig7_tradeoff,
        "fig7seg": multitask.fig7_segmentation,
        "fig7kp": multitask.fig7_keypoint,
        "fig7ae": multitask.autoencoder_baseline,
        "fig8": paper_figs.fig8_delay_breakdown,
        "fig9": paper_figs.fig9_camera_overhead,
        "fig10": paper_figs.fig10_bandwidth,
        "fig11": paper_figs.fig11_reuse,
        "table2": paper_figs.table2_training_time,
        "fig12": paper_figs.fig12_fp_tolerance,
        "appxc": paper_figs.appxc_size_growth,
        "kernels": kernel_bench.run,
        "roofline": roofline.run,
    }
    from benchmarks import common
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    wanted = sys.argv[1:] or list(benches)
    print("name,us_per_call,derived")
    failures = 0
    for name in wanted:
        fn = benches[name]
        common.drain_rows()  # rows emitted from here on belong to `name`
        t0 = time.time()
        try:
            fn()
            print(f"bench/{name}_wall,{(time.time() - t0) * 1e6:.0f},ok")
            if name in JSON_BENCHES:  # only a complete run may replace
                common.write_bench_json(name, common.drain_rows())
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"bench/{name}_wall,{(time.time() - t0) * 1e6:.0f},"
                  f"FAILED:{type(e).__name__}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
