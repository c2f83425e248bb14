"""Run every benchmark; print ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--fast]

--fast skips the CPU wall-clock measurements (model-only rows).
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip CPU wall-clock measurements")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig8_dwc, pipeline_int8, roofline, serve_cnn,
                            serve_lm, table1_dse, table2_resources,
                            table3_e2e, table4_mlperf)

    suites = [
        ("table1", lambda: table1_dse.run()),
        ("table2", lambda: table2_resources.run()),
        ("table3", lambda: table3_e2e.run(measure=not args.fast)),
        ("table4", lambda: table4_mlperf.run()),
        ("fig8", lambda: fig8_dwc.run(measure=not args.fast)),
        ("pipeline", lambda: pipeline_int8.run(measure=not args.fast)),
        ("serve", lambda: serve_cnn.run(measure=not args.fast)),
        ("serve_lm", lambda: serve_lm.run(measure=not args.fast)),
        ("roofline", lambda: roofline.run()),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}")
        except Exception as e:
            failures += 1
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
