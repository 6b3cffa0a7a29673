"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers that decide ``correct`` follow on standard error, each beside its
limit. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
# one process with few threads: no idle math-library pools beside the
# host loop, whose run-to-run spread they widened on the chip's host
for _v in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb to this path")
    ap.add_argument("--trace-steps", type=int, default=3,
                    help="whole steps profiled in a traced run")
    ap.add_argument("--root", default=str(ROOT),
                    help="directory holding BENCHMARK.json and bench/")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench import common, harness
    root = Path(args.root)
    spec = common.load_benchmark(root)
    wl = common.workload(spec, args.workload)
    result, lines, _ = harness.run_cell(spec, wl, args.seed, args.seconds,
                                        bool(args.trace), t_start=T_START,
                                        root=root, keep_trace=args.keep_trace,
                                        trace_steps=args.trace_steps)
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
