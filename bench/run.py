#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights made on the device from the seed, compile or the
checkout's compile cache, warm-up) runs first, then the window of
``--seconds``.  With ``--trace 1`` a short steady stretch of the window is
profiled and the cell's per-layer metrics are printed instead of its
end-to-end ones.  The last line of standard output is one JSON object;
the numbers the correctness check compared, each with its limit, are the
last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(REPO / "src"), str(REPO)]

    import jax

    from bench import harness
    from repro.runtime.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (first device is {devices[0].platform})", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 3
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r}", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.measure(cell, args.seed, args.seconds, bool(args.trace), peaks[kind],
                             T_START, devices[: cell.chips])
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
