#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program and its control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one process-local run of the cell (set-up and a window of
``--seconds`` at the cell's own load, the same code path as ``run.py``)
and then, over the same sample of served requests, the numbers that
``run.py`` compares (``harness.compare``):

* ``program``: of the logits rows the timed path produced and the tokens
  it served;
* ``fp8`` (the control) and ``int8``: of the rows that the reference
  computed in that precision, the one below the configuration's
  bfloat16, and of its own first choices at each position.

The program's readings over many seeds give each limit's lower end, the
control's its upper end.  One JSON line per seed; needs a TPU like
``run.py``.
"""
import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float, devices) -> dict:
    from bench import harness

    s = harness.serve(cell, seed, seconds, False, time.monotonic(), devices)
    ref, targets = harness.reference_rows(cell, seed, s.sample)
    rows = harness.served_rows(s.sample, ref.shape[1])
    out = {"workload": cell.name, "seed": seed,
           "program": harness.compare(ref, rows, [t[2] for t in targets])}
    for lp in ("fp8", "int8"):
        rows, _ = harness.reference_rows(cell, seed, s.sample, lowp=lp)
        out[lp] = harness.compare(ref, rows, rows.argmax(axis=1))
    out.update(served_tokens=sum(len(t) for _, t, _ in s.sample),
               requests=len(s.sample), steps=s.n_steps)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(REPO / "src"), str(REPO)]

    import jax

    from bench import harness
    from repro.runtime.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control: no TPU (first device is {devices[0].platform})", file=sys.stderr)
        return 3
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, devices[: cell.chips])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
