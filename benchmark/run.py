#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix and per-layer metrics are the files those
names lead to (``harness/manifest.py``). One process holds the chip;
traffic generators that are processes import no JAX.

The last line of standard output is the result, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and with
``--trace 1`` ``breakdown``, and last ``compared``: every number compared
as ``[value, op, limit, ok]``, which the last lines of standard error
repeat. Without a TPU, with fewer chips than the cell asks for, or without
the program beside it, it exits non-zero and prints no result.
``--control 1`` (never passed by the driver) serves the configuration's
lower-precision control, which has to come out not correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import core, manifest

    try:
        cell = manifest.Manifest(ROOT).resolve(args.workload)
    except manifest.ManifestError as e:
        core.fail(str(e), 2)
    if not os.path.isdir(os.path.join(ROOT, "ccfd_tpu")):
        core.fail("the program (ccfd_tpu/) is not in this checkout", 2)
    import jax

    marks = [("import_jax", time.perf_counter())]
    core.enable_compile_cache(ROOT)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        core.fail(f"JAX found no backend: {e}", 3)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        core.fail(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                  f"found {len(devices)} {devices[0].platform} device(s). "
                  "There is no CPU fallback.", 3)
    marks.append(("tpu_runtime", time.perf_counter()))
    result = core.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, root=ROOT, control=bool(args.control), marks=marks)
    print(json.dumps(result), flush=True)
    for name, row in result["compared"].items():
        print(core.check_line(name, *row), file=sys.stderr)
    sys.stderr.flush()
    return 0


def release_chip(limit_s: float = 30.0) -> None:
    """Hand the chip back in order before the process goes: a runtime that
    is cut off instead makes the next process wait for the chip. Bounded,
    because a teardown that hangs must not hang the run."""
    import threading

    import jax.extend.backend

    watchdog = threading.Timer(limit_s, os._exit, args=(0,))
    watchdog.daemon = True
    watchdog.start()
    jax.clear_caches()
    jax.extend.backend.clear_backends()
    watchdog.cancel()


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    if rc == 0:
        release_chip()
    # the result is printed and every process the run started has been
    # waited for; a lingering service thread must not turn the interpreter's
    # own teardown into a hang
    os._exit(rc)
