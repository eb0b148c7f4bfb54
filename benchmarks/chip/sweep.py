"""Find an open-loop cell's knee: offer its traffic at several fixed rates,
one after another in one process, and print what each rate did.

    python benchmarks/chip/sweep.py --workload <name> --seed <n> \
        --seconds <s> --rates <r1,r2,...>

Each rate gets a fresh engine (the weights and compiled programs are
kept), the traffic file's pre-window and ``--seconds`` of measurement.  A
rate is sustained while requests finish about as fast as they are due and
the queue does not grow through the window.  The cell's traffic file then
records a fixed rate below the knee; the benchmark never searches.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    base = harness.load_cell(args.workload)
    if base.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload}: a closed loop has no rate")
    try:
        harness.require_chips(base.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    harness.enable_caches()
    cfg = harness.program_config(base)
    params = harness.make_params(base, cfg, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell.traffic["arrivals"]["rate_per_s"] = rate
        driver = harness.Driver(cell, params, cfg, args.seed)
        driver.warm()
        sched = driver.sched
        state = {}

        def window_start():
            state["backlog"] = len(sched.queue)

        win = driver.run(float(cell.traffic["pre_window_s"]), args.seconds,
                         on_window_start=window_start)
        due = [d for d in win.due.values() if win.start <= d < win.end]
        done = [r for r in driver.finished()
                if win.start <= r.finish_time <= win.end]
        ttft = harness.ttft_samples(win) * 1e3
        itl = harness.itl_samples(win) * 1e3
        row = {"rate_sessions_per_s": rate,
               "requests_due_per_s": len(due) / win.seconds,
               "requests_done_per_s": len(done) / win.seconds,
               "queue_start": state["backlog"], "queue_end": len(sched.queue),
               "active_end": len(sched.active),
               "output_tok_s": harness.output_tokens(win) / win.seconds,
               "ttft_p50_ms": float(np.median(ttft)) if len(ttft) else None,
               "ttft_p95_ms": harness.p95(ttft),
               "itl_p50_ms": float(np.median(itl)) if len(itl) else None,
               "itl_p95_ms": harness.p95(itl), "ticks": len(win.ticks)}
        print("rate " + json.dumps(row), flush=True)
        del sched, window_start
        driver.close()
        del driver
    return 0


if __name__ == "__main__":
    sys.exit(main())
