"""Readings that set a cell's correctness limit: the program's widest logit
gap and its control's, seed by seed, in one process.

    python benchmarks/chip/calibrate.py --workload <name> --seconds <s> \
        --seeds <n1,n2,...>

For each seed it does what ``run_cell.py`` does (weights from the seed, a
fresh engine, the cell's traffic for its pre-window and ``--seconds``),
then compares the same sampled requests twice: the served tokens against
the float32 reference (the program's reading), and the token the control
(the cell's reference module with float8 matrix products) puts first
(the control's reading).  Each reading goes through the decision that
``run_cell.py`` makes, at the cell's committed limit: the program's has to
come out correct and the control's not.  The limit goes between the
largest program reading and the smallest control reading.  The benchmark's
own runs never run the control.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import harness  # noqa: E402
import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    harness.enable_caches()
    clock = harness.CompileClock()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        driver = run_cell.setup(cell, seed)
        win = driver.run(float(cell.traffic["pre_window_s"]), args.seconds)
        prompts, finished = driver.prompts, driver.finished()
        streaming = driver.streaming()
        driver.close()
        del driver
        sample = harness.pick_sample(finished, seed, run_cell.WANT_TOKENS,
                                     run_cell.MAX_REQUESTS, streaming)
        got = harness.compare(cell, seed, sample, prompts, control="fp8")
        got.update(seed=seed, output_tokens=harness.output_tokens(win))
        for who, gap in (("program", got["max_logit_gap"]),
                         ("control", got["control_max_logit_gap"])):
            got[who + "_correct"] = harness.verdict(
                gap, got["served_tokens_compared"],
                cell.config["limits"]["max_logit_gap"],
                run_cell.MIN_TOKENS)[0]
        rows.append(got)
        print("reading " + json.dumps(got), flush=True)
    prog = [r["max_logit_gap"] for r in rows if r["max_logit_gap"] is not None]
    ctl = [r["control_max_logit_gap"] for r in rows
           if r.get("control_max_logit_gap") is not None]
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "program_max": max(prog) if prog else None,
                      "control_min": min(ctl) if ctl else None,
                      "limit": cell.config["limits"]["max_logit_gap"],
                      "program_correct": all(r["program_correct"]
                                             for r in rows),
                      "control_correct": any(r["control_correct"]
                                             for r in rows),
                      "compile_s": clock.seconds,
                      "wall_s": time.monotonic() - PROCESS_START}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
