#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a ramp on the chip.

    python3 bench/sweep.py --workload <cell> --seed N --rates 0.6 0.8 ... \\
        --warm-seconds 40 --seconds-per-rate 40

One engine, set up as ``bench/run.py`` sets it up, serves the mix's traffic
at each rate in turn, with no pause between rates: ``--warm-seconds`` to
bring the load to its steady state, then ``--seconds-per-rate`` measured.
Per rate it prints one JSON line over the measured part: requests due,
their time to first token (p50, p90; those still without one count to the
end of the ramp), the slots in use and the waiting queue at its start and
end, the median gap between tokens and the tokens per second completed.
The knee is the highest rate whose waiting queue does not grow.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import drive, run  # noqa: E402
from bench.traffic import generate  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--warm-seconds", type=float, required=True)
    ap.add_argument("--seconds-per-rate", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.find_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch import serve
    from repro.serving import Request
    serve.enable_compile_cache()
    ref = run.load_module(run.BENCH / "references"
                          / f"{cell.config['reference']}.py", "ref")
    engine = run.build_engine(cell, ref, args.seed)["engine"]
    run.warm_up(engine, cell, cell.config["vocab_size"])
    warm, span = args.warm_seconds, args.warm_seconds + args.seconds_per_rate
    planned, rid = [], 0
    for i, rate in enumerate(args.rates):
        mix = dict(cell.mix, rate_per_s=rate, preroll_s=0.0)
        for p in generate.open_loop(mix, span, args.seed + i,
                                    cell.config["vocab_size"]):
            p.rid, p.due = rid, p.due + i * span
            rid += 1
            planned.append((i, p))
    first, last = {}, {}    # (waiting, slots in use) by part: rate i's
    #                         warm part is 2i, its measured part 2i + 1

    def on_step(w, t, finished):
        k = 2 * int(t // span) + (t % span >= warm)
        st = (len(engine.sched.waiting),
              sum(s is not None for s in engine.sched.slots))
        first.setdefault(k, st)
        last[k] = st

    def make(p, due):
        return Request(rid=p.rid, prompt=p.prompt,
                       max_new_tokens=p.max_new_tokens, temperature=0.0,
                       arrival=due)

    total = span * len(args.rates)
    w, tracks = drive.open_loop(engine, [p for _, p in planned], total, make,
                                on_step)
    end = w.now()
    for i, rate in enumerate(args.rates):
        lo, hi = i * span + warm, (i + 1) * span
        mine = [tr for tr in tracks if lo <= tr.due < hi]
        ttft = [(tr.first if tr.first is not None else end) - tr.due
                for tr in mine]
        toks = sum(1 for tr in tracks for t in tr.stamps if lo <= t < hi)
        gaps = [b - a for tr in tracks for a, b in zip(tr.stamps,
                                                       tr.stamps[1:])
                if lo <= b < hi]
        start, stop = first.get(2 * i + 1), last.get(2 * i + 1)
        print(json.dumps({
            "rate_per_s": rate, "due": len(mine),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p90_s": float(np.percentile(ttft, 90)),
            "itl_p50_s": float(np.percentile(gaps, 50)),
            "waiting_at_start": start and start[0],
            "waiting_at_end": stop and stop[0],
            "slots_in_use_at_start": start and start[1],
            "slots_in_use_at_end": stop and stop[1],
            "output_tokens_per_s": toks / args.seconds_per_rate}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
