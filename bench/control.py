#!/usr/bin/env python3
"""Readings that set each cell's limit: the program's sound runs, and the
control.

    python3 bench/control.py --workload <cell> --seconds S --seeds N ... \\
        [--control-seeds N ...]

For every seed it makes a run of the cell as ``bench/run.py`` does (set-up,
a window at the cell's own load, the same sample of finished requests) and
prints the sound readings: the gaps, in logit standard deviations, by
which the served tokens lie below the float32 reference's best.  For a
control seed it also reads the control on the same prompts and served
tokens: at each position the token that the next precision below the
configuration's puts first, and that token's gap under the reference.
Where the configuration states its limits, both are judged by them as a
run is (``run.judge``, ``run.passed``): ``correct`` for the sound run,
``control_correct`` for the control, which has to come out false.

- A bfloat16 configuration's control is the program's own int8 path: the
  same weights through the W8A8 engine's ragged step (calibration, int8
  codes, ``int8_matmul``), fed the prompt and served tokens in chunks.
- A W8A8 configuration's control is the reference computed in int4: every
  matmul's weight and input fake-quantised to 4 bits.

One JSON line per seed on stdout.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import run  # noqa: E402


def w8a8_argmax(cell: run.Cell, ref, seed: int, sample) -> list:
    """At every served position of ``sample``, the token the program's
    W8A8 path (calibrated int8 weights, ``int8_matmul``) puts first, from
    its ragged step fed the prompt and served tokens chunk by chunk."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.launch import steps as S
    from repro.models import model as M
    from repro.models.attention import RaggedBatch
    c = cell.config
    bs, chunk = c["block_size"], c["chunk"]
    with run.benchmark_weights(ref, c, seed):
        built = serve.serve_engine(
            c["arch"], smoke=bool(c.get("smoke", False)), requests=[],
            n_slots=1, max_model_len=bs, num_blocks=2, block_size=bs,
            chunk=chunk, kv_bits=c["kv_bits"], w8a8=True,
            prompt_lens=(c["calibration_tokens"],),
            cfg_overrides=c.get("cfg_overrides"),
            seed=run.PROGRAM_SEED)
    eng = built["engine"]
    cfg, ctx, params = eng.cfg, built["ctx"], eng.params
    del built, eng
    nbmax = -(-cell.mix["max_model_len"] // bs)
    step = jax.jit(S.build_ragged_step(cfg, ctx))
    out = []
    for tr in sample:
        seq, rows, _ = run.served(tr)
        n = len(seq)
        cache = M.init_paged_cache(cfg, nbmax + 1, bs)
        table = np.zeros(nbmax, np.int32)
        table[:-(-n // bs)] = np.arange(1, -(-n // bs) + 1)
        arg = np.zeros(n, np.int32)
        for lo in range(0, n, chunk):
            hi = min(n, lo + chunk)
            pos = np.arange(lo, hi, dtype=np.int32)
            tok = np.zeros(chunk, np.int32)
            ps = np.zeros(chunk, np.int32)
            dest = np.zeros(chunk, np.int32)      # padding rows: trash block
            tok[:hi - lo], ps[:hi - lo] = seq[lo:hi], pos
            dest[:hi - lo] = table[pos // bs] * bs + pos % bs
            one = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
            rb = RaggedBatch(dest=jnp.asarray(dest),
                             block_tables=jnp.asarray(table[None]),
                             q_start=one(0), q_len=one(hi - lo),
                             kv_len=one(hi), tq_max=chunk)
            logits, cache = step(params, jnp.asarray(tok), cache,
                                 jnp.asarray(ps), rb)
            arg[lo:hi] = np.asarray(jnp.argmax(logits[:hi - lo], -1))
        out.append(arg[rows])
        del cache
    return out


def int4_argmax(cell: run.Cell, ref, seed: int, sample) -> list:
    """At every served position, the argmax of the reference in int4."""
    w = ref.make_weights(cell.config, seed)
    out = []
    for tr in sample:
        seq, rows, g = run.served(tr)
        out.append(ref.token_gaps(w, cell.config, seq, rows, g,
                                  precision="int4")["argmax"])
    return out


def readings(cell: run.Cell, seed: int, seconds: float, control: bool,
             chip_check: bool = True, compile_cache: bool = True) -> dict:
    t0 = time.perf_counter()
    r = run.serve_cell(cell, seed, seconds, False, chip_check, compile_cache)
    ref = r["ref"]
    c = cell.config["correct"]
    sample = run.sample_requests(r["tracks"], seed, c["sample_tokens"],
                                 c["max_requests"])
    w = ref.make_weights(cell.config, seed)
    sound = run.regrets(ref, cell.config, w, sample)
    del w
    limits = bool(c.get("limits"))
    out = {"workload": cell.name, "seed": seed,
           "device": run.device_name(r["out"]["device"]),
           "metrics": {k: v["value"] for k, v in r["out"]["metrics"].items()},
           "requests": len(sample),
           "tokens": int(sum(len(g) for g in sound)),
           "sound": run.gap_stats(sound)}
    if limits:
        out["correct"] = run.passed(run.judge(cell, sample, sound))
    if control:
        pick = (int4_argmax if cell.config["w8a8"] else w8a8_argmax)(
            cell, ref, seed, sample)
        w = ref.make_weights(cell.config, seed)
        ctrl = run.regrets(ref, cell.config, w, sample, picks=pick)
        del w
        served = np.concatenate([run.served(tr)[2] for tr in sample])
        out.update(control=("int4 reference" if cell.config["w8a8"]
                            else "program W8A8 path"),
                   control_stats=run.gap_stats(ctrl),
                   control_agreement=float(np.mean(np.concatenate(pick)
                                                   == served)))
        if limits:
            checks = run.judge(cell, sample, ctrl)
            out.update(control_checks=checks,
                       control_correct=run.passed(checks))
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = run.find_cell(args.workload)
    for s in args.seeds:
        try:
            out = readings(cell, s, args.seconds, s in args.control_seeds)
        except Exception as e:                     # a seed that raised
            import traceback
            traceback.print_exc()
            out = {"workload": cell.name, "seed": s, "error": repr(e)}
        print(json.dumps(out), flush=True)
        import gc
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
