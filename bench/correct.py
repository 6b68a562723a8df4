"""Whether what the timed window served is correct.

Once the window has closed, a sample of the requests it finished, drawn
from the seed, goes through the configuration's plain reference: the
prompt and the served tokens, teacher-forced. At every served position the
comparison reads how far the reference's logit of the served token lies
below the reference's best logit, and keeps the widest such gap over the
sample (`logit_gap`). Greedy decoding serves the argmax of the program's
own bf16 logits, so a sound run's gap is the rounding between bf16 and the
float32 reference; a wrong adapter, page, position or kernel picks tokens
the reference ranks far below its best.

The sample always holds the request with the most served tokens, a cold
(CPU-assisted) and a warm request where the window had both, and one
request of each adapter rank it served; the rest is drawn from the seed
until it holds `TOKENS` served tokens.

The limit of each configuration is in `bench/limits/<config>.json`, with
the readings it was set from: the largest gap of sound runs, and the
smallest of the control, the reference at float8 put in the program's
place (a run with `control`), read at every position of the same
prompts and tokens. The control's run has to come out not correct.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List

import numpy as np

from bench import arch, traffic

HERE = pathlib.Path(__file__).resolve().parent
TOKENS = 512
MAX_REQUESTS = 16
# the control: the reference one precision step below the configurations'
# bf16, put in the program's place (`bench/run.py --control 1`)
CONTROL = "fp8"


def limits(config: str) -> dict:
    with open(HERE / "limits" / f"{config}.json") as f:
        return json.load(f)


def reference(conf: dict, weights, seq: int, n_rows: int, quant: str = ""):
    mod = arch.load(conf, "reference")
    return mod.Reference(conf, weights, seq, n_rows, quant=quant)


def choose(done: List[dict], seed: int) -> List[dict]:
    """The sample: records of finished requests (keys `tokens`, `rank`,
    `cold`)."""
    if not done:
        return []
    order = [done[i] for i in traffic.rng_for(seed, 99).permutation(
        len(done))]
    must = [max(done, key=lambda r: len(r["tokens"]))]
    for pick in (lambda r: r["cold"], lambda r: not r["cold"]):
        must += [r for r in order if pick(r)][:1]
    for rank in sorted({r["rank"] for r in done}):
        must += [r for r in order if r["rank"] == rank][:1]
    chosen, seen, must_ids = [], set(), {id(r) for r in must}
    for r in must + order:
        if id(r) in seen:
            continue
        if id(r) not in must_ids and (len(chosen) >= MAX_REQUESTS or sum(
                len(c["tokens"]) for c in chosen) >= TOKENS):
            break
        chosen.append(r)
        seen.add(id(r))
    return chosen


def gaps(ref, rec: dict, adapter, ctrl=None) -> tuple:
    """Per served token: the reference's best logit minus its logit of the
    served token; with `ctrl` (the control), also minus its logit of the
    token the control ranks first at the same position of the same
    teacher-forced sequence (None without)."""
    toks = np.concatenate([rec["prompt"],
                           np.asarray(rec["tokens"][:-1], np.int32)])
    n, first = len(rec["tokens"]), len(rec["prompt"]) - 1
    lg = ref.logits(toks, first, n, adapter, rec["rank"])
    best = lg.max(axis=1)
    served = best - lg[np.arange(n), np.asarray(rec["tokens"])]
    if ctrl is None:
        return served, None
    pick = ctrl.logits(toks, first, n, adapter, rec["rank"]).argmax(axis=1)
    return served, best - lg[np.arange(n), pick]


def judge(ref, sample: List[dict], adapters: Dict[str, dict],
          ctrl=None) -> dict:
    """The widest gap over the sample (`logit_gap`), with what the sample
    covered; with `ctrl`, the control's widest gap too (`control_gap`)."""
    worst, worst_ctrl, agree, total = 0.0, 0.0, 0, 0
    for rec in sample:
        g, gc = gaps(ref, rec, adapters[rec["adapter"]], ctrl)
        worst = max(worst, float(g.max()))
        if gc is not None:
            worst_ctrl = max(worst_ctrl, float(gc.max()))
        agree += int((g == 0).sum())
        total += len(g)
    out = {"logit_gap": worst, "requests": len(sample), "tokens": total,
           "argmax_agree": agree,
           "cold": sum(r["cold"] for r in sample),
           "warm": sum(not r["cold"] for r in sample),
           "ranks": sorted({r["rank"] for r in sample})}
    if ctrl is not None:
        out["control_gap"] = worst_ctrl
    return out
