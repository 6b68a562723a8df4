"""The one traffic generator: a mix file of parameters and a seed in,
requests out.

A mix (`bench/traffic/<name>.json`) names its adapter set, its prompt and
output length laws and its arrival law. Each law is a module of its own
under `bench/laws/`, found by the name the mix gives (`prompt.dist`,
`output.dist`, `arrival.kind`, `adapters.popularity`), so a mix that needs
a new law adds a file there and edits nothing here.

Every seed serves the same multiset of sizes and gaps, in another order:
lengths are the law's quantiles at (i + 0.5) / n, dealt in blocks of
`BLOCK` so that every block holds one draw from each of `BLOCK` strata
(any prefix of a backlog is then a balanced sample), and arrival gaps are
a multiset the law fixes, shuffled. So two seeds differ in order and in
prompt tokens, not in the amount of work, which keeps the spread between
seeds near the spread between two runs of one seed. The adapter a request
names follows the same rule over the popularity law.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from typing import List, Tuple

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
BLOCK = 8


@dataclasses.dataclass(frozen=True)
class Item:
    due_s: float          # when the client sends it, from the window start
    adapter: str          # adapter uid
    prompt: np.ndarray    # (L,) int32 token ids
    max_new: int          # output tokens asked for


def load(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if mix.get("name") != name:
        raise ValueError(f"{path}: 'name' must be {name!r}")
    return mix


def law(kind: str, name: str):
    """The module of law `name` of `kind` (length, arrival, popularity)."""
    module = f"bench.laws.{kind}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"no {kind} law {name!r} in bench/laws/{kind}/") \
            from e


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([seed & (2 ** 64 - 1), stream])


def adapters(mix: dict) -> List[Tuple[str, int]]:
    """(uid, rank) in popularity order: rank cycles over the mix's ranks,
    so the hot set always holds every rank in equal shares."""
    a = mix["adapters"]
    ranks = a["ranks"]
    return [(f"lora{i:03d}", int(ranks[i % len(ranks)]))
            for i in range(a["count"])]


def popularity(mix: dict) -> np.ndarray:
    """Probability of each adapter, in popularity order."""
    a = mix["adapters"]
    return law("popularity", a.get("popularity", "zipf")).weights(a)


def lengths_at(spec: dict, u: np.ndarray) -> np.ndarray:
    """The length law's values at probabilities `u`, clipped to the
    spec's range, as ints."""
    v = law("length", spec["dist"]).quantiles(spec, u)
    return np.clip(v, int(spec["min"]), int(spec["max"])).astype(np.int64)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The length law's quantiles at (i + 0.5) / n, as ints, ascending."""
    return np.sort(lengths_at(spec, (np.arange(n) + 0.5) / n))


def blocked(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Deal ascending `values` into blocks of BLOCK, one from each stratum
    per block, each stratum and each block shuffled."""
    n = len(values)
    per = -(-n // BLOCK)
    padded = np.concatenate([values, values[::-1][:per * BLOCK - n]])
    strata = padded.reshape(BLOCK, per)
    strata = np.stack([rng.permutation(s) for s in strata])
    out = [rng.permutation(strata[:, j]) for j in range(per)]
    return np.concatenate(out)[:n]


def registration_order(mix: dict, seed: int) -> List[Tuple[str, int]]:
    """The adapters in the order the server registers them: shuffled by
    the seed, so popularity is not aligned with registration order."""
    ads = adapters(mix)
    return [ads[i] for i in rng_for(seed, 15).permutation(len(ads))]


def popularity_draws(mix: dict, n: int) -> np.ndarray:
    """Adapter index (popularity order) of n requests: the popularity
    law's quantiles, ascending."""
    cdf = np.cumsum(popularity(mix))
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), len(cdf) - 1)


def count(mix: dict, seconds: float) -> int:
    """Requests in a window of `seconds`, a whole number of blocks."""
    arr = mix["arrival"]
    n = law("arrival", arr["kind"]).count(arr, seconds)
    return max(BLOCK, -(-n // BLOCK) * BLOCK)


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             stream: int = 0) -> List[Item]:
    """The mix's requests for a window of `seconds`, sorted by due time.
    `stream` picks an independent draw of the same mix (0: the measured
    window; others: warm-up)."""
    n = count(mix, seconds)
    base = 16 * stream
    plens = blocked(quantiles(mix["prompt"], n), rng_for(seed, base + 1))
    olens = blocked(quantiles(mix["output"], n), rng_for(seed, base + 2))
    picks = blocked(popularity_draws(mix, n), rng_for(seed, base + 3))
    uid_of = [uid for uid, _ in adapters(mix)]
    arr = mix["arrival"]
    due = law("arrival", arr["kind"]).due(arr, n, rng_for(seed, base + 5))
    toks = rng_for(seed, base + 6)
    items = [Item(float(due[i]), uid_of[int(picks[i])],
                  toks.integers(0, vocab, int(plens[i])).astype(np.int32),
                  int(olens[i])) for i in range(n)]
    return sorted(items, key=lambda it: it.due_s)
