"""The one traffic generator: a mix file (``bench/traffic/<name>.json``)
plus a seed give the requests of a run.

Every seed gets the same set of sizes and gaps, in its own order: prompt
and output lengths are the quantiles (i + 1/2) / n of the mix's clipped
lognormal laws, and open-loop gaps the same quantiles of the exponential
law of the mix's rate.  The seed orders each set and draws the token
ids, so runs differ in which request is which and not in how much work
there is.  With ``block_requests`` k in the mix, every block of k
consecutive requests also gets the same share of each set: the sorted set
is cut into k strata, and each block takes one value from every stratum
(which one, and in what order, drawn from the seed).  So each block holds
the same number of arrivals over about the same time and about the same
work, and a seed cannot bunch the long prompts or the short gaps of the
whole run into one stretch of it.

An open loop (``"loop": "open"``) sends request i at its due time whether
or not earlier ones finished.  A closed loop (``"loop": "closed"``) runs
one client per engine slot; each sends its next request when its last one
finished, and the clients start one by one over the ramp.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_traffic(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic file {path}")
    t = json.loads(path.read_text())
    t["name"] = name
    return t


@dataclasses.dataclass(frozen=True)
class Plan:
    """The requests of one run, in the order they are sent."""

    prompt_lens: np.ndarray      # (n,)
    output_lens: np.ndarray      # (n,)
    due: np.ndarray | None       # (n,) seconds after the traffic starts (open)
    client_starts: np.ndarray | None  # (clients,) start offsets (closed)
    seed: int
    vocab: int

    def __len__(self) -> int:
        return len(self.prompt_lens)

    def prompt(self, i: int) -> tuple[int, ...]:
        """Request i's token ids, uniform over the vocabulary."""
        rng = np.random.default_rng([self.seed % 2 ** 64, i])
        return tuple(int(t) for t in rng.integers(0, self.vocab,
                                                  int(self.prompt_lens[i])))


def lognormal_quantiles(law: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 1/2) / n of a lognormal with the
    given median and sigma, clipped to [min, max] and rounded."""
    if law["law"] != "lognormal":
        raise ValueError(f"unknown length law {law['law']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = law["median"] * np.exp(law["sigma"] * z)
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def request_count(t: dict, seconds: float, slots: int) -> int:
    """How many requests a run of ``seconds`` after the ramp draws: an
    open loop's arrivals with room to spare; a closed loop's set, which
    its clients go through as often as they need."""
    if t["loop"] == "open":
        span = t["ramp_seconds"] + seconds
        n = int(math.ceil(t["rate_per_s"] * span * 1.25)) + 16
    else:
        n = 8 * slots
    k = int(t.get("block_requests", 1))
    return -(-n // k) * k


def spread_over_blocks(values: np.ndarray, k: int,
                       rng: np.random.Generator) -> np.ndarray:
    """``values`` (a multiple of k of them) in an order drawn from ``rng``
    in which every block of k consecutive entries holds one value of each
    of the k strata of the sorted values.  k = 1 is a plain permutation."""
    v = np.sort(values).reshape(k, -1)           # stratum j: row j
    picked = np.stack([rng.permutation(row) for row in v], axis=1)
    return np.stack([rng.permutation(b) for b in picked]).reshape(-1)


def make_plan(t: dict, seed: int, seconds: float, slots: int,
              vocab: int) -> Plan:
    n = request_count(t, seconds, slots)
    k = int(t.get("block_requests", 1))
    rng = np.random.default_rng(seed % 2 ** 64)
    prompts = spread_over_blocks(lognormal_quantiles(t["prompt"], n), k, rng)
    outputs = spread_over_blocks(lognormal_quantiles(t["output"], n), k, rng)
    due = starts = None
    if t["loop"] == "open":
        q = (np.arange(n) + 0.5) / n
        gaps = spread_over_blocks(-np.log1p(-q) / t["rate_per_s"], k, rng)
        due = np.cumsum(gaps) - gaps[0]
    elif t["loop"] == "closed":
        starts = np.arange(slots) * (t["ramp_seconds"] / slots)
    else:
        raise ValueError(f"unknown loop {t['loop']!r}")
    return Plan(prompts, outputs, due, starts, seed, vocab)
