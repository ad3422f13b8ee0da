"""The requests of a ``backlog`` traffic mix (``chipbench/traffic/<name>.json``).

A backlog is every request queued before the window opens, the way an
offline batch job hands a server its work.  The mix's file gives:

* ``requests``, ``particles``: how many requests, and the SMC population
  of each (its particle sequences);
* ``prompt_tokens`` ``[lo, hi]``: a prompt's length is drawn
  log-uniformly in this range and rounded up to the smallest of
  ``prompt_buckets``, so the prefill sees few shapes;
* ``steps``: the tokens each request generates (the job's
  ``max_new_tokens``; SMC decoding runs every request to it).

Prompt tokens are uniform over the model's vocabulary.  Everything comes
from ``--seed`` through :mod:`chipbench.traffic`'s streams.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

from chipbench import traffic as traffic_lib

SIZE_STREAM, TOKEN_STREAM = 1, 2


@dataclasses.dataclass(frozen=True)
class Request:
    rid: str
    prompt: np.ndarray  # [plen] int32
    particles: int
    steps: int


def prompt_lengths(mix: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can draw (its prefill shapes)."""
    lo, hi = mix["prompt_tokens"]
    buckets = sorted(int(b) for b in mix["prompt_buckets"])
    used = [b for b in buckets if b >= lo]
    return used[: next(i for i, b in enumerate(used) if b >= hi) + 1]


def requests(mix: Dict[str, Any], seed: int, vocab: int) -> List[Request]:
    if mix["kind"] != "backlog":
        raise ValueError(f"not a backlog mix: {mix['kind']!r}")
    n = int(mix["requests"])
    lo, hi = (float(x) for x in mix["prompt_tokens"])
    buckets = np.asarray(prompt_lengths(mix))
    sizes = traffic_lib.rng(seed, SIZE_STREAM)
    drawn = np.exp(sizes.uniform(np.log(lo), np.log(hi), n))
    plens = buckets[np.searchsorted(buckets, np.ceil(drawn))]
    tokens = traffic_lib.rng(seed, TOKEN_STREAM)
    return [
        Request(rid=f"r{i:03d}",
                prompt=tokens.integers(0, vocab, int(plens[i])).astype(np.int32),
                particles=int(mix["particles"]), steps=int(mix["steps"]))
        for i in range(n)
    ]
