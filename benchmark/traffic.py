"""The one traffic generator. A mix is a data file
``benchmark/traffic/<name>.json``; this module reads its parameters and
makes the requests, so a new mix is a new file and no code.

Every ``--seed`` sends the SAME sizes and (open loop) the same gaps
between arrivals, drawn once from the file's own ``shape_seed``. The run's
seed only shuffles them inside consecutive blocks of ``order_block`` and
draws the token ids. A window holds a few dozen requests of a pool of
hundreds, so a free permutation would hand each seed a different sample
of the sizes, and the spread between seeds would be the draw's, not the
system's: with blocks, every seed sends the same work at about the same
time, in a locally different order.

Parameters of a serving mix (all in the file):

``loop``          "closed" (``clients`` callers, each sending its next
                  request when the last ends) or "open" (arrivals at
                  ``rate_per_s``, Poisson, timed from due times)
``pool``          how many distinct (prompt, output) sizes are drawn
``order_block``   the seed permutes sizes and gaps inside blocks of this many;
                  1 fixes the order, and the seed draws token ids only
``prompt``/``output``  {"median", "sigma", "min", "max"}: lognormal, clipped
``max_total``     prompt + output is cut to this (output shrinks first)
``sampled_every`` every n-th request of the pool samples with ``sampling``
                  (temperature, top_k); the others decode greedily
``ramp_s``        seconds of load before the window opens (part of set-up)
``edge_s``        soft edges of the window for the token rate
                  (``metrics.tapered_rate``)
``drain_s``       seconds allowed after the window for open requests
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, roots=(HERE,)) -> Dict[str, Any]:
    """The mix ``traffic/<name>.json`` from the first root that has it."""
    for root in roots:
        path = os.path.join(root, "traffic", name + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(f"no traffic/{name}.json under {list(roots)}")


def _lognormal(rng, n: int, p: Dict[str, float]) -> np.ndarray:
    x = rng.lognormal(np.log(p["median"]), p["sigma"], n)
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


def size_pool(mix: Dict[str, Any]) -> List[Dict[str, int]]:
    """The fixed multiset of request sizes of this mix (seed-independent)."""
    rng = np.random.default_rng(int(mix["shape_seed"]))
    n = int(mix["pool"])
    prompts = _lognormal(rng, n, mix["prompt"])
    outputs = _lognormal(rng, n, mix["output"])
    cap = int(mix["max_total"])
    outputs = np.minimum(outputs, np.maximum(cap - prompts,
                                             mix["output"]["min"]))
    prompts = np.minimum(prompts, cap - outputs)
    every = int(mix.get("sampled_every", 0))
    return [{"prompt_len": int(p), "output_len": int(o),
             "sampled": bool(every and i % every == every - 1)}
            for i, (p, o) in enumerate(zip(prompts, outputs))]


def gap_pool(mix: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` exponential gaps of mean 1/rate (a Poisson process), fixed by
    the mix's own seed."""
    rng = np.random.default_rng(int(mix["shape_seed"]) + 1)
    return rng.exponential(1.0 / float(mix["rate_per_s"]), n)


def block_order(n: int, block: int, rng) -> np.ndarray:
    """0..n-1 with each consecutive block of ``block`` shuffled in place."""
    order = np.arange(n)
    for a in range(0, n, block):
        rng.shuffle(order[a:a + block])
    return order


def make_requests(mix: Dict[str, Any], vocab_size: int, seed: int,
                  count: int) -> List[Dict[str, Any]]:
    """``count`` requests for this run: the size pool in the order ``seed``
    gives (cycled if ``count`` exceeds it), token ids from (seed, index),
    and for an open loop the due time of each (seconds from the start of
    load, cumulative gaps in the order ``seed`` gives)."""
    seed = int(seed)
    pool = size_pool(mix)
    block = int(mix["order_block"])
    order = block_order(len(pool), block, np.random.default_rng([seed, 0]))
    due = None
    if mix["loop"] == "open":
        gaps = gap_pool(mix, count)
        due = np.cumsum(gaps[block_order(
            count, block, np.random.default_rng([seed, 1]))])
    reqs = []
    for i in range(count):
        size = pool[int(order[i % len(pool)])]
        ids = np.random.default_rng([seed, 2, i]).integers(
            0, vocab_size, size["prompt_len"]).astype(np.int32)
        kw = {"max_new_tokens": size["output_len"], "eos_token_id": None}
        if size["sampled"]:
            kw.update(mix["sampling"], seed=(seed + i) % (2 ** 31 - 1))
        reqs.append({"index": i, "prompt": ids, "kw": kw,
                     "sampled": size["sampled"],
                     "due_s": None if due is None else float(due[i])})
    return reqs


def train_batch(vocab_size: int, batch: int, seq: int, seed: int, step: int
                ) -> np.ndarray:
    """Token ids of training step ``step``: a new batch every step."""
    return np.random.default_rng([int(seed), 3, int(step)]).integers(
        0, vocab_size, (batch, seq)).astype(np.int32)
