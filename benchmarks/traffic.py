"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix of kind ``train`` says what a step's batch is. A mix of kind ``serve``
is an open-loop arrival schedule: when each request is due, how long its
prompt is, how many tokens it asks for. Sizes and the gaps between arrivals
are the quantiles of the mix's distributions on an even grid (stratified), so
every seed offers the same multiset of requests and gaps inside the window;
``seed`` draws the order of the sizes, the order of the gaps and the token
ids. Runs of one mix therefore differ in which request meets which, never in
the amount of work offered.

The arrival arithmetic (exponential gaps summed into due times, each request
timed from when it was due) is a copy of ``tools/bench_serve.py``'s
``_LoadGenerator.run_phase``; the original stays where it is.
"""

from __future__ import annotations

import json
import math
import os
import random
import statistics
from typing import List, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("train", "serve"):
        raise ValueError(f"traffic mix {name!r}: kind must be train or serve")
    return mix


# -- sizes -------------------------------------------------------------------


def _quantile(dist: dict, u: float) -> float:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    z = statistics.NormalDist().inv_cdf(u)
    x = dist["median"] * math.exp(dist["sigma"] * z)
    return min(max(x, dist["min"]), dist["max"])


def stratified(dist: dict, n: int) -> List[int]:
    """n whole-number sizes: the quantiles (i + 1/2)/n of ``dist``."""
    return [int(round(_quantile(dist, (j + 0.5) / n))) for j in range(n)]


# -- serve schedules -----------------------------------------------------------


class Request(NamedTuple):
    due_s: float            # relative to the window's start; negative = lead-in
    prompt: List[int]       # token ids over the whole vocabulary
    max_tokens: int


def _segment(mix: dict, start: float, span: float, rng: random.Random):
    """``rate x span`` arrivals filling [start, start + span): [(due_s,
    prompt_len, max_tokens)]. The sizes and the exponential gaps are the
    stratified quantiles, so the segment's multiset is the mix's own whatever
    the seed; ``rng`` draws the order of each. The gaps are scaled to fill
    the span exactly; the first arrival opens the segment."""
    n = int(round(float(mix["arrival"]["rate_per_s"]) * span))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (j + 0.5) / n) for j in range(n)]
    scale = span / sum(gaps)
    prompts = stratified(mix["prompt_tokens"], n)
    outs = stratified(mix["max_tokens"], n)
    for seq in (gaps, prompts, outs):
        rng.shuffle(seq)
    events, t = [], start
    for g, n_prompt, n_out in zip(gaps, prompts, outs):
        events.append((t, n_prompt, n_out))
        t += g * scale
    return events


def serve_schedule(mix: dict, seed: int, seconds: float,
                   vocab_size: int) -> List[Request]:
    """Requests due in [-lead_s, seconds + tail), in due order: the lead-in
    (load already running when the window opens), the window, and for a mix
    that drains (``end: drain``) ``drain_s`` more seconds of arrivals, because
    an open loop does not stop offering when a measurement ends and the
    window's last requests must not have the replica to themselves. Each of
    the three is a segment of its own, so the window holds the same multiset
    for every seed."""
    rng = random.Random(seed)
    lead = float(mix.get("lead_s", 0.0))
    tail = float(mix.get("drain_s", 0.0)) if mix.get("end") == "drain" else 0.0
    events = (_segment(mix, -lead, lead, rng) + _segment(mix, 0.0, seconds, rng)
              + _segment(mix, seconds, tail, rng))
    return [Request(t, [rng.randrange(vocab_size) for _ in range(n_prompt)], n_out)
            for t, n_prompt, n_out in events]


def serve_prefill_buckets(mix: dict, bucket_min: int, max_model_len: int
                          ) -> List[int]:
    """The prefill buckets this mix can reach (powers of two from
    ``bucket_min``, as the engine pads), and no others."""
    buckets = set()
    for n in stratified(mix["prompt_tokens"], 512):
        b = bucket_min
        while b < n:
            b *= 2
        buckets.add(min(b, max_model_len))
    return sorted(buckets)


def serve_warmup_lengths(mix: dict, bucket_min: int, max_model_len: int
                         ) -> List[int]:
    """One prompt length per prefill bucket this mix can reach."""
    return [min(b, max_model_len - 1) - 8
            for b in serve_prefill_buckets(mix, bucket_min, max_model_len)]


# -- train batches -----------------------------------------------------------


def train_shape(mix: dict, job: dict) -> dict:
    """The step's shape: the mix gives the sequence length and may override
    the configuration's per-chip batch."""
    return {"seq_len": int(mix["seq_len"]),
            "per_chip_batch": int(mix.get("per_chip_batch",
                                          job["per_chip_batch"]))}
