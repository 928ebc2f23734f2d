"""The one general generator.  A traffic mix is a data file of parameters
(benchmark/traffic/<mix>.json); nothing here knows a mix by name."""

from __future__ import annotations

import math

import numpy as np


def fold_seed(seed: int) -> int:
    """--seed may pass 2**31; numpy's and the program's generators take 32
    bits."""
    return int(seed) % (2 ** 31 - 1)


def draw_lengths(rng, spec: dict, n: int) -> list:
    """n whole numbers from {"dist": "fixed", "value": v},
    {"dist": "table", "values": [...], "weights": [...]} (any measured
    histogram of lengths, as data; weights default to equal) or
    {"dist": "uniform"|"loguniform", "lo": a, "hi": b}."""
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["value"])] * n
    if dist == "table":
        values = [int(v) for v in spec["values"]]
        w = np.asarray(spec.get("weights", [1.0] * len(values)), float)
        return [values[i] for i in
                rng.choice(len(values), size=n, p=w / w.sum())]
    if dist not in ("uniform", "loguniform"):
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if dist == "uniform":
        return [int(v) for v in rng.randint(lo, hi + 1, size=n)]
    vals = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), size=n))
    return [int(min(max(int(v), lo), hi)) for v in vals]


def serve_requests(mix: dict, vocab: int, seed: int) -> list:
    """[(prompt token ids, tokens to generate)] of one pass.  The set of
    (prompt, output) lengths is the mix's, drawn once from its
    distributions by its `shape_seed`, so that every --seed does the same
    work; --seed gives their order, the ids, and the shared prefix if the
    mix has one."""
    n = int(mix["requests"])
    shape_rng = np.random.RandomState(fold_seed(mix["shape_seed"]))
    prompt_lens = draw_lengths(shape_rng, mix["prompt_len"], n)
    output_lens = draw_lengths(shape_rng, mix["output_len"], n)
    rng = np.random.RandomState(fold_seed(seed))
    order = rng.permutation(n)
    prompt_lens = [prompt_lens[i] for i in order]
    output_lens = [output_lens[i] for i in order]
    shared = int(mix.get("shared_prefix_tokens", 0))
    prefix = [int(t) for t in rng.randint(1, vocab, size=shared)]
    out = []
    for p, o in zip(prompt_lens, output_lens):
        own = [int(t) for t in rng.randint(1, vocab, size=max(p - shared, 0))]
        out.append(((prefix + own)[:p], o))
    return out


def longest_context(mix: dict) -> int:
    def top(spec):
        if spec["dist"] == "fixed":
            return int(spec["value"])
        if spec["dist"] == "table":
            return max(int(v) for v in spec["values"])
        return int(spec["hi"])

    return top(mix["prompt_len"]) + top(mix["output_len"])
