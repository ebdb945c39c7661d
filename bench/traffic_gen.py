"""The one traffic generator. It reads a mix file `bench/traffic/<name>.json`.

A mix file holds data only:

- "requests": {"prompt_lengths": [[length, count], ...], "new_tokens": n,
  "order": "shuffled" | "interleaved"}. Every seed gets exactly these
  counts and draws the prompt token ids (uniform over [1, vocab - 1)).
  "shuffled" (the default) lets the seed draw the order of the lengths;
  "interleaved" admits them in one fixed order, one of each length in
  turn, so that every seed gets the same schedule as well.
- "engine": the serving set-up the mix is measured under, the keyword
  arguments of the program's `TieredKVConfig` except `max_requests`, which
  is the number of requests.
- "warmup": {"min_steps": n, "until_all_scheduled": bool}: engine steps run
  in set-up, before the window; with `until_all_scheduled`, until every
  request has decoded at least one token.
"""
from __future__ import annotations

import numpy as np


def prompt_lengths(mix: dict) -> list:
    return [int(n) for n, count in mix["requests"]["prompt_lengths"]
            for _ in range(int(count))]


def interleaved(mix: dict) -> list:
    left = [[int(n), int(count)] for n, count in mix["requests"]["prompt_lengths"]]
    out = []
    while any(c for _, c in left):
        for group in left:
            if group[1]:
                out.append(group[0])
                group[1] -= 1
    return out


def requests(mix: dict, seed: int, vocab: int) -> list:
    """[(rid, prompt token list, max_new_tokens)], the same for one seed."""
    rng = np.random.default_rng([seed % 2**64, 1])
    order = mix["requests"].get("order", "shuffled")
    if order == "interleaved":
        lengths = interleaved(mix)
    elif order == "shuffled":
        lengths = rng.permutation(prompt_lengths(mix))
    else:
        raise ValueError(f"unknown order {order!r}")
    new = int(mix["requests"]["new_tokens"])
    return [(rid, rng.integers(1, vocab - 1, size=int(n)).tolist(), new)
            for rid, n in enumerate(lengths)]

