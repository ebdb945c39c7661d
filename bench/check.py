"""The comparison that decides `correct`: served tokens against the plain
reference.

For each compared request the reference runs once over its prompt and the
tokens the timed path served, and reads, at every served token, by how much
that token's logit lies below the reference's best logit there (the gap).
The number compared is the widest gap over all compared tokens; the cell's
limit file, `bench/limits/<cell>.json`, holds its limit and the readings the
limit was set from.

The control puts the reference in the program's place at the precision step
below the configuration's (float8 for bfloat16): at each position of the
same sequences it reads the gap of the token the control ranks first.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from bench import refs
from bench.weights import make


def gaps(root: Path, config: dict, seed: int, served: dict,
         *, control: bool = False) -> dict:
    """{rid: widest gap of that request's served tokens}. `served` maps rid
    to (prompt, out). With `control`, the gap of the control's first-ranked
    token at each position instead of the served one."""
    import jax.numpy as jnp

    ref = refs.load(root, config)
    params = make(config, seed, root=root)
    out = {}
    for rid, (prompt, toks) in sorted(served.items()):
        logits = ref.served_logits(config, params, prompt, toks)
        best = jnp.max(logits, axis=-1)
        if control:
            pick = jnp.argmax(ref.served_logits(config, params, prompt, toks,
                                                control=True), axis=-1)
        else:
            pick = jnp.asarray(toks, jnp.int32)
        chosen = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
        out[rid] = float(np.max(np.asarray(best - chosen)))
    del params
    return out
