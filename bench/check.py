"""Output check of a served model against its plain reference.

After the window, a sample of the requests the window served, drawn
from the seed and always holding the longest, is fed to the reference
once: each prompt followed by the tokens the program served. At every
position where the program chose a token, the reference gives the
logits of that choice. Two readings of the difference

    best reference logit - reference logit of the served token

which is 0 where greedy decoding agrees with the reference (the
configuration's reference module, its `logits`): its largest
value over the served tokens (`gap_max`) and its mean over them
(`gap_mean`). A cell's workload file names the one it compares
(`compare`) and its limit; both are printed. The control reads the same
positions with the reference computed in a lower precision put in the
program's place, taking the token that the control ranks first.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# sequences are padded to a multiple of this many tokens, so the
# reference compiles for a few lengths only
PAD = 512


@dataclasses.dataclass
class Served:
    prompt: np.ndarray      # int32 prompt ids
    tokens: list            # ids the program served, in order


def sample(served: list[Served], seed: int, n: int) -> list[Served]:
    """The longest request and n - 1 others drawn from the seed."""
    order = sorted(range(len(served)),
                   key=lambda i: -(len(served[i].prompt)
                                   + len(served[i].tokens)))
    if not order:
        return []
    rest = np.random.default_rng(int(seed) + 1).permutation(order[1:])
    return [served[i] for i in [order[0]] + list(rest[:n - 1])]


def _batch(reqs: list[Served]):
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in reqs]
    s = -(-max(len(x) for x in seqs) // PAD) * PAD
    toks = np.zeros((len(seqs), s), np.int32)
    rows, cols, served = [], [], []
    for i, (r, x) in enumerate(zip(reqs, seqs)):
        toks[i, :len(x)] = x
        p = len(r.prompt)
        rows += [i] * len(r.tokens)
        cols += list(range(p - 1, p - 1 + len(r.tokens)))
        served += list(r.tokens)
    return toks, np.asarray(rows), np.asarray(cols), np.asarray(served)


@jax.jit
def _gaps(ref_logits, chosen):
    best = jnp.max(ref_logits, axis=-1)
    picked = jnp.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]
    return best - picked


def served_gaps(ref, a, seed: int, reqs: list[Served],
                control: Optional[str] = None) -> dict:
    """Readings of the served tokens of `reqs` against the reference
    module `ref` of the model `a`, and with `control` (a dtype name)
    those of the control's first choices under `control_` names. With no
    request to compare, no position is read (the position check fails)."""
    if not reqs:
        return {"positions": 0, "gap_max": 0.0, "gap_mean": 0.0}
    toks, rows, cols, served = _batch(reqs)
    ref_logits = ref.logits(a, seed, toks, rows, cols)
    out = {"positions": int(len(served))}
    out["gap_max"], out["gap_mean"] = _read(ref_logits,
                                             jnp.asarray(served))
    if control is not None:
        ctl = ref.logits(a, seed, toks, rows, cols, dtype=control)
        out["control_gap_max"], out["control_gap_mean"] = _read(
            ref_logits, jnp.argmax(ctl, axis=-1).astype(jnp.int32))
    return out


def _read(ref, chosen) -> tuple[float, float]:
    g = _gaps(ref, chosen)
    return float(jnp.max(g)), float(jnp.mean(g))
