"""Digests of what the benchmark builds for a configuration at smoke
size: the served param tree and the reference's logits (and its float8
control's) at a few positions, all from one seed.

    python bench/tests/bench_digest.py <configuration> ...

prints one JSON object, configuration -> {"tree", "logits", "control"}.
Float32 sums on XLA's CPU backend depend on how a matmul is split over
threads, so test_bench_digests.py runs this with one Eigen thread."""
import hashlib
import json
import sys

import numpy as np

from bench_smoke import smoke
import weights

SEED = 2**33 + 7


def _hash(arrays, head: str = "") -> str:
    h = hashlib.sha256(head.encode())
    for x in arrays:
        x = np.asarray(x)
        h.update(f"{x.dtype}{x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


def digests(name: str) -> dict:
    import jax

    _, ref, a, cfg = smoke(name)
    leaves, treedef = jax.tree_util.tree_flatten(
        weights.build_served(ref, a, cfg, SEED))
    toks = np.random.default_rng(5).integers(0, a.vocab, (2, 40))
    toks = toks.astype(np.int32)
    rows, cols = np.array([0, 0, 1, 1, 1]), np.array([3, 39, 0, 17, 39])
    return {"tree": _hash(leaves, str(treedef)),
            "logits": _hash([ref.logits(a, SEED, toks, rows, cols)]),
            "control": _hash([ref.logits(a, SEED, toks, rows, cols,
                                         dtype="float8_e4m3fn")])}


if __name__ == "__main__":
    print(json.dumps({n: digests(n) for n in sys.argv[1:]}))
