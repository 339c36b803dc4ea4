"""What the benchmark builds for the dense configurations stays bit for
bit what it was when their cells' limits and bounds were set: the served
param tree, the reference's logits and the float8 control's, at smoke
size from one seed (bench_digest.py, in a process of its own with one
Eigen thread, so that float32 sums are split the same way every time)."""
import json
import os
import subprocess
import sys

import pytest

PINNED = {
    "chatglm3-6b-w4g128": {
        "tree": "f5150803e00a24a146c95ac1ef9e5291f662fc52b67ae3b583e7915221ef3c36",
        "logits": "7ba67deb5f1b715ccac452b406f9961ceb213ad315c66d44f772da9cacfdc8a7",
        "control": "96c27a9a29c0e17543a61605f442c100cb546c522a22ef7c46d39d119520f7c0"},
    "granite-20b-w3g128": {
        "tree": "e65c66112000052cbf258f7a44052c851f301ec5b4a407df98bbb6345938be7b",
        "logits": "caba9398ec23ba1d075e5da997d6f6b49d171c0b9ba47dd9e0dd85c715d4e045",
        "control": "f46a206e815aa46f62afb718755d05786eec7c031ffb58897df25539427cd5f4"},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_served_tree_and_reference_logits_keep_their_digests(name):
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(os.path.dirname(here)), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false",
               PYTHONPATH=os.pathsep.join(
                   [here, src] + [p for p in [os.environ.get("PYTHONPATH")]
                                  if p]))
    out = subprocess.run([sys.executable, os.path.join(here,
                                                       "bench_digest.py"),
                          name], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])[name] == \
        PINNED[name]
