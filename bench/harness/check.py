"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the program finished, drawn from the seed and always holding
the longest of them, is run through the plain reference: one forward pass
over each prompt followed by the tokens the program served.  At every
served position the reference's logits give the gap by which the served
token's logit lies below the reference's best; the number compared is the
widest such gap.  Greedy decoding in the program's precision leaves small
gaps where the reference's top two logits nearly tie; a layer computed
wrongly, a wrong page, mask or position, or a token altered where it is
produced leaves large ones.

``control_gaps`` gives the same reading for the reference itself computed
one precision step lower (float8 with per-row scales): at each position
the token it would put first, measured by the float32 reference.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def sample(finished: list[tuple[int, int]], k: int, seed: int) -> list[int]:
    """``finished``: (rid, served tokens + prompt tokens).  The longest,
    then ``k - 1`` others drawn from the seed."""
    if not finished:
        return []
    finished = sorted(finished)
    longest = max(finished, key=lambda f: (f[1], -f[0]))[0]
    rest = [rid for rid, _ in finished if rid != longest]
    rng = np.random.default_rng([seed, 99])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + sorted(rest[i] for i in pick)


def _gaps(ref_logits, chosen) -> np.ndarray:
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)


def served_gaps(ref, w, m, prompt, served, *, control: bool = False):
    """Gaps of the served tokens (or, with ``control``, of the tokens the
    lower-precision reference puts first) at each served position."""
    toks = list(prompt) + list(served[:-1])
    read = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    logits = ref.logits_at(w, m, toks, read)
    if control:
        low = ref.logits_at(w, m, toks, read, quant="fp8")
        chosen = jnp.argmax(low, axis=-1)
    else:
        chosen = jnp.asarray(np.asarray(served, np.int32))
    return _gaps(logits, chosen)
