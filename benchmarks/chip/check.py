"""The comparison that decides ``correct``.

After the window, a sample of the requests the window finished, drawn
from the seed and always holding the longest, is run once through the
float32 reference of the configuration's family (``families/``) over
its prompt and its served tokens. For each
served token the reading is how far its reference logit lies below the
reference's best logit at that position; the number compared is the
widest such gap over the sample (``max_logit_gap``). Greedy tokens of a
correct bfloat16 engine trail the best only where two logits are within
rounding of each other.

The control puts the reference computed in float8 in the engine's
place: at each position of the same sequences it takes the token the
float8 logits put first and reads that token's gap in the float32
logits (``control_gap``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmarks.chip import families, traffic


def sample(done: Sequence[Tuple[List[int], List[int]]], seed: int,
           min_tokens: int) -> List[int]:
    """Indices into ``done`` ((prompt, served) pairs): the longest
    request, then requests drawn from the seed until the sample holds
    ``min_tokens`` served tokens."""
    lengths = [len(p) + len(o) for p, o in done]
    first = int(np.argmax(lengths))
    picked, n_tok = [first], len(done[first][1])
    rng = traffic.rng_for(seed, traffic.SAMPLE)
    for i in rng.permutation(len(done)):
        if n_tok >= min_tokens:
            break
        if int(i) != first:
            picked.append(int(i))
            n_tok += len(done[int(i)][1])
    return picked


def _rows(prompt: List[int], served: List[int]):
    """Sequence fed to the reference and the positions whose logits
    chose each served token: token j was chosen at position P - 1 + j."""
    seq = list(prompt) + list(served[:-1])
    return seq, np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))


def _gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    best = ref.max(axis=-1)
    return best - ref[np.arange(len(chosen)), chosen]


def readings(cfg: dict, seed: int, seqs, *, pad_to: int,
             control: bool = False) -> Dict[str, float]:
    """``max_logit_gap`` of the served tokens of ``seqs`` ((prompt,
    served) pairs) and, with ``control``, the float8 control's
    ``control_gap`` on the same positions."""
    ref_logits = families.of(cfg).logits
    out = {"max_logit_gap": 0.0, "tokens": 0}
    if control:
        out["control_gap"] = 0.0
    for prompt, served in seqs:
        seq, rows = _rows(prompt, served)
        ref = ref_logits(cfg, seed, seq, rows, pad_to=pad_to)
        g = _gaps(ref, np.asarray(served))
        out["max_logit_gap"] = max(out["max_logit_gap"], float(g.max()))
        out["tokens"] += len(served)
        if control:
            low = ref_logits(cfg, seed, seq, rows, pad_to=pad_to,
                             quant="fp8")
            gc = _gaps(ref, low.argmax(axis=-1))
            out["control_gap"] = max(out["control_gap"], float(gc.max()))
    return out
