"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine served
(drawn from the seed, the longest always in it, and on several chips
one served by each chip's shard) is replayed through the
plain float32 reference: one forward over each prompt followed by its
served tokens.  Requests still streaming when the window closed count
with the tokens they were served: each request lasts longer than the
window, so at the cells' loads the finished ones alone can hold under a
hundred tokens.  For each served token the gap is the reference's best
logit minus the reference's logit of that token (0 where they agree).
The number compared is the widest gap over the sample.

A control puts a lower-precision copy of the reference in the program's
place: at each of the same positions the token it ranks first is read
against the float32 reference in the same way.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

Served = Tuple[List[int], List[int]]  # (prompt, served tokens)


def served(records: Sequence[Dict]) -> List[Served]:
    """Prompt and served tokens of each request the engine served a
    token to (the drive loop's records, before they let go of the
    engine's requests)."""
    return [(r["req"].prompt, list(r["req"].out)) for r in records
            if r["req"] is not None and r["req"].out]


def shards(records: Sequence[Dict]) -> Optional[List[Set[int]]]:
    """The chips whose shards served each of ``served(records)``, where
    the drive loop kept them (``None`` on one chip)."""
    out = [r.get("shards", set()) for r in records
           if r["req"] is not None and r["req"].out]
    return out if any(out) else None


def sample(reqs: Sequence[Served], seed: int, k: int,
           shards: Optional[Sequence[Set[int]]] = None) -> List[Served]:
    """``k`` served requests: the longest, and others drawn from the
    seed.  With ``shards`` (on several chips) the draw first takes one
    request of each shard that those already taken leave out, so a fault
    in one chip's rows always meets the comparison."""
    if not reqs:
        return []
    order = sorted(range(len(reqs)),
                   key=lambda i: -(len(reqs[i][0]) + len(reqs[i][1])))
    rest = order[1:]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 7])
    if shards is None:
        pick = list(rng.choice(rest, size=min(k - 1, len(rest)),
                               replace=False)) if rest else []
        return [reqs[i] for i in [order[0]] + pick]
    chosen = [order[0]]
    covered = set(shards[order[0]])
    for s in sorted(set().union(*shards) - covered):
        if s in covered:
            continue
        i = int(rng.choice([i for i in rest if s in shards[i]]))
        chosen.append(i)
        covered |= shards[i]
    left = [i for i in rest if i not in chosen]
    n = min(k - len(chosen), len(left))
    if n > 0:
        chosen += [int(i) for i in rng.choice(left, size=n, replace=False)]
    return [reqs[i] for i in chosen]


def gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Best reference logit minus the reference logit of each token."""
    rows = np.arange(len(tokens))
    return ref_logits.max(-1) - ref_logits[rows, np.asarray(tokens)]


def positions(prompt: Sequence[int], served: Sequence[int]):
    """The sequence the reference reads and the positions predicting
    each served token."""
    seq = list(prompt) + list(served[:-1])
    want = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq, want


def correct(cmp: Dict, chk: Dict, failed: int) -> bool:
    """The verdict on one side of a comparison: no request refused, at
    least ``min_tokens`` tokens compared, and the widest gap within the
    configuration's limit."""
    limit = chk["max_logit_gap"]
    return (limit is not None and failed == 0
            and cmp["tokens_compared"] >= chk["min_tokens"]
            and cmp["max_logit_gap"] <= limit)


def control_side(cmp: Dict) -> Dict:
    """The control's readings of a comparison, in the program's form."""
    return dict(cmp, max_logit_gap=cmp["control_max_logit_gap"])


def compare(ref, params, conf: Dict, chosen: Sequence[Served],
            control: Optional[Dict] = None) -> Dict:
    """Widest gap of the served tokens (and of the control's first
    choices, when ``control`` names its precision)."""
    out = {"max_logit_gap": 0.0, "tokens_compared": 0, "requests": 0}
    if control is not None:
        out["control_max_logit_gap"] = 0.0
    for prompt, served in chosen:
        if not served:
            continue
        seq, want = positions(prompt, served)
        lg = ref.logits(params, conf, seq, want)
        g = gaps(lg, served)
        out["max_logit_gap"] = max(out["max_logit_gap"], float(g.max()))
        out["tokens_compared"] += len(served)
        out["requests"] += 1
        if control is not None:
            cl = ref.logits(params, conf, seq, want, quant=control)
            cg = gaps(lg, cl.argmax(-1))
            out["control_max_logit_gap"] = max(
                out["control_max_logit_gap"], float(cg.max()))
    return out
