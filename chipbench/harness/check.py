"""Is what the timed path served correct?

Once the window has closed and the program's state is freed, a sample
drawn from the seed of the window's finished requests, the longest among
them, is scored by the configuration's plain reference: one float32
forward pass over each prompt and the tokens served for it.  The number
compared is the widest gap by which a served token's logit lies below
the reference's best logit at that position, over every token of the
sample.  Serving is greedy, so a served token is the reference's best up
to rounding; a gap wider than the configuration's limit is a wrong token.

The first served token of a request comes from its last prefill chunk
and the rest from fused decode blocks through the paged KV cache, so the
sample covers chunked prefill across several chunks and decode across
several blocks, at the slots, lengths and batch the window ran.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from . import spec

PAD_STEP = 1024


def sample(recs: Sequence, seed: int, k: int) -> List:
    """The longest finished request of the window and ``k - 1`` others
    drawn from the seed."""
    done = [r for r in recs if r.in_window and r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.n_tokens, -r.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(config: Dict, seed: int, picked: Sequence, served: Dict,
            pad_to: int, control: bool = False) -> Dict:
    """Widest logit gap of the served tokens (and of the control's
    tokens) over the picked requests.  A request that names a LoRA tenant
    passes it to the reference as ``adapter_id``; a reference without
    adapters refuses it."""
    ref = spec.reference(config)
    gap = ctl = 0.0
    n = 0
    for r in picked:
        prompt = np.asarray(r.prompt, np.int32)
        toks = np.asarray(served[r.idx], np.int32)
        seq = np.concatenate([prompt, toks[:-1]])
        # one compiled shape per 1024 positions, cached across runs
        pad = min(pad_to, -(-len(seq) // PAD_STEP) * PAD_STEP)
        tokens = np.zeros(pad, np.int32)
        tokens[:len(seq)] = seq
        targets = np.full(pad, -1, np.int32)
        rows = np.arange(len(prompt) - 1, len(seq))
        targets[rows] = toks
        extra = {} if r.adapter_id is None else {"adapter_id": r.adapter_id}
        sc = ref.score(config["arch"], seed, tokens, targets, control,
                       **extra)
        g = ref.gaps(sc, rows)
        gap = max(gap, g[0])
        if control:
            ctl = max(ctl, g[1])
        n += len(rows)
    out = {"logit_gap": gap, "tokens": n, "requests": len(picked)}
    if control:
        out["control_logit_gap"] = ctl
    return out
