"""`correct`: the served tokens against the plain reference.

Once the window has closed and the engine's pools are freed, a sample
drawn from the seed of the requests the run finished is run through
`reference.teacher_forced_logits` in float32. The sample always holds
the longest finished request and the longest finished one whose
offloaded layers were brought back to the device (host-to-device
`copy_blocks`), then others until `sample_tokens` served tokens. For
every served token the reference gives the gap between its best logit
and the served token's logit; the widest gap over the sample is
compared with the configuration's limit (`correct.max_logit_gap`), which
was set from the readings recorded in PERF.md. A sound bf16 program only
picks a token the float32 model ranks lower where two logits nearly tie.
"""
from __future__ import annotations

import random

import numpy as np

import reference


def sample(recs, seed: int, tokens: int, max_requests: int,
           reloaded=frozenset()):
    done = [r for r in recs if r.handle is not None and r.handle.finished
            and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.tr.rid)

    def size(r):
        return (len(r.tr.prompt) + len(r.tokens), r.tr.rid)
    out = [max(done, key=size)]
    back = [r for r in done if r.tr.rid in reloaded]
    if back and max(back, key=size) is not out[0]:
        out.append(max(back, key=size))
    rest = [r for r in done if all(r is not o for o in out)]
    random.Random(seed ^ 0x5EED).shuffle(rest)
    n = sum(len(r.tokens) for r in out)
    for r in rest:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def check(cfile: dict, dims: dict, weights, recs, seed: int,
          reloaded=frozenset(), chosen=None):
    """({name: {"value", "limit"}}, correct, what was sampled). `chosen`,
    given the float32 logits and a sampled record, names the tokens to
    judge in place of the served ones (the control's)."""
    c = cfile["correct"]
    picked = sample(recs, seed, c["sample_tokens"], c["max_requests"],
                    reloaded)
    gap = 0.0
    n = 0
    for r in picked:
        logits = reference.teacher_forced_logits(
            dims, weights, r.tr.prompt, r.tokens)
        toks = r.tokens if chosen is None else chosen(r)
        gap = max(gap, reference.widest_gap(logits, toks))
        n += len(r.tokens)
    back = [r for r in picked if r.tr.rid in reloaded]
    sampled = {"requests": len(picked), "tokens": n,
               "reloaded_requests": len(back),
               "reloaded_tokens": sum(len(r.tokens) for r in back),
               "reloaded_in_run": len(reloaded)}
    checks = {
        "logit_gap": {"value": gap, "limit": c["max_logit_gap"]},
        "tokens_compared": {"value": n, "limit": c["min_tokens_compared"]},
    }
    ok = bool(np.isfinite(gap)) and gap <= c["max_logit_gap"] \
        and n >= c["min_tokens_compared"]
    return checks, ok, sampled
