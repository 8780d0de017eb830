"""How ``correct`` is decided for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the longest, is run through the float32
reference over its prompt and served tokens.  Every served token was chosen
greedily, so it should be the reference's best token up to the rounding of
the served precision.  The configuration's ``correct`` names the numbers
compared: the widest gap by which a served token's reference logit lies
below the reference's best at its position, and the mean of that gap over
the served tokens.  The share of served tokens that are not the
reference's best is read too, and not compared: random weights drive a
greedy stream into a loop of a few tokens, and one near-tie that bf16
rounding flips then recurs at every turn of the loop."""
from __future__ import annotations

import numpy as np

import reference


def pick(finished, rng, min_tokens: int):
    """The longest finished request (prompt + served), then others in an
    order ``rng`` draws, until ``min_tokens`` served tokens are in."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["prompt"])
                                   + len(finished[i]["tokens"])))
    chosen, rest = [order[0]], order[1:]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    total = len(finished[order[0]]["tokens"])
    for i in rest:
        if total >= min_tokens:
            break
        chosen.append(i)
        total += len(finished[i]["tokens"])
    return [finished[i] for i in chosen]


def gaps(weights, cfg: dict, rec, quant=None):
    """Per served token: (reference best - reference logit of the token the
    server chose, or with ``quant`` the token the control puts first)."""
    prompt, served = rec["prompt"], rec["tokens"]
    seq = list(prompt) + list(served[:-1])
    at = np.arange(len(prompt) - 1, len(seq))
    ref = reference.logits(weights, cfg, seq, at)
    if quant is None:
        chosen = np.asarray(served)
    else:
        chosen = reference.logits(weights, cfg, seq, at, quant).argmax(-1)
    return ref.max(-1) - ref[np.arange(len(at)), chosen]


def summarize(all_gaps) -> dict:
    """Widest gap, mean gap, share of tokens off the reference's best, and
    the number of tokens compared, over per-request gap arrays."""
    flat = np.concatenate(all_gaps) if len(all_gaps) else np.zeros(0)
    if not flat.size:
        return {"max_logit_gap": float("inf"), "mean_logit_gap": float("inf"),
                "mismatch_share": 1.0, "tokens_compared": 0}
    return {"max_logit_gap": float(flat.max()),
            "mean_logit_gap": float(flat.mean()),
            "mismatch_share": float(np.mean(flat > 0)),
            "tokens_compared": int(flat.size)}


def compare(weights, cfg: dict, sample, quant=None) -> dict:
    """``summarize`` over the sample."""
    return summarize([gaps(weights, cfg, rec, quant) for rec in sample])
