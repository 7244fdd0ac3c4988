"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one with
the longest prompt, is run through the configuration's plain reference:
each prompt with its served tokens, in one causal pass.  At every position
that produced a served token the reference's best logit is compared with
the reference's logit of the served token.  The compared number is the
widest such gap, in units of the standard deviation of the reference's
logits at that position (so it reads alike across model widths).  Greedy
decoding serves the argmax; a sound bf16 program trails the reference's
best only at near-ties, by rounding.

The control (``--control 1``, for setting limits only) puts the reference
in the program's place at one precision below the configuration's (float8
matmuls for bf16): the tokens it puts first at the same positions take the
served tokens' place in the same comparison, so a control run reads as not
correct; the program's own gap is kept beside it.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from pbench.manifest import load_reference


def sample(finished: Sequence, k: int, seed: int) -> List:
    """``k`` finished requests: the one with the longest prompt and
    ``k - 1`` others drawn from the seed."""
    if not finished:
        return []
    reqs = sorted(finished, key=lambda r: r.rid)
    longest = max(reqs, key=lambda r: (len(r.prompt), -r.rid))
    rest = [r for r in reqs if r is not longest]
    rng = np.random.default_rng([abs(int(seed)), 99])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _gaps(ref_logits, tokens, mask):
    """Per position: (best - logit of ``tokens``) / std of the logits."""
    import jax.numpy as jnp
    R = ref_logits
    best = jnp.max(R, axis=-1)
    got = jnp.take_along_axis(R, jnp.asarray(tokens)[..., None],
                              axis=-1)[..., 0]
    g = (best - got) / jnp.std(R, axis=-1)
    return np.where(mask, np.asarray(g), 0.0)


def served_gaps(cell, seed: int, reqs: Sequence, control: bool = False
                ) -> Dict[str, float]:
    """The widest gap of the served tokens over ``reqs``, and with
    ``control`` that of the float8 reference's own first choices."""
    import jax.numpy as jnp
    ref = load_reference(cell)
    model = cell.config["model"]
    seqs, rows = [], []
    for r in reqs:
        n = len(r.prompt)
        seqs.append(np.concatenate([r.prompt, np.asarray(r.output[:-1],
                                                         np.int32)]))
        rows.append(list(range(n - 1, n - 1 + len(r.output))))
    t_max = max(len(rw) for rw in rows)
    served = np.zeros((len(reqs), t_max), np.int32)
    mask = np.zeros((len(reqs), t_max), bool)
    for b, r in enumerate(reqs):
        served[b, :len(r.output)] = r.output
        mask[b, :len(r.output)] = True
    s_max = int(cell.config["server"]["max_seq"])
    R = ref.logits(model, seed, seqs, rows, s_max)
    out = {"logit_gap": float(_gaps(R, served, mask).max()),
           "positions": int(mask.sum())}
    if control:
        C = ref.logits(model, seed, seqs, rows, s_max, fp8=True)
        first = np.asarray(jnp.argmax(C, axis=-1))
        del C
        out["control_logit_gap"] = float(_gaps(R, first, mask).max())
        out["control_differs"] = int(((first != served) & mask).sum())
    return out


def verdict(cell, out, seed: int, control: bool = False) -> (bool, Dict,
                                                             Dict):
    """``correct``, the numbers compared with their limits, and what else
    the check read."""
    limits = cell.config["check"]
    max_new = int(cell.config["server"]["max_new_tokens"])
    vocab = int(cell.config["model"]["vocab_size"])
    malformed = sum(1 for r in out.finished
                    if len(r.output) != max_new
                    or not all(0 <= t < vocab for t in r.output))
    reqs = sample(out.finished, int(cell.config["check_requests"]), seed)
    info: Dict = {"sampled": len(reqs)}
    checks: Dict = {
        "unserved": {"value": out.failed, "limit": 0},
        "malformed": {"value": malformed, "limit": 0},
    }
    if reqs:
        got = served_gaps(cell, seed, reqs, control)
        gap = got.pop("logit_gap")
        if control:
            info["program_logit_gap"] = gap
            gap = got.pop("control_logit_gap")
        checks["logit_gap"] = {"value": gap, "limit": limits["logit_gap"]}
        info.update(got)
    else:
        checks["logit_gap"] = {"value": None, "limit": limits["logit_gap"]}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks, info
