"""Whether what the window served is correct.

The served tokens of a sample of finished requests, drawn from the
seed with the longest answer of each served rung in it, are scored by
the plain reference of the configuration's architecture (its module's
``score``, found by ``arch.load``): each request alone, its prompt
and its served tokens, with the weights of the rung that served it.  At
every served position the number read is the gap by which the served
token's reference logit lies below the reference's best; a rung's
numbers are the widest gap over its sample and the mean gap.  The
configuration's ``limits`` say which of them each cell compares.  Exact
checks go beside it: every token stamped once, every token a vocabulary
id, every answer as long as asked, and every switch a ledgered adjacent
move of exactly bytes(delta_k) as computed from the architecture's
quantized leaves.

The control puts the reference at a lower precision (the
configuration's ``control_bits`` per rung, plain round-to-nearest) in
the program's place: at each position of the same rows it reads the gap
of the token that the lower precision puts first, and is judged by the
same limits.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import arch
import shapes


def sample(w, seed: int, per_rung: int) -> Dict[int, List[Dict]]:
    """Per served rung: the request with the longest answer and
    ``per_rung - 1`` others drawn from the seed."""
    rng = np.random.default_rng([seed, 7])
    by_rung: Dict[int, List[Dict]] = {}
    for r in w.requests:
        if r["rung"] is not None and r["req"].out_tokens:
            by_rung.setdefault(r["rung"], []).append(r)
    out = {}
    for rung, rs in sorted(by_rung.items()):
        longest = max(range(len(rs)), key=lambda i: len(rs[i]["req"].out_tokens))
        rest = [i for i in range(len(rs)) if i != longest]
        pick = rng.choice(rest, size=min(per_rung - 1, len(rest)),
                          replace=False) if rest else []
        out[rung] = [rs[longest]] + [rs[int(i)] for i in pick]
    return out


def row(r: Dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(tokens, positions, targets) of one served request on its own: its
    prompt, then its served tokens, each predicted from all before it."""
    req = r["req"]
    out = np.asarray(req.out_tokens, np.int32)
    toks = np.concatenate([np.asarray(req.prompt, np.int32), out[:-1]])
    S = len(req.prompt)
    return toks, np.arange(S - 1, S - 1 + len(out)), out[:, None]


def request_faults(r: Dict, s) -> Dict[str, bool]:
    """What is wrong with one served request's answer, checked exactly."""
    out = r["req"].out_tokens
    return {"stamp_mismatch": len(out.stamps) != len(out),
            "bad_token_ids": any(not 0 <= t < s.vocab for t in out),
            "short_answers": len(out) != r["req"].max_new_tokens}


def exact_failures(r: Dict, s) -> int:
    return int(any(request_faults(r, s).values()))


def exact(w, config: Dict) -> Dict[str, int]:
    """Counts that must be 0: served requests with each fault, and
    switches that were not an adjacent move of bytes(delta_k)."""
    a = arch.load(config)
    s = a.sizes(config)
    leaves, bits = a.quantized_leaves(s), config["quant_bits"]
    counts = {"stamp_mismatch": 0, "bad_token_ids": 0, "short_answers": 0}
    for r in w.requests:
        if r["rung"] is not None:
            for k, bad in request_faults(r, s).items():
                counts[k] += bad
    moves = 0
    for sw in w.switches:
        for frm, to, pin, pout in sw["events"]:
            want = shapes.delta_bytes(leaves, bits, min(frm, to))
            moved, other = (pin, pout) if to > frm else (pout, pin)
            moves += abs(to - frm) != 1 or moved != want or other != 0
        moves += sw["rung_after"] != sw["to"]
    return {**counts, "bad_switches": moves}


def numbers(w, seed: int, config: Dict, per_rung: int,
            control: bool = False) -> Dict[str, float]:
    """Per served rung r: ``gap_r<r>``, the widest gap of a served token
    below the reference's best, ``mean_gap_r<r>``, its mean over the
    served positions, and ``tokens_r<r>``, how many there are.  With
    ``control`` the tokens read are the control's instead."""
    if config["quant_rounding"] != "rtn":
        raise ValueError("the reference nests with round-to-nearest codes; "
                         f"the configuration states {config['quant_rounding']}")
    a = arch.load(config)
    s = a.sizes(config)
    bits = tuple(sorted(config["quant_bits"]))

    def ref(rows, quant):
        return a.score(rows, s, config["weights"], config["weight_seed"],
                       quant)
    out: Dict[str, float] = {}
    for rung, rs in sample(w, seed, per_rung).items():
        rows = [row(r) for r in rs]
        if control:
            low = ref(rows, quant=((config["control_bits"][rung],), 0))
            rows = [(t, p, c["arg"][:, None]) for (t, p, _), c in
                    zip(rows, low)]
        scored = ref(rows, quant=(bits, rung))
        gaps = np.concatenate([x["top"] - x["tgt"][:, 0] for x in scored])
        out[f"gap_r{rung}"] = float(gaps.max())
        out[f"mean_gap_r{rung}"] = float(gaps.mean())
        out[f"tokens_r{rung}"] = len(gaps)
    return out


def judge(w, seed: int, config: Dict, per_rung: int,
          control: bool = False) -> Dict[str, Dict]:
    """Every number compared, with its limit: the exact counts (limit 0)
    and the numbers the configuration's ``limits`` name."""
    return compare(w, config, numbers(w, seed, config, per_rung, control))


def compare(w, config: Dict, got: Dict[str, float]) -> Dict[str, Dict]:
    """The exact counts (limit 0) and the ``limits`` of ``got``."""
    checks = {k: {"value": v, "limit": 0}
              for k, v in exact(w, config).items()}
    for name, limit in sorted(config["limits"].items()):
        if name in got:
            checks[name] = {"value": got[name], "limit": limit}
    return checks


def passed(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
