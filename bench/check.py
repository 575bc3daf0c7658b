"""Whether what the timed path served is correct.

After the window, a sample of the requests the server finished, drawn from
the seed and always holding the longest of them, is run through the plain
float32 reference of the configuration's model family
(``bench.families``, ``bench/reference/``): each prompt followed by the tokens
the server gave out for it.  At every position that produced a served token
the reference gives its logits; the number compared is the widest gap by
which a served token's reference logit lies below the reference's best
logit there.  Greedy decoding that follows the model to within rounding
keeps that gap small; a wrong token, a stale cache or a corrupted matmul
opens it.

The control (``control_gap``) reads the same positions of the same
sequences with the reference computed in float8 in place of the program:
the gap of the token the float8 model puts first.
"""
from __future__ import annotations

import numpy as np

from bench import families


def sample_finished(reqs, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = sorted((r for r in reqs if r.reason in ("done", "eos") and r.tokens is not None
                   and len(r.tokens) > 0), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 0x5A3])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _arrays(sample, smax: int):
    width = max(len(r.tokens) for r in sample)
    tokens = np.zeros((len(sample), smax), np.int32)
    pos = np.zeros((len(sample), width), np.int32)
    served = np.zeros((len(sample), width), np.int32)
    mask = np.zeros((len(sample), width), bool)
    for b, r in enumerate(sample):
        seq = np.concatenate([r.prompt, r.tokens])[:smax]
        tokens[b, : len(seq)] = seq
        n = len(r.tokens)
        pos[b, :n] = len(r.prompt) - 1 + np.arange(n)
        served[b, :n] = r.tokens
        mask[b, :n] = True
    return tokens, pos, served, mask


def _gap_of(logits, best, pick, mask) -> float:
    import jax.numpy as jnp

    at = jnp.take_along_axis(logits, jnp.asarray(pick)[..., None], axis=-1)[..., 0]
    return float(np.asarray(best - at)[mask].max())


def served_gaps(config: dict, seed: int, sample, smax: int, *, control: bool = False,
                family=None) -> dict:
    """``{"max_gap": ..., "served_tokens": ...}`` of the sample against the
    reference of the configuration's family (``bench.families``, looked up
    by the file's ``family`` key where none is given), and with ``control``
    the float8 reference's ``control_gap`` at the same positions."""
    family = family or families.load(config)
    spec = family.reference_spec(config)
    tokens, pos, served, mask = _arrays(sample, smax)
    logits = family.logits_at(spec, seed, tokens, pos)
    best = logits.max(-1)
    out = {"max_gap": _gap_of(logits, best, served, mask), "served_tokens": int(mask.sum())}
    if control:
        pick = np.asarray(family.logits_at(spec, seed, tokens, pos, mode="fp8").argmax(-1))
        out["control_gap"] = _gap_of(logits, best, pick, mask)
    return out


def judge(readings: dict | None, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit, and whether all are within."""
    checks = {}
    ok = readings is not None
    for name, lim in limits["compare"].items():
        value = None if readings is None else readings.get(name)
        checks[name] = {"value": value, "limit": lim["limit"]}
        ok = ok and value is not None and value <= lim["limit"]
    return ok, checks
