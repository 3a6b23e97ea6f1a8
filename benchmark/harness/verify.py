"""Verdicts against the seeded truth and the plain reference checker."""

from __future__ import annotations

import random


def fields(wl, res: dict) -> tuple:
    """The parts of a verdict the program and the references share
    (the workload module's FIELDS), lists sorted."""
    out = []
    for k in wl.FIELDS:
        v = res.get(k)
        out.append(tuple(sorted(v)) if isinstance(v, list) else v)
    return tuple(out)


def sample(truth: dict, seed: int, n_valid: int, n_invalid: int) -> list:
    """Runs for the reference, drawn from the seed: some of the seeded
    invalid ones and some valid ones."""
    rng = random.Random(f"sample:{seed}")
    bad = sorted(n for n, t in truth.items() if t["valid?"] is not True)
    good = sorted(n for n, t in truth.items() if t["valid?"] is True)
    return sorted(rng.sample(bad, min(n_invalid, len(bad)))
                  + rng.sample(good, min(n_valid, len(good))))


def compare(wl, answers: dict, truth: dict, ref: dict) -> dict:
    """Counts of answers missing, disagreeing with the truth, and (over
    the reference's sample) disagreeing with the reference."""
    missing = sum(1 for n in truth if answers.get(n) is None)
    wrong_truth = sum(1 for n, a in answers.items()
                      if a is not None and fields(wl, a) != fields(wl, truth[n]))
    wrong_ref = sum(1 for n, r in ref.items()
                    if answers.get(n) is None
                    or fields(wl, answers[n]) != fields(wl, r))
    return {"missing": missing, "wrong_vs_truth": wrong_truth,
            "wrong_vs_reference": wrong_ref}


def report(wl, answers: dict, truth: dict, ref: dict, limit: int = 8):
    """Lines naming the first answers that disagree, for the log."""
    out = []
    for n in sorted(truth):
        a = answers.get(n)
        want = ref.get(n, truth[n])
        if a is None or fields(wl, a) != fields(wl, want):
            out.append(f"{n}: answer "
                       f"{None if a is None else fields(wl, a)}, truth "
                       f"{fields(wl, truth[n])}"
                       + (f", reference {fields(wl, ref[n])}"
                          if n in ref else ""))
    return out[:limit]
