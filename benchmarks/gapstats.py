"""Statistics of the served tokens' gaps, and the rule that decides ``correct``.

A gap is how far a served token's reference logit lies below the reference's
best at its position, in standard deviations of that position's logits
(the family's ``compare``). Standard library only: ``run.py`` decides with it.

A rule is data: ``benchmarks/correct/<cell>.json`` where a cell has one of its
own, else the ``correct`` group of the cell's configuration. Each entry names
a statistic of the gaps and its limit:

    {"of": "share_of", "against": "control", "where_zero": "witness", "cap": 0.3,
     "limit": 0.5, "unit": "ratio"}
    {"of": "quantile", "q": 0.9, "limit": 0.05, "unit": "spreads"}
    {"of": "max", "limit": 0.3, "unit": "spreads"}
    {"of": "count_over", "over": 2.5, "limit": 2, "unit": "tokens"}

``share_of`` is the mean gap (each gap counted up to ``cap``) as a share of
the same mean of the tokens that the reference in lower precision (the
control, the precision below the configuration's) puts first at the same positions: how much of
the precision that the step down would lose the path has lost already. How
far rounding moves a token differs from one seed's weights to the next, for
the program and the control alike, and the share takes that out.
``where_zero`` keeps to the positions where another stand-in (the witness:
the reference in the precision the configurations state, bfloat16) still
puts the reference's best first: where bfloat16 itself cannot decide, the
path is not asked to. It and a quantile overlook a few tokens by construction; the maximum, or the count of
tokens far below the reference's best, is what a few made-up tokens fail. A
cell's rule holds one of each kind.
"""

from __future__ import annotations

import math

MISSING = 1e30  # a statistic that could not be read fails its limit


def quantile(values: list, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    s = sorted(values)
    k = (len(s) - 1) * q
    lo, hi = math.floor(k), math.ceil(k)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def clipped_mean(gaps: list, cap: float) -> float:
    return sum(min(g, cap) for g in gaps) / len(gaps)


def needs(rule: dict) -> list:
    """The stand-ins a rule's statistics are taken against."""
    return sorted({spec[k] for spec in rule.values()
                   for k in ("against", "where_zero") if k in spec})


def stat(gaps: list, spec: dict, others: dict | None = None) -> float:
    """``others``: {stand-in's name: its gaps at the same positions}."""
    if not gaps:
        return MISSING
    kind = spec["of"]
    if kind == "share_of":
        base = (others or {}).get(spec["against"])
        if not base or len(base) != len(gaps):
            return MISSING
        if "where_zero" in spec:
            mask = (others or {}).get(spec["where_zero"])
            if not mask or len(mask) != len(gaps):
                return MISSING
            keep = [i for i, m in enumerate(mask) if m == 0.0]
            if not keep:
                return MISSING
            gaps, base = [gaps[i] for i in keep], [base[i] for i in keep]
        mine = clipped_mean(gaps, float(spec["cap"]))
        theirs = clipped_mean(base, float(spec["cap"]))
        if theirs <= 0.0:  # the step down loses nothing here: nor may the path
            return 0.0 if mine <= 0.0 else MISSING
        return mine / theirs
    if kind == "quantile":
        return quantile(gaps, float(spec["q"]))
    if kind == "max":
        return max(gaps)
    if kind == "count_over":
        return float(sum(1 for g in gaps if g > float(spec["over"])))
    raise ValueError(f"unknown statistic {kind!r}")


def checks(gaps: list, rule: dict, others: dict | None = None) -> list:
    """[(name_unit, value, limit)] of a rule over one list of gaps."""
    return [(name + "_" + spec.get("unit", ""), stat(gaps, spec, others),
             spec["limit"]) for name, spec in sorted(rule.items())]


def passes(rows: list) -> bool:
    return all(v <= lim for _, v, lim in rows)


def summary(gaps: list) -> dict:
    """What every run prints beside the compared numbers."""
    if not gaps:
        return {"tokens": 0}
    return {"tokens": len(gaps), "widest_gap": max(gaps),
            "mean_gap": sum(gaps) / len(gaps),
            "clipped_mean_0.3": clipped_mean(gaps, 0.3),
            "p90_gap": quantile(gaps, 0.9), "p99_gap": quantile(gaps, 0.99),
            "argmax_share": sum(1 for g in gaps if g == 0.0) / len(gaps),
            "over_half_spread": sum(1 for g in gaps if g > 0.5),
            "over_2.5_spreads": sum(1 for g in gaps if g > 2.5)}
