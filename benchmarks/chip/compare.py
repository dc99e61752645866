"""The comparison that decides ``correct``: an engine answer against the
oracle's, by the configuration's guarantee (copied from ``chip_smoke.py``
``compare``, which stays the original).

Exact: fixed-point sums, counts, keys, strings and dates equal; ORDER BY
honoured; under a LIMIT every row before the last row's ties is there and
the rest come from the ties.  Floats (SQL ``avg``) are not exact by nature:
their largest relative gap from the oracle is returned as a number of its
own, with a limit of its own.
"""
from __future__ import annotations

import math
from collections import Counter
from typing import List, Optional, Tuple


def table_rows(table) -> list:
    """An engine answer (pyarrow Table) as tuples of python values."""
    import pyarrow as pa

    cols = []
    for col in table.columns:
        if pa.types.is_dictionary(col.type):
            col = col.cast(pa.string())
        cols.append(col.to_pylist())
    return list(zip(*cols)) if cols else []


def _split(row) -> Tuple[tuple, tuple]:
    exact = tuple(v for v in row if not isinstance(v, float))
    floats = tuple(v for v in row if isinstance(v, float))
    return exact, floats


def _rel_gap(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


def compare(got: list, oracle) -> Tuple[Optional[str], Optional[float]]:
    """``(fault, float_gap)``: ``fault`` is None where ``got`` answers the
    query exactly as the oracle does, else one line saying how it differs;
    ``float_gap`` is the largest relative gap of a float field over the rows
    that could be paired (None where the answer has no float field, inf
    where rows cannot be paired)."""
    want, order_keys, limit = oracle
    has_floats = any(isinstance(v, float) for r in want[:1] for v in r)
    no_gap = math.inf if has_floats else None

    def okey(row):
        return tuple(row[i] for i, _ in order_keys)

    for a, b in zip(got, got[1:]):
        for i, asc in order_keys:
            if a[i] != b[i]:
                if (a[i] < b[i]) != asc:
                    return f"ORDER BY violated: {a} then {b}", no_gap
                break
    if limit is not None:
        k = min(limit, len(want))
        if len(got) != k:
            return f"{len(got)} rows, want {k}", no_gap
        if k == 0:
            return None, 0.0 if has_floats else None
        last = okey(want[k - 1])
        before = Counter(r for r in want[:k] if okey(r) != last)
        ties = Counter(r for r in want if okey(r) == last)
        missing = before - Counter(got)
        if missing:
            return f"rows missing: {list(missing)[:2]}", no_gap
        extra = (Counter(got) - before) - ties
        if extra:
            return f"rows not in the oracle's answer: {list(extra)[:2]}", \
                no_gap
        return None, 0.0 if has_floats else None
    if len(got) != len(want):
        return f"{len(got)} rows, want {len(want)}", no_gap
    fault, gap = None, 0.0
    pairs = zip(sorted(got, key=lambda r: str(_split(r)[0])),
                sorted(want, key=lambda r: str(_split(r)[0])))
    for g, w in pairs:
        (ge, gf), (we, wf) = _split(g), _split(w)
        if ge != we or len(gf) != len(wf):
            fault = fault or f"row differs: got {g} want {w}"
        if len(gf) == len(wf):
            gap = max([gap] + [_rel_gap(a, b) for a, b in zip(gf, wf)])
    return fault, gap if has_floats else None


def judge(answers: List[Tuple[str, Optional[list]]], oracles: dict,
          limits: dict) -> dict:
    """Every answer of a window against its query's oracle.

    ``answers``: ``(query, rows)`` per query the window attempted, rows None
    where the query failed or never answered.  Returns the numbers compared,
    each beside its limit, and ``correct``: every number within its limit
    and at least one answer compared."""
    wrong, missing, gap, first_fault = 0, 0, None, None
    for query, rows in answers:
        if rows is None:
            missing += 1
            continue
        fault, g = compare(rows, oracles[query])
        if fault is not None:
            wrong += 1
            first_fault = first_fault or f"{query}: {fault}"
        if g is not None:
            gap = g if gap is None else max(gap, g)
    compared = len(answers) - missing
    numbers = {
        "answers_compared": {"value": compared, "limit": 1,
                             "at_least": True},
        "answers_wrong": {"value": wrong, "limit": limits["answers_wrong"]},
        "answers_missing": {"value": missing,
                            "limit": limits["answers_missing"]},
    }
    if gap is not None:       # only a mix with SQL avg has float fields
        numbers["float_rel_gap"] = {"value": gap,
                                    "limit": limits["float_rel_gap"]}
    correct = compared >= 1 and all(
        n["value"] <= n["limit"] for name, n in numbers.items()
        if not n.get("at_least"))
    return {"correct": correct, "numbers": numbers,
            "first_fault": first_fault}
