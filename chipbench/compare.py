"""The comparison that decides ``correct``: records against the reference.

Every field is compared exactly, floats by their hexadecimal form: the
configurations state bit-identity with the plain reference, so the limit
of every number compared is 0.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from chipbench.reference import RESULT_FIELDS

# Name -> limit. A run is correct when every number is at most its limit
# and at least one record was compared.
LIMITS = {"records_differing": 0, "requests_failed": 0}


def bits(result: dict) -> tuple:
    return tuple((k, result[k].hex() if isinstance(result[k], float)
                  else result[k]) for k in RESULT_FIELDS)


def differs(got: Optional[dict], want: dict) -> bool:
    """True when `got` is missing, malformed or off by any bit."""
    if got is None or set(got) != set(want):
        return True
    try:
        return bits(got) != bits(want)
    except (KeyError, AttributeError):
        return True


def checks(answers: Iterable[Tuple[tuple, Optional[dict]]], reference,
           machines: Dict[str, dict], requests_failed: int) -> dict:
    """Compare ``((machine, bench, seed), record)`` answers.

    A record of None stands for an answer that should have come and did
    not. Returns the numbers compared, each with its limit, plus the
    count of records compared.
    """
    n = bad = 0
    wanted: Dict[tuple, dict] = {}
    # An answer object repeated (one record read many times) is judged once.
    verdict: Dict[tuple, bool] = {}
    for cell, got in answers:
        n += 1
        key = (cell, id(got))
        if key not in verdict:
            want = wanted.get(cell)
            if want is None:
                mname, bench, seed = cell
                want = wanted[cell] = reference.cell(bench, seed,
                                                     machines[mname])
            verdict[key] = differs(got, want)
        bad += verdict[key]
    return {"records_differing": {"value": bad,
                                  "limit": LIMITS["records_differing"]},
            "requests_failed": {"value": requests_failed,
                                "limit": LIMITS["requests_failed"]},
            "records_compared": {"value": n, "limit": 1}}


def passed(result: dict) -> bool:
    """Every number within its limit; at least one record compared."""
    return (result["records_compared"]["value"] >= 1
            and all(result[k]["value"] <= lim for k, lim in LIMITS.items()))


def lines(result: dict) -> List[str]:
    """The numbers compared, one plain line each, for standard error."""
    out = []
    for k, v in result.items():
        rel = ">=" if k == "records_compared" else "<="
        out.append(f"check {k}={v['value']} limit {rel} {v['limit']}")
    return out
