"""One implementation of the repo's JSON-strictness rule.

NaN/Inf are not JSON: ``json.dumps`` happily writes literal ``NaN`` /
``Infinity`` tokens (``allow_nan`` defaults True) and strict consumers
(jq, ``JSON.parse``) abort the whole stream on one bad line.  The serving
entry point's output line routes through ``json_safe``; utils/metrics_writer.py applies
the same rule inline at
its single scalar() write site (a scalar check, not a tree walk).
"""

from __future__ import annotations


def json_safe(obj):
    """NaN and ±Inf -> None, recursively, through dicts/lists/tuples."""
    if isinstance(obj, float) and (obj != obj or obj in
                                   (float("inf"), float("-inf"))):
        return None
    if isinstance(obj, dict):
        return {k: json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    return obj
