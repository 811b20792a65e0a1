"""Structured plan-event stream: each planner decision with its
predicted cost.

Every planner decision (`gspmm`, `block:*`, `block_bwd:*`, `hetero:*`,
`sddmm:*`, `attn:*`, `serve:infer`, `partitioned:train`) flows through
:func:`plan_event`, which records the cost model's *predicted* cost for
the chosen strategy next to the decision. :func:`plan_events` lists the
rows; the record schema (:data:`PLAN_EVENT_FIELDS`) is pinned by a
golden test, and BENCH_*.json embeds the stream.
"""
import threading

from .metrics import enabled

__all__ = ["PLAN_EVENT_FIELDS", "plan_event", "plan_events",
           "clear_events", "family_of", "enabled"]

# Golden schema: tests/obs/test_plan_events.py pins this field list so
# downstream BENCH_*.json parsing can't silently break. Extend by
# appending (and updating the golden test) — never reorder or rename.
PLAN_EVENT_FIELDS = (
    "op", "family", "requested", "chosen", "count",
    "predicted_cost", "dtype",
)

_LOCK = threading.Lock()
# (op, requested, chosen, dtype) -> {"count", "predicted_cost"}
_PLANS = {}

_FAMILIES = ("block_bwd", "block", "hetero", "sddmm", "attn", "serve",
             "partitioned")


def family_of(op):
    """Plan-row family of an op key: the prefix before ':' for
    prefixed rows, ``gspmm`` for bare binary-reduce spec names."""
    head, sep, _ = op.partition(":")
    if sep and head in _FAMILIES:
        return head
    return "gspmm"


def plan_event(op, requested, chosen, predicted_cost=None, dtype=None):
    """Record one planner decision row. ``predicted_cost`` is the cost
    model's estimate for the *chosen* strategy (relative element-ops);
    pass None when the site has no cost model input (e.g. forced
    strategies without graph stats). ``dtype`` is the operand element
    type the decision was made for (a string, e.g. "bfloat16"), or None
    at sites with no operand in hand — rows are keyed on it, so the
    same op planned at two precisions yields two rows."""
    if not enabled():
        return
    key = (str(op), str(requested), str(chosen),
           None if dtype is None else str(dtype))
    with _LOCK:
        row = _PLANS.get(key)
        if row is None:
            row = {"count": 0, "predicted_cost": None}
            _PLANS[key] = row
        row["count"] += 1
        if predicted_cost is not None:
            row["predicted_cost"] = float(predicted_cost)


def plan_events():
    """The plan-event stream as a list of dicts in the pinned
    :data:`PLAN_EVENT_FIELDS` schema, sorted by op key."""
    with _LOCK:
        plans = {k: dict(v) for k, v in _PLANS.items()}
    rows = []
    def sort_key(k):
        op, requested, chosen, dtype = k
        return (op, requested, chosen, dtype or "")

    for (op, requested, chosen, dtype) in sorted(plans, key=sort_key):
        p = plans[(op, requested, chosen, dtype)]
        rows.append({
            "op": op,
            "family": family_of(op),
            "requested": requested,
            "chosen": chosen,
            "count": p["count"],
            "predicted_cost": p["predicted_cost"],
            "dtype": dtype,
        })
    return rows


def clear_events():
    """Drop all plan events (tests / bench isolation)."""
    with _LOCK:
        _PLANS.clear()
