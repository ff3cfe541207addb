"""Planner: host seconds per query building the logical plan and lowering it
with ``compile_plan``; None for a query without a planning step."""


def read(record):
    vals = [q["plan_s"] for q in record.queries if "plan_s" in q]
    return sum(vals) / len(vals) if vals else None
