"""Reader for an expert bank that holds a SHARE of the experts its router
chooses among (``GPTConfig.moe_experts_held``): how many of the live rows'
assignments fell on experts that are here.  ``serve.decode.commit`` carries
``moe_assignments`` (top-k x live rows x layers of its step's program) and
``moe_assignments_held``; a program without them (a parent commit, a dense
model) gives nothing to read: None."""

from benchmarks.readers import moe


def assignments_held_pct(run):
    """Over the traced stretch's decode steps: assignments on held experts
    over all assignments.  ``100 x held / experts`` under even routing."""
    stats = moe.span_stats(run) or {}
    held = total = 0
    for s in stats.get(moe.LOAD_SPAN, []):
        if "moe_assignments_held" in s and s.get("moe_assignments"):
            held += s["moe_assignments_held"]
            total += s["moe_assignments"]
    return 100.0 * held / total if total else None
