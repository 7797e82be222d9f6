"""What a traffic kind shares with the others that CALL ``serve-backlog-
resident`` for one stack (every ``kinds/serve_backlog_resident_*.py`` since
PR 68 folded the older kinds' copies in here): that module's names replaced for the
length of one ``resident.run`` (the stack's count of its caches' work in the
place of ``attention_counters``, and whatever else the kind hands in), and
the run's sample judged again under the kind's own two limits.  A new stack
brings its counters and its limits, and no copy of this.
"""

import contextlib

from benchmarks.kinds import serve_backlog_resident as resident


def judge(largest, noise_scales, median, logit_margin, noise_limit):
    """Samples over the gross limit, and those over the noise limit when
    their median is (``resident.check_sample``'s rule, these limits)."""
    return sum(w > logit_margin or (median > noise_limit and s > noise_limit)
               for w, s in zip(largest, noise_scales))


@contextlib.contextmanager
def replaced(module, **names):
    """``module``'s ``names`` are the given objects inside the block."""
    theirs = {name: getattr(module, name) for name in names}
    try:
        for name, mine in names.items():
            setattr(module, name, mine)
        yield
    finally:
        for name, was in theirs.items():
            setattr(module, name, was)


def run(cell, args, ctx, *, logit_margin, noise_limit, **names):
    """``resident.run`` with ``names`` of that module replaced, its sample
    judged again by these limits."""
    with replaced(resident, **names):
        out = resident.run(cell, args, ctx)
    notes = out["notes"]
    if not notes["checked"]:
        return out
    other = out["failed"] - notes["wrong"]            # short or refused requests
    wrong = judge(notes["logit_gaps"], notes["noise_scales"],
                  notes["noise_scale_median"], logit_margin, noise_limit)
    notes.update(wrong=wrong, tie_tolerance=logit_margin, noise_limit=noise_limit)
    out.setdefault("compared", {}).update(
        largest_logit_gap=[max(notes["logit_gaps"]), logit_margin],
        noise_scale_median=[notes["noise_scale_median"], noise_limit],
        requests_wrong=[wrong, 0])
    out.update(failed=wrong + other,
               correct=(wrong == 0 and other == 0 and not notes["backlog_ran_dry"]
                        and notes["cohort_filled"]))
    return out
