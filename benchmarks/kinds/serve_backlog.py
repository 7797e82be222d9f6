"""Traffic kind ``serve-backlog``: no arrival schedule.  At the window's start
every decode slot is full and the queue holds more requests than the window
can finish, so the slots stay full: what is measured is the tokens the engine
generates a second.

Set-up fills the slots with a first cohort whose REMAINING outputs are the
evenly spaced quantiles of the residual-life distribution of the cell's
output lengths (``draws.residual_quantiles``): the window opens on the mix of
ages a long-running server holds, and no cohort finishes together.  A cohort
member admitted ``k`` steps before the window's start will have generated
about ``k`` tokens by then, so it is asked for its residual plus ``k``.
``serve_tokens_per_s`` counts the tokens of the whole engine steps inside the
window over the time from the first of those steps' start to the last one's
end, not over ``--seconds``.
"""

import time

import numpy as np

from benchmarks.lib import draws
from benchmarks.lib.device import memory_peak_bytes
from benchmarks.lib.serving import TIE_TOL, Sent, Serving

END_TO_END = ("serve_tokens_per_s",)


def plan(mix, slots, n_positions, vocab, seed):
    """(first cohort, backlog) as lists of (prompt ids, new tokens)."""
    rng = np.random.default_rng(seed)
    n_back = int(mix["backlog_requests"])
    cohort_prompts = draws.lengths(mix["prompt_tokens"], slots, rng)
    residual = draws.spread_order(draws.residual_quantiles(
        mix["output_tokens"], slots), rng)
    cohort = []
    for i in range(slots):
        p = int(cohort_prompts[i])
        # one prompt chunk a step: member i waits slots - i more steps
        new = min(int(residual[i]) + (slots - i), n_positions - p)
        cohort.append((draws.prompt_tokens(p, vocab, rng), new))
    back_p = draws.lengths(mix["prompt_tokens"], n_back, rng)
    back_o = draws.lengths(mix["output_tokens"], n_back, rng)
    backlog = [(draws.prompt_tokens(int(p), vocab, rng),
                min(int(o), n_positions - int(p))) for p, o in zip(back_p, back_o)]
    return cohort, backlog


def run(cell, args, ctx):
    mix = cell.traffic
    srv = Serving(cell, args, ctx)
    mcfg = srv.model.cfg
    cohort, backlog = plan(mix, srv.slots, mcfg.n_positions, mcfg.vocab_size,
                           args.seed)
    srv.warm()
    with ctx["phase"]("fill"):
        for prompt, new in cohort:
            srv.submit(Sent(None, prompt, new, measured=True))
        for _ in range(4 * srv.slots):
            stats = srv.step(record=False)
            if stats["decode_batch"] >= stats["active"] and not stats["queue_depth"]:
                break
        for prompt, new in backlog:
            srv.submit(Sent(None, prompt, new, measured=True))

    tracer, trace, snaps = ctx["tracer"], None, {}
    trace_at = args.seconds - ctx["trace_seconds"]
    ctx["compiles"].mark()
    programs0 = srv.engine.compiled_programs()
    finished0 = srv.engine.sched.finished_count
    generated0 = srv.engine.tokens_generated
    t0 = time.monotonic()
    ctx["setup_done"](time.perf_counter())
    while time.monotonic() - t0 < args.seconds and srv.has_work:
        if tracer and not tracer.on and time.monotonic() - t0 >= trace_at:
            tracer.start()
            snaps["before"], snaps["step"] = srv.snapshot(), len(srv.steps)
        srv.step()
    if tracer and tracer.on:
        snaps["after"] = srv.snapshot()
        trace = tracer.stop()
    compiles = max(ctx["compiles"].in_window(),
                   srv.engine.compiled_programs() - programs0)

    steps = srv.steps
    span_s = steps[-1][1] - steps[0][0]
    tokens = steps[-1][4] - generated0
    done = [s for s in srv.sent if s.request is not None
            and s.request.finished_at is not None and s.request.finished_at >= t0]
    ran_dry = not srv.has_work         # then the slots did not stay full
    queue_left = srv.engine.sched.stats()["queue_depth"]
    short = sum(len(s.request.generated) != s.max_new for s in done)
    refused = sum(s.refused for s in srv.sent)
    checked, wrong, worst = srv.check_sample(
        done, int(mix["check_requests"]), np.random.default_rng(args.seed))
    counters = dict(srv.step_counters(steps),
                    compiles_in_window=compiles,
                    memory_peak_bytes=memory_peak_bytes(),
                    finished_in_window=srv.engine.sched.finished_count - finished0)
    if trace is not None:
        counters.update(srv.paged_counters(snaps, steps[snaps["step"]:]))
    srv.close()
    return {
        "correct": wrong == 0 and short == 0 and checked > 0 and not ran_dry,
        "attempted": len(done) + refused, "failed": wrong + short + refused,
        "end_to_end": {"serve_tokens_per_s": tokens / span_s},
        "counters": counters, "trace": trace,
        "compared": {"largest_logit_gap": [worst, TIE_TOL], "tokens_wrong": [wrong, 0],
                     "requests_short_of_their_tokens": [short, 0],
                     "backlog_ran_dry": [int(ran_dry), 0]},
        "notes": {"checked": checked, "wrong": wrong, "largest_logit_gap": worst,
                  "tie_tolerance": TIE_TOL, "window_s": span_s, "tokens": tokens,
                  "backlog_ran_dry": ran_dry, "queue_left": queue_left,
                  "slow_steps": srv.slow_steps(steps, t0)},
    }
