"""Traffic kind ``serve-open-loop``: requests fall due on a schedule fixed by
the cell, whatever the server does (independent users).  The rate is a number
in the traffic file, found once by a sweep on the chip; nothing here searches
for one.

Every second of the schedule holds the same number of arrivals (to rounding);
prompt and output lengths are the evenly spaced quantiles of their
distributions, dealt so that every few seconds span the whole range (``plan``).
``lead_seconds`` of the same traffic run before the window as set-up, so that
the window opens on a server in its steady state; the requests DUE inside the
window are the measured ones.  After the window the loop drains only until the last of them
has its first token, under ``drain_cap_seconds``: a request cut while it
generates counts for ``tpot_p90_ms`` with the tokens it has, one without a
first token by then has failed.
"""

import time

import numpy as np

from benchmarks.lib import draws
from benchmarks.lib.device import memory_peak_bytes, span
from benchmarks.lib.serving import TIE_TOL, Sent, Serving

END_TO_END = ("ttft_p90_ms", "tpot_p90_ms")


def plan(mix, seconds, n_positions, vocab, seed):
    """(schedule, lead seconds): the schedule is [(due second relative to the
    window's start, prompt ids, new tokens)], ascending; negative dues are
    the lead-in.

    The schedule is a TRACE, replayed by every run: blocks of
    ``block_seconds`` (``draws.blocks``) drawn once from the traffic file's
    ``canonical_seed``, so lengths, their pairing and every due time are data
    of the cell.  ``--seed`` gives the weights and every token id.  A tail
    over some hundred requests of a queue near its knee moved by 10 to 25%
    between seeds that reordered the same work, and by 2% between replays
    (PERF.md, PR 23): under a bound of at most 10% only the replay can carry
    a claim."""
    rate, B = float(mix["rate_per_s"]), float(mix["block_seconds"])
    lead_blocks = int(-(-float(mix["lead_seconds"]) // B))
    n_blocks = lead_blocks + int(-(-float(seconds) // B))
    blocks = draws.blocks(rate, B, n_blocks, mix["prompt_tokens"],
                          mix["output_tokens"],
                          np.random.default_rng(int(mix["canonical_seed"])))
    rng = np.random.default_rng(seed)
    out = []
    for place, block in enumerate(blocks):
        start = (place - lead_blocks) * B
        for offset, p, o in block:
            if start + offset < seconds:
                out.append((start + offset, draws.prompt_tokens(p, vocab, rng),
                            min(o, n_positions - p)))
    return out, lead_blocks * B


def run(cell, args, ctx):
    mix = cell.traffic
    srv = Serving(cell, args, ctx)
    mcfg = srv.model.cfg
    schedule, lead = plan(mix, args.seconds, mcfg.n_positions, mcfg.vocab_size,
                          args.seed)
    srv.warm()
    tracer, trace, snaps = ctx["tracer"], None, {}
    trace_at = args.seconds - ctx["trace_seconds"]
    t0 = srv.clock() + lead                  # the window's start
    t_end = t0 + args.seconds
    cap = t_end + float(mix["drain_cap_seconds"])
    pending = [Sent(t0 + d, prompt, new, measured=d >= 0.0)
               for d, prompt, new in schedule]
    measured = [s for s in pending if s.measured]
    i, window_open, first_step, programs0 = 0, False, None, None
    while True:
        now = srv.clock()
        if not window_open and now >= t0:
            window_open = True
            ctx["phases"]["lead_in"] = lead
            ctx["setup_done"](time.perf_counter() - (now - t0))
            ctx["compiles"].mark()
            programs0, first_step = srv.engine.compiled_programs(), len(srv.steps)
        if now >= t_end and i == len(pending) and (now >= cap or all(
                s.refused or s.request.first_token_at is not None
                or s.request.state == "expired" for s in measured)):
            break
        if tracer and not tracer.on and trace is None and now >= t0 + trace_at:
            tracer.start()
            snaps["before"], snaps["step"] = srv.snapshot(), len(srv.steps)
        if tracer and tracer.on and now >= t_end:
            snaps["after"], snaps["end"] = srv.snapshot(), len(srv.steps)
            trace = tracer.stop()
        while i < len(pending) and pending[i].due <= now:
            srv.submit(pending[i])
            i += 1
        if srv.has_work:
            srv.step()
        else:
            with span("bench.idle"):
                nxt = pending[i].due if i < len(pending) else t_end
                time.sleep(max(0.0, min(nxt, t_end) - srv.clock()))
    cut_at = srv.clock()
    if tracer and tracer.on:
        snaps["after"], snaps["end"] = srv.snapshot(), len(srv.steps)
        trace = tracer.stop()
    compiles = max(ctx["compiles"].in_window(),
                   srv.engine.compiled_programs() - programs0)

    ttft, tpot, waits, late, failed, cut = [], [], [], [], 0, 0
    for s in measured:
        r = s.request
        late.append(1e3 * (s.submitted - s.due))
        if s.refused or r.first_token_at is None:
            # failed: it misses every limit, so it stays in the tail with
            # the wait it had when the drain was cut (less than its real one)
            failed += 1
            ttft.append(1e3 * (cut_at - s.due))
            continue
        ttft.append(1e3 * (r.first_token_at - s.due))
        if s.prefill_seen is not None:
            waits.append(1e3 * (s.prefill_seen - s.due))
        n = len(r.generated)
        end = r.finished_at
        if end is None:                 # cut by the drain while it generates
            end, cut = cut_at, cut + 1
        elif n != s.max_new:
            failed += 1
        if n > 1:
            tpot.append(1e3 * (end - r.first_token_at) / (n - 1))
    finished = [s for s in measured if s.request is not None
                and s.request.finished_at is not None]
    checked, wrong, worst = srv.check_sample(
        finished, int(mix["check_requests"]), np.random.default_rng(args.seed))
    window_steps = [st for st in srv.steps[first_step:] if st[0] < t_end]
    counters = dict(srv.step_counters(window_steps),
                    compiles_in_window=compiles,
                    memory_peak_bytes=memory_peak_bytes(),
                    prefill_wait_p90_ms=float(np.percentile(waits, 90)),
                    prefill_wait_share_of_ttft_p90=float(
                        np.percentile(waits, 90) / np.percentile(ttft, 90)),
                    gen_lateness_p99_ms=float(np.percentile(late, 99)),
                    ttft_p50_ms=float(np.percentile(ttft, 50)),
                    tpot_p50_ms=float(np.percentile(tpot, 50)),
                    requests_measured=len(measured), requests_cut=cut,
                    drain_s=cut_at - t_end)
    if trace is not None:
        counters.update(srv.paged_counters(
            snaps, srv.steps[snaps["step"]:snaps["end"]]))
    srv.close()
    return {
        "correct": wrong == 0 and checked > 0,
        "attempted": len(measured), "failed": failed + wrong,
        "end_to_end": {"ttft_p90_ms": float(np.percentile(ttft, 90)),
                       "tpot_p90_ms": float(np.percentile(tpot, 90))},
        "counters": counters, "trace": trace,
        "compared": {"largest_logit_gap": [worst, TIE_TOL], "tokens_wrong": [wrong, 0]},
        "notes": {"checked": checked, "wrong": wrong, "largest_logit_gap": worst,
                  "tie_tolerance": TIE_TOL, "rate_per_s": float(mix["rate_per_s"]),
                  "requests_with_ttft": len(ttft), "requests_with_tpot": len(tpot),
                  "slow_steps": srv.slow_steps(srv.steps[first_step:], t0)},
    }
