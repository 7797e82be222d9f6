"""Traffic kind ``serve-backlog-resident``: ``serve-backlog`` for long
contexts.  No arrival schedule; at the window's start every decode slot is
full and the queue holds more requests than the window can finish; what is
measured is the tokens the engine generates a second, as ``serve-backlog``
computes it (the tokens of the whole engine steps inside the window over the
time from the first one's start to the last one's end).

What differs, and why it is a kind of its own:

* the first cohort is RESIDENT AT ITS AGE: what a long-running server holds
  at a random moment.  What remains of a member's output is a quantile of
  the outputs' residual life (as ``serve-backlog`` draws it), its whole
  output ``L`` a quantile of the outputs longer than that, its age the
  difference; the ``prompt + age`` tokens it would hold are prefilled during
  set-up as its prompt, and it is asked for the rest.  ``serve-backlog``
  starts every member at age 0 and lets the fill age them, which at prompts
  of one chunk is the same thing; at prompts of 20-40 chunks the fill takes
  a thousand steps and more, so a member is prefilled short by the tokens it
  will generate while the later members' chunks run, and asked for that many
  more;
* ONE plan is replayed whatever ``--seed`` (as ``serve-open-loop`` replays
  one trace), and it is CONSTRUCTED, not drawn (:func:`plan`): the token ids
  (and the weights) come from ``--seed``.  A request that finishes brings a
  prompt of 18-37 chunk steps, 7% of a window that holds seven to ten of
  them, so seeds that only REORDERED the same lengths moved
  ``serve_tokens_per_s`` between 488 and 555 as 274 to 165 of a window's
  steps carried a chunk (PERF.md § 6, PR 31), and any one drawn order is a
  lucky or an unlucky one.  Constructed, a member finishes every ``mean
  output / slots`` steps and each two that finish bring two mean prompts, so
  a step carries a chunk as often as in a long run (6,144 / 224 chunk steps a
  request over 3,072 / 32 steps a request: 28.5%);
* the fill runs until every member decodes, however many chunks that takes;
* the sample is checked by ONE full forward pass of the reference over
  prompt and output with the head run over the generated positions alone, in
  blocks: ``lib/serving.py:check_sample`` asks for ``[n_positions, vocab]``
  logits a row, 9.96 GB at 16,384 positions and 151,936 words.  The engine
  and its arena are let go first: the reference's activations take their
  place on the chip;
* the attention's operations and bytes are the algorithm's LEAST for what
  the program ran (:func:`attention_counters` over ``lib/arith_window.py``,
  the ONE count of every kind that calls this one): a decode slot a single
  query at its position, a prompt's tokens the chunks they ran as, each
  chunk's pages once for all its queries, a window layer at the pages it can
  see, a row without a request nothing.
"""

import gc
import math
import statistics
import time

import numpy as np

from benchmarks.lib import arith_window, draws
from benchmarks.lib.cells import resolve
from benchmarks.lib.device import memory_peak_bytes
from benchmarks.lib.serving import Sent, Serving

END_TO_END = ("serve_tokens_per_s",)
# The two limits of the check (PERF.md § 6, PR 31).  This model's weights are
# random, and what it generates is a cycle of one to fifteen words in which
# the best two logits lie 0.05-0.3 apart: bf16 against the float32 reference
# then flips 1-9% of a request's tokens to the second best (a sixth and a
# seventh expert swap on rounding and the six weights are renormalised), one
# request in fifteen 31%, and a served token loses by up to 0.311 of logit
# where GPT-2 and OLMoE lose 0.04 (``lib/serving.py:TIE_TOL`` 0.0625).  Neither
# the largest gap nor the mean gap nor the share of flips of ONE request tells
# bf16 from the precision below it: all three follow how close the request's
# own cycle runs to a tie.
# Every served token within this of the reference's best logit: the GROSS
# limit, 2.4 times the largest seen (0.311), a third of what a cache of
# garbage gives (2.1-2.4, PR 21).  A float8 bank reads 0.22-0.40 and passes it.
LOGIT_MARGIN = 0.75
# The limit on precision is on the NOISE SCALE (:func:`noise_scale`): how
# large a Gaussian difference between the served logits and the reference's
# would flip as many of the request's tokens as did flip, given how close to
# a tie each position is; and on its MEDIAN over the run's checked requests,
# because a request is one cycle, not a thousand independent positions.
NOISE_LIMIT = 0.06
HEAD_BLOCK = 256            # generated positions a call of the reference's head


def noise_scale(margins, flips):
    """``s`` with ``sum_t Phi(-margins[t] / s) == flips``: the scale, in
    logits, of the difference between the served model's logits and the
    reference's at which the expected number of positions whose best two
    swap equals the number that did.  ``margins`` are the reference's best
    logit less its second best a position.  0 where nothing flipped."""
    if flips <= 0:
        return 0.0
    margins = np.asarray(margins, np.float64)
    half_erfc = np.vectorize(lambda x: 0.5 * math.erfc(x))
    lo, hi = 1e-6, 1e3
    for _ in range(60):
        mid = math.sqrt(lo * hi)
        if half_erfc(margins / (mid * math.sqrt(2.0))).sum() < flips:
            lo = mid
        else:
            hi = mid
    return mid


def radical_inverse(n, base=2):
    """The first ``n`` terms of the van der Corput sequence in ``base``, in
    [0, 1): every run of consecutive terms covers the range evenly, and the
    sequences of two coprime bases pair up evenly too (Halton)."""
    out = np.zeros(n)
    for i in range(n):
        k, f = i, 1.0
        while k:
            f /= base
            out[i] += f * (k % base)
            k //= base
    return out


def dealt(values, base):
    """``values`` (ascending) dealt in the order of :func:`radical_inverse`:
    item ``k`` is the quantile the ``k``-th term points at."""
    values = np.asarray(values)
    return values[np.argsort(np.argsort(radical_inverse(len(values), base), kind="stable"))]


def plan(mix, slots, chunk, n_positions, vocab, seed):
    """(first cohort, backlog) as lists of (prompt ids, new tokens), and the
    cohort's planned (prompt, age, whole output) a member.  Every length and
    order is CONSTRUCTED from the mix; ``seed`` gives the token ids.

    What a long-running server holds at a random moment, without the luck of
    a draw: the members' REMAINING outputs are the evenly spaced quantiles of
    the outputs' residual life (``draws.residual_quantiles``, as
    ``serve-backlog`` draws them), so one member finishes every ``mean
    output / slots`` steps; a member's whole output is a quantile of the
    outputs longer than what remains of it (what the whole is distributed as,
    given the rest), its prompt a quantile of the prompts, the two dealt
    across the members by the van der Corput sequences of bases 2 and 3.  The
    backlog's prompts come in pairs of the ``i``-th shortest and ``i``-th
    longest, so each two requests that finish bring two mean prompts."""
    rng = np.random.default_rng(seed)
    n_back = int(mix["backlog_requests"])
    remaining = draws.residual_quantiles(mix["output_tokens"], slots)
    grid = draws.quantiles(mix["output_tokens"], 4096)
    at = radical_inverse(slots, 2) + 0.5 / slots
    whole = np.asarray([grid[grid >= r][int(u * (grid >= r).sum())]
                        for r, u in zip(remaining, at)])
    ages = whole - remaining
    prompts = dealt(draws.quantiles(mix["prompt_tokens"], slots), 3)
    # the oldest first: member i generates, while the chunks of the members
    # behind it run and in the two steps that end the fill, ``ahead`` tokens,
    # and is prefilled that much short of its age (never short of its prompt)
    order = np.argsort(-ages, kind="stable")
    prompts, ages, whole = prompts[order], ages[order], whole[order]
    ahead, cohort = 2, [None] * slots
    for i in reversed(range(slots)):
        resident = int(prompts[i] + ages[i] - min(ages[i], ahead))
        new = int(whole[i] - ages[i] + ahead)
        assert resident + new <= n_positions, (resident, new, n_positions)
        cohort[i] = (draws.prompt_tokens(resident, vocab, rng), new)
        ahead += -(-resident // chunk)
    q = draws.quantiles(mix["prompt_tokens"], n_back + n_back % 2)
    half = len(q) // 2
    pairs = np.argsort(radical_inverse(half, 2), kind="stable")
    back_p = np.stack([q[half - 1 - pairs], q[half + pairs]], 1).reshape(-1)[:n_back]
    back_o = dealt(draws.quantiles(mix["output_tokens"], n_back), 3)
    backlog = [(draws.prompt_tokens(int(p), vocab, rng),
                min(int(o), n_positions - int(p))) for p, o in zip(back_p, back_o)]
    return cohort, backlog, list(zip(prompts.tolist(), ages.tolist(), whole.tolist()))


class Resident(Serving):
    """``Serving`` with what the engine says of its layer groups' pages kept
    a step (absent on a program without layer groups: nothing is kept)."""

    def __init__(self, cell, args, ctx):
        super().__init__(cell, args, ctx)
        self.pages = []

    def step(self, record=True):
        stats = super().step(record)
        if record and "pages_window" in stats:
            self.pages.append((stats["pages_full"], stats["pages_window"],
                               stats["pages_given_back"]))
        return stats


def rows_between(srv, snaps):
    """(decode, chunks) of what ran between two snapshots, from the lengths
    alone: ``decode`` the position of every single-query row (each request's
    decode steps in between; its rows end at ``resident - 1``), ``chunks`` the
    ``(first position, tokens)`` of every prompt chunk (a request's prompt
    tokens in between, cut as ``srv.chunk`` cut them)."""
    decode, chunks = [], []
    for rid, (plen, res1, gen1) in snaps["after"].items():
        _, res0, gen0 = snaps["before"].get(rid, (plen, 0, 0))
        if gen0 == 0 and res0 < plen:                 # prompt chunks run
            end = min(res1, plen)
            chunks += [(first, min(srv.chunk, end - first))
                       for first in range(res0, end, srv.chunk)]
        # each generated token but the one the last prompt chunk yields
        d = max((gen1 - gen0) - (1 if gen0 == 0 and gen1 > 0 else 0), 0)
        decode.append(np.arange(res1 - d, res1))
    return (np.concatenate(decode) if decode else np.zeros(0, np.int64)), chunks


def live_positions(decode, chunks):
    """The position of every live row: the decode rows', then each chunk's
    consecutive queries'."""
    return np.concatenate([decode] + [first + np.arange(n) for first, n in chunks])


def row_counters(srv, steps, decode, chunks):
    """What every kind's count leaves of the stretch's rows: the live ones,
    the chunks they made, the rows of the programs run that carried no
    request (which cost nothing), how many of a chunk's queries the
    program's attention packs a row, and the live rows a step."""
    live = len(decode) + sum(n for _, n in chunks)
    programs = sum(1 for st in steps if st[2] > 0 or st[3] > 0)
    return {"attention_rows_live": live, "attention_chunks": len(chunks),
            "attention_rows_idle": max(programs * (srv.slots + srv.chunk) - live, 0),
            "chunk_queries_per_row": getattr(getattr(srv, "engine", None),
                                             "chunk_queries_per_row", 0),
            "traced_step_rows": Serving.step_rows(steps)}


def attention_counters(srv, snaps, steps):
    """Operations and bytes attention needed between two snapshots, the
    algorithm's least (``lib/arith_window.py``): each request's decode steps
    in between a single-query row at its own position, its prompt tokens the
    chunks they ran as, in every layer at the pages that layer's kind can
    see.  ``paged_gqa_*`` are K and V pages' (``readers/paged_gqa.py``);
    ``attention_keys_read`` and ``attention_key_products`` the same count in
    keys, for a cache that is not K and V heads (``readers/paged_mla.py``)."""
    mcfg = srv.model.cfg
    decode, chunks = rows_between(srv, snaps)
    layers = {}
    for kind in mcfg.pattern:
        layers[kind.window] = layers.get(kind.window, 0) + mcfg.n_layer // len(mcfg.pattern)
    rows = row_counters(srv, steps, decode, chunks)
    read, products = arith_window.keys(decode, chunks, layers, srv.block)
    flops, nbytes = arith_window.cost(
        read, products, rows["attention_rows_live"] * mcfg.n_layer, srv.lanes,
        mcfg.n_head, mcfg.head_dim, srv.params["wte"].dtype.itemsize)
    return dict(rows, paged_gqa_flops=flops, paged_gqa_bytes=nbytes,
                attention_keys_read=read, attention_key_products=products)


def check_sample(model, params, reference, samples):
    """Each (prompt, generated) teacher-forced through one full forward pass
    of the plain reference.  Every generated token must be the reference's
    best within ``LOGIT_MARGIN`` of logit at its own position, and the median
    over the samples of the :func:`noise_scale` within ``NOISE_LIMIT``.
    -> {checked, wrong (samples over the gross limit, and those over the
    noise limit when the median is), and a sample: ``largest`` and ``mean``
    gap, ``share`` of tokens not the reference's best, ``noise_scale``}."""
    import jax
    import jax.numpy as jnp
    kw = reference["kwargs"]
    hidden_fn, head_fn = resolve(reference["hidden"]), resolve(reference["head"])
    q_block = int(kw.get("q_block", 1024))
    longest = max(len(p) + len(g) for p, g in samples)
    padded = -(-longest // q_block) * q_block

    def gaps(params, hidden, ids, start):
        """of the tokens after positions ``start .. start + HEAD_BLOCK``: the
        served token's gap, and the best logit's lead over the second"""
        rows = jax.lax.dynamic_slice_in_dim(hidden, start, HEAD_BLOCK)
        nxt = jax.lax.dynamic_slice_in_dim(ids, start + 1, HEAD_BLOCK)
        lg = head_fn(params, rows, **kw)
        best = jax.lax.top_k(lg, 2)[0]
        return (best[:, 0] - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0],
                best[:, 0] - best[:, 1])

    hidden_of = jax.jit(lambda p, ids: jnp.pad(hidden_fn(p, ids[:padded], **kw),
                                               ((0, HEAD_BLOCK), (0, 0))))
    gaps_of = jax.jit(gaps)
    out = {"largest": [], "mean": [], "share": [], "noise_scale": []}
    for prompt, generated in samples:
        seq = np.zeros(padded + HEAD_BLOCK + 1, np.int32)
        seq[:len(prompt) + len(generated)] = list(prompt) + list(generated)
        ids = jnp.asarray(seq)
        hidden = hidden_of(params, ids)
        lo, hi = len(prompt) - 1, len(prompt) + len(generated) - 1
        g, lead = (np.concatenate(cols) for cols in zip(*(
            [np.asarray(a)[:min(HEAD_BLOCK, hi - start)]
             for a in gaps_of(params, hidden, ids, start)]
            for start in range(lo, hi, HEAD_BLOCK))))
        out["largest"].append(float(g.max()))
        out["mean"].append(float(g.mean()))
        out["share"].append(float((g > 0).mean()))
        out["noise_scale"].append(noise_scale(lead, int((g > 0).sum())))
    median = statistics.median(out["noise_scale"])
    wrong = sum(w > LOGIT_MARGIN or (median > NOISE_LIMIT and s > NOISE_LIMIT)
                for w, s in zip(out["largest"], out["noise_scale"]))
    return dict(out, checked=len(samples), wrong=int(wrong), noise_scale_median=median)


def compared(check, short, refused, ran_dry, filled, logit_margin, noise_limit):
    """Each number the run is held to, beside its limit."""
    return {"largest_logit_gap": [max(check["largest"]), logit_margin],
            "noise_scale_median": [check.get("noise_scale_median"), noise_limit],
            "requests_wrong": [check["wrong"], 0],
            "requests_short_of_their_tokens": [short, 0], "requests_refused": [refused, 0],
            "backlog_ran_dry": [int(ran_dry), 0], "cohort_not_filled": [int(not filled), 0]}


def run(cell, args, ctx):
    mix = cell.traffic
    srv = Resident(cell, args, ctx)
    mcfg = srv.model.cfg
    cohort, backlog, _ = plan(mix, srv.slots, srv.chunk, mcfg.n_positions,
                              mcfg.vocab_size, args.seed)
    srv.warm()
    with ctx["phase"]("fill"):
        for prompt, new in cohort:
            srv.submit(Sent(None, prompt, new, measured=True))
        chunks = sum(-(-len(p) // srv.chunk) for p, _ in cohort)
        for _ in range(chunks + 4 * srv.slots):
            stats = srv.step(record=False)
            if stats["decode_batch"] >= stats["active"] and not stats["queue_depth"]:
                break
        filled = stats["decode_batch"] == srv.slots
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
    counters = dict(srv.step_counters(steps),
                    compiles_in_window=compiles,
                    finished_in_window=srv.engine.sched.finished_count - finished0)
    if srv.pages:
        held = np.asarray(srv.pages, np.float64)
        grown = held[:, 1] + held[:, 2]      # what tables that only grow would hold
        counters.update(
            kv_window_freed_pct=100.0 * float(np.mean(held[:, 2] / np.maximum(grown, 1))),
            kv_pages_full_mean=float(held[:, 0].mean()),
            kv_pages_window_mean=float(held[:, 1].mean()))
    if trace is not None:
        counters.update(attention_counters(srv, snaps, steps[snaps["step"]:]))
    slow = srv.slow_steps(steps, t0)

    # the sample, then the engine and its arena are let go: the reference's
    # activations need the room
    rng = np.random.default_rng(args.seed)
    with_tokens = [s for s in done if len(s.request.generated) > 0]
    pick = rng.choice(len(with_tokens), replace=False,
                      size=min(int(mix["check_requests"]), len(with_tokens)))
    samples = [(list(with_tokens[i].request.prompt), list(with_tokens[i].request.generated))
               for i in pick]
    attempted = len(done) + refused
    model, params = srv.model, srv.params
    srv.close()
    counters["memory_peak_bytes"] = memory_peak_bytes()
    del srv, done, with_tokens
    gc.collect()
    with ctx["phase"]("check"):
        check = (check_sample(model, params, cell.config["reference"], samples)
                 if samples else {"checked": 0, "wrong": 0, "largest": [0.0]})
    checked, wrong = check["checked"], check["wrong"]
    return {
        "correct": (wrong == 0 and short == 0 and checked > 0 and not ran_dry
                    and filled and refused == 0),
        "attempted": attempted, "failed": wrong + short + refused,
        "end_to_end": {"serve_tokens_per_s": tokens / span_s},
        "counters": counters, "trace": trace,
        "compared": compared(check, short, refused, ran_dry, filled,
                             LOGIT_MARGIN, NOISE_LIMIT),
        "notes": {"checked": checked, "wrong": wrong,
                  "largest_logit_gap": max(check["largest"]),
                  "logit_gaps": check["largest"], "tie_tolerance": LOGIT_MARGIN,
                  "noise_scale_median": check.get("noise_scale_median"),
                  "noise_scales": check.get("noise_scale"), "noise_limit": NOISE_LIMIT,
                  "mean_logit_gaps": check.get("mean"),
                  "not_the_references_best_share": check.get("share"),
                  "window_s": span_s, "tokens": tokens,
                  "backlog_ran_dry": ran_dry, "queue_left": queue_left,
                  "cohort_filled": filled,
                  "slow_steps": slow},
    }
