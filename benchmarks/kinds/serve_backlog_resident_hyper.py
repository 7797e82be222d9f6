"""Traffic kind ``serve-backlog-resident-hyper``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window and its check of the sample
against one full pass of the plain reference are that module's, called
through ``lib/resident_stack.py``, not copied) for a stack of latent attention
over EVERY cached key and a whole expert bank on FOUR RESIDUAL STREAMS a
token, mixed by manifold-constrained hyper-connections round both sublayers
(Xing4.0), under traffic whose prompts set the step, with

* the cache's work counted for THAT traffic (:func:`attention_counters` over
  ``lib/arith_xing4.py``): every (query, key) pair's operations, a decode
  row's keys read once a row and a prompt chunk's ONCE for all its queries,
  under the names the resident kinds use (``paged_gqa_*``), which
  ``readers/paged_gqa.py:work`` hands ``step_mfu_pct``.  The resident kind's
  own count and ``readers/paged_mla.py`` read a chunk's keys once a TOKEN:
  right where a step is decode rows (Mistral's cell carries a chunk in 7% of
  its steps), 150% of a roofline here;
* this stack's layers in the traced line's ``notes.xing4_layers``
  (:func:`layer_notes`): ``BENCHMARK.json``'s ``per_layer`` is full (PERF.md
  § 7), so the shares of the three ``hc_*`` scopes, of the attention kernel's
  region, of the bank, the shared expert, the dense lead and the head, the
  two kernels' shares of their rooflines and ``hc_mix_bytes_pct`` (the time
  under the ``hc_*`` scopes against what ``lib/arith_xing4.py:mix_bytes``
  says the mixes must move) wait there for the ``benchmark`` PR that makes
  room;
* THREE limits on the comparison that decides ``correct``, found on this
  model, and the controls they were read against (:data:`PLANTED`: ``--set
  planted='"sinkhorn-1"'`` stops the Sinkhorn-Knopp projection after one
  iteration, ``'"maps-bfloat16"'`` computes the streams' maps in bf16 where
  the model computes them in float32, ``'"hpost-unscaled"'`` writes through
  ``sigmoid`` where the model writes through ``2 sigmoid``,
  ``'"weights-float8"'`` serves every matrix rounded through
  ``float8_e4m3fn``: all four must read ``correct`` false).  Two limits are
  the resident kind's, on the served tokens' LOGITS.  The third is on the
  streams' MAPS themselves (:func:`maps_gaps`): a bf16 program rounds the
  streams it carries by three times what maps computed in bf16 add to them,
  so nothing it serves or caches tells the maps' precision apart (PERF.md
  § 6, PR 66); before the first sublayer nothing is rounded yet, so there
  the program's maps (``gpt.hyper_maps``, the function its step calls, as
  the chip compiles it) are held to the reference's.

* ``LOGIT_MARGIN``: the GROSS limit on every served token's gap.
* ``NOISE_LIMIT``: the limit on precision, on the MEDIAN over the run's
  checked requests of the noise scale, as in the resident kind.
* ``MAPS_LIMIT``: on every checked request's largest ``|program -
  reference|`` over the entries of ``Hpre``, ``Hpost`` and ``Hres`` of the
  first sublayer.

The readings each limit lies between are in PERF.md § 6 (PR 66).
"""

import contextlib
import functools

import numpy as np

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds.serve_backlog_resident_latent_indexed import _weights_through
from benchmarks.lib import arith, arith_xing4, device, resident_stack
from benchmarks.lib.build import jax_seed
from benchmarks.lib.cells import BenchmarkError, resolve
from benchmarks.lib.serving import Serving
from benchmarks.readers import afmoe
from benchmarks.readers.program_spans import scope_share_pct

END_TO_END = resident.END_TO_END
# The GROSS limit on every served token's gap, a request at a time (the noise
# limit below is on a run's MEDIAN, so one request of garbage passes it): 1.53
# times the largest a sound bf16 request has read (4.25 over 116 requests of
# 29 runs; the next 3.29; a run's largest 1.47-4.25), 0.79 of the least a
# request of tokens UNRELATED to the reference reads (8.21-9.53 over eight).
# It refuses no planted control by itself (they read 1.84-5.22): where a
# fourth and a fifth expert swap on rounding a sound token loses what a wrong
# model's loses.  The readings of all three limits: PERF.md § 6 (PR 66).
LOGIT_MARGIN = 6.5
# The limit on precision, on the MEDIAN over the run's checked requests of
# the noise scale (``resident.noise_scale``).  The program in FLOAT32 reads
# 0.0 (``tools/serve_parity.py``), so what a bf16 run reads is bf16's own.
NOISE_LIMIT = 0.20
# The limit on the first sublayer's maps (:func:`maps_gaps`), float32 against
# float32: the program reads 8.3e-7 to 1.1e-6 on the chip (the order of a sum
# of 14,336 products), maps computed in bf16 5.0e-3 to 6.8e-3, the projection
# stopped after one iteration 0.33 to 0.42: 88 times the one, a fiftieth of
# the next.
MAPS_LIMIT = 1e-4
SCOPES = ("hc_coeff", "hc_pre", "hc_post", "attn", "attn_latent", "mlp", "lead_mlp",
          "moe", "moe_router", "moe_experts", "moe_shared", "head")
MIX_SCOPES = ("hc_coeff", "hc_pre", "hc_post")
KERNEL = "paged_mla_attention"

judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def attention_counters(srv, snaps, steps):
    """What the cache cost between two snapshots, from the lengths alone:
    each request's decode steps in between a single-query row at its own
    position in every layer, its prompt tokens the chunks they ran as (a
    chunk's keys once for all its queries)."""
    mcfg = srv.model.cfg
    decode, chunks = [], []
    for rid, (plen, res1, gen1) in snaps["after"].items():
        _, res0, gen0 = snaps["before"].get(rid, (plen, 0, 0))
        if gen0 == 0 and res0 < plen:                 # prompt chunks run
            end = min(res1, plen)
            chunks += [(first, min(srv.chunk, end - first))
                       for first in range(res0, end, srv.chunk)]
        d = max((gen1 - gen0) - (1 if gen0 == 0 and gen1 > 0 else 0), 0)
        decode.append(np.arange(res1 - d, res1))
    decode = np.concatenate(decode) if decode else np.zeros(0, np.int64)
    live = len(decode) + sum(n for _, n in chunks)
    ran = [st for st in steps if st[2] > 0 or st[3] > 0]
    flops, nbytes = arith_xing4.latent_rows(
        decode, chunks, mcfg.n_layer, mcfg.n_head, mcfg.kv_lora_rank,
        mcfg.qk_rope_dim, srv.params["wte"].dtype.itemsize)
    return {"paged_gqa_flops": flops, "paged_gqa_bytes": nbytes,
            "attention_rows_live": live, "attention_chunks": len(chunks),
            "attention_rows_idle": max(len(ran) * (srv.slots + srv.chunk) - live, 0),
            "traced_step_rows": Serving.step_rows(steps)}


def kernel_roofline(run):
    """The least time for :func:`attention_counters`' operations and bytes
    over the attention kernel's time in the traced stretch."""
    t, c = run["trace"], run["counters"]
    took = t.op_seconds().get(KERNEL)
    if not took or "paged_gqa_bytes" not in c:
        return None
    bound_s, which = arith.roofline_seconds(
        c["paged_gqa_flops"], c["paged_gqa_bytes"], run["peaks"])
    run["notes"].setdefault("roofline_bound", {})[KERNEL] = which
    return 100.0 * bound_s / took


def mix_bytes_pct(run):
    """The time the chip's memory needs for what the mixes of the traced
    stretch's steps must move (``arith_xing4.mix_bytes`` over the live rows
    of the steps whose programs the device's trace holds) over the time
    under the three ``hc_*`` scopes there."""
    import jax.numpy as jnp
    t, cfg = run["trace"], run["cell"].config
    share = scope_share_pct(run, list(MIX_SCOPES))
    rows = [r for r in run["counters"].get("traced_step_rows", ()) if r > 0]
    held = t.program_runs()
    kept = rows[-held:] if held else rows
    if not share or not kept:
        return None
    kw = cfg["model"]["kwargs"]
    nbytes = arith_xing4.mix_bytes(sum(kept), len(kept), kw["n_layer"], kw["hyper"][0],
                                   kw["n_embd"], jnp.dtype(cfg["dtype"]).itemsize)
    return 100.0 * (nbytes / run["peaks"]["hbm_bytes_per_s"]) / (share / 100.0 * t.busy_s())


def layer_notes(run):
    """What the traced stretch says of this stack's layers: the share of the
    device's busy time under each of :data:`SCOPES`, the two kernels' shares
    of their rooflines and the mixes' time against their bytes.  {} without a
    trace."""
    if run["trace"] is None:
        return {}
    out = {f"{scope}_share_pct": scope_share_pct(run, [scope]) for scope in SCOPES}
    out.update(paged_mla_attention_roofline=kernel_roofline(run),
               grouped_matmul_roofline=afmoe.grouped_matmul_roofline(run),
               hc_mix_bytes_pct=mix_bytes_pct(run))
    return out


def maps_gaps(model, params, reference, samples):
    """A checked request's largest ``|program - reference|`` over the entries
    of the FIRST sublayer's ``Hpre``, ``Hpost`` and ``Hres``, at as many of
    its prompt's first positions as the shortest checked prompt has.  The
    streams enter as the embedding copied, so nothing rounded lies before
    these maps: the program's come from ``gpt.hyper_maps`` as the step calls
    it (looked up when traced: inside a control, the planted one), the
    reference's from ``reference["first_maps"]``."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt
    cfg = model.cfg
    rows = min(len(prompt) for prompt, _ in samples)

    @jax.jit
    def gap(params, ids):
        x = gpt._embed(cfg, params["wte"], ids, params["wte"].dtype)
        first = {k: v[0] for k, v in params["blocks"].items() if k.startswith("hc_attn_")}
        got = gpt.hyper_maps(cfg, first, "attn", jnp.repeat(x[:, None], cfg.hyper.streams, 1))
        want = resolve(reference["first_maps"])(params, ids, **reference["kwargs"])
        return jnp.stack([jnp.abs(g.astype(jnp.float32) - w).max()
                          for g, w in zip(got, want)]).max()
    return [float(gap(params, jnp.asarray(np.asarray(prompt[:rows], np.int32))))
            for prompt, _ in samples]


# ---- the controls: what the limits must refuse ------------------------------------- #
def _sinkhorn_stopped_after(iters):
    """The projection of ``Hres`` stopped after ``iters`` iterations: rows
    that sum to 1 and columns that do not yet."""
    from deepspeed_tpu.models import gpt
    real = gpt.sinkhorn_knopp
    return resident_stack.replaced(
        gpt, sinkhorn_knopp=lambda m, _, eps: real(m, iters, eps))


def _hpost_unscaled():
    """A sublayer's output written through ``sigmoid(Hpost~)`` where the
    model writes it through ``2 sigmoid(Hpost~)``: a wrong model, every
    product right."""
    from deepspeed_tpu.models import gpt
    real = gpt.hyper_write
    return resident_stack.replaced(
        gpt, hyper_write=lambda x, maps, f: real(x, (maps[0], 0.5 * maps[1]), f))


def _maps_in(dtype):
    """The streams' maps computed in ``dtype`` throughout (the norm over all
    lanes, the product with ``phi``, the sigmoids, ``exp`` and
    Sinkhorn-Knopp) where the model computes them in float32."""
    from deepspeed_tpu.models import gpt
    return resident_stack.replaced(
        gpt, hyper_maps=functools.partial(gpt.hyper_maps, compute=dtype))


PLANTED = {None: contextlib.nullcontext,
           "sinkhorn-1": functools.partial(_sinkhorn_stopped_after, 1),
           "hpost-unscaled": _hpost_unscaled,
           "maps-bfloat16": functools.partial(_maps_in, "bfloat16"),
           "weights-float8": functools.partial(_weights_through, "float8_e4m3fn")}


def run(cell, args, ctx):
    """``resident.run`` with this traffic's count of the cache's work, its
    sample judged again by this module's limits, the first sublayer's maps
    held to the reference's, and the layers' notes."""
    try:        # a program without this family (a parent commit) says so at once
        resolve(cell.config["model"]["config"])
    except AttributeError as e:
        raise BenchmarkError(f"the program in this checkout cannot build {cell.config_name}: {e}")
    gaps = []
    fault = cell.traffic.get("planted")
    their_check = resident.check_sample

    def check(model, params, reference, samples):
        if fault == "weights-float8":                 # the served tree was donated
            import jax
            params = jax.jit(lambda key: jax.tree.map(
                lambda p: p.astype(cell.config["dtype"]), model.init_params(key)))(
                    jax.random.PRNGKey(jax_seed(args.seed)))
        gaps.extend(maps_gaps(model, params, reference, samples))
        return their_check(model, params, reference, samples)

    with PLANTED[fault]():
        out = resident_stack.run(
            cell, args, ctx, logit_margin=LOGIT_MARGIN, noise_limit=NOISE_LIMIT,
            attention_counters=attention_counters, check_sample=check)
    notes = out["notes"]
    if out.get("trace") is not None:
        notes["xing4_layers"] = layer_notes(dict(
            out, cell=cell, peaks=device.peaks(ctx["device"]["kind"])))
    if fault:
        notes["planted"] = fault
    if not notes["checked"]:
        return out
    wrong = sum(g > MAPS_LIMIT for g in gaps)
    notes.update(first_maps_gaps=gaps, maps_limit=MAPS_LIMIT, maps_wrong=wrong)
    out["compared"].update(first_maps_gap=[max(gaps), MAPS_LIMIT],
                           requests_whose_maps_are_wrong=[wrong, 0])
    out.update(failed=out["failed"] + wrong, correct=bool(out["correct"] and wrong == 0))
    return out
