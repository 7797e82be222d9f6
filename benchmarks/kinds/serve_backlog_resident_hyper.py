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
  ``readers/paged_gqa.py:work`` hands ``step_mfu_pct``, and as reads and
  pairs to the key, which ``readers/paged_mla.py`` holds the latent kernel's
  time to (the resident kind's count is the same by whole pages);
* this stack's layers listed in ``BENCHMARK.json`` since PR 68: the shares of
  the three ``hc_*`` scopes and of the attention kernel's region entries of
  their own, ``hc_mix_bytes_pct.gen`` (``readers/xing4.py``), the cell
  appended to the two kernels' rooflines and to the bank's, the shared
  expert's, the dense lead's and the head's shares;
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
from benchmarks.lib import arith_mla, arith_xing4, resident_stack
from benchmarks.lib.build import jax_seed
from benchmarks.lib.cells import BenchmarkError, resolve

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
judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def attention_counters(srv, snaps, steps):
    """What the cache cost between two snapshots, from the lengths alone
    (``resident.rows_between``): each request's decode steps in between a
    single-query row at its own position in every layer, its prompt tokens
    the chunks they ran as (a chunk's keys once for all its queries), to the
    key.  ``paged_gqa_*`` for ``step_mfu_pct``; ``attention_keys_read`` and
    ``attention_key_products`` for the latent kernel's roofline
    (``readers/paged_mla.py``)."""
    mcfg = srv.model.cfg
    decode, chunks = resident.rows_between(srv, snaps)
    rows = resident.row_counters(srv, steps, decode, chunks)
    read, pairs = arith_xing4.latent_keys(decode, chunks, mcfg.n_layer)
    flops, nbytes = arith_mla.latent_attention(
        read, pairs, mcfg.n_layer * rows["attention_rows_live"], mcfg.n_head,
        mcfg.kv_lora_rank, mcfg.qk_rope_dim, srv.params["wte"].dtype.itemsize)
    return dict(rows, paged_gqa_flops=flops, paged_gqa_bytes=nbytes,
                attention_keys_read=read, attention_key_products=pairs)


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
    held to the reference's."""
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
