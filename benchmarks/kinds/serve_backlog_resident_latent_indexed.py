"""Traffic kind ``serve-backlog-resident-latent-indexed``: ``serve-backlog-
resident`` as it stands (its plan, its fill, its window and its check of the
sample against one full pass of the plain reference are that module's, called
through ``lib/resident_stack.py``, not copied) for a stack whose every layer
is latent attention under a lightning indexer (DeepSeek-V3.2-Exp: the indexer
reads the query's latent, scores every cached token's index key, and the 128
heads attend the 2,048 rows of the latent cache it scores highest), with

* the caches' work counted for THAT stack (:func:`attention_counters` over
  ``lib/arith_deepseek_v32.py``): the index keys a row scored and the cached
  vectors it chose, whatever implements either, under the counter names
  ``serve_backlog_resident_indexed`` leaves (``index_*``, ``indexed_*``), so
  that the metrics of ``readers/keye_vl2.py``, which name no model, read them;
* this stack's layers listed in ``BENCHMARK.json`` since PR 68 (the shares of
  ``latent_project`` and ``route_groups`` entries of their own, the cell
  appended to the bank's, the shared expert's and the held assignments');
* the two LIMITS of the comparison that decides ``correct`` found on this
  model, and the controls they were read against (:data:`PLANTED`: ``--set
  planted='"weights-float8"'`` serves every matrix rounded through
  ``float8_e4m3fn``, ``'"index-rope-interleaved"'`` the indexer's rope in the
  attention's pairing: a wrong SELECTION, every product right).

Why this model needs limits of its own (PERF.md § 6, PR 61): as Keye-VL-2.0's
(``serve_backlog_resident_indexed``), the scores around the 2,048th place lie
close and a token swaps there on bf16's own rounding; beside it the sigmoid
router's eighth and ninth expert swap, of which this chip holds a sixteenth.

* ``LOGIT_MARGIN``: the GROSS limit on every served token's gap.
* ``NOISE_LIMIT``: the limit on precision, on the MEDIAN over the run's
  checked requests of the noise scale, as in the resident kind.

Both readings a limit lies between are in PERF.md § 6.
"""

import contextlib
import functools

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import arith_deepseek_v32 as arith_ds
from benchmarks.lib import resident_stack
from benchmarks.lib.build import jax_seed

END_TO_END = resident.END_TO_END
# 1.87 times the largest a bf16 run has read (2.68 over the 24 requests of
# twelve runs of the cell; a request's largest 0.87-2.68; 0.78 on ``serve_parity``'s
# sequences), three quarters of what a token unrelated to the reference loses
# by (the best of 16,160 logits of deviation 1.7 less a random one: 6.7).
# Every matrix through float8 reads 3.33 and 4.45 and passes it; the indexer's
# rope in the wrong pairing reads 9.63 on both requests (99.8% of the served
# positions are not the reference's best: a wrong selection is another model).
LOGIT_MARGIN = 5.0
# bf16 runs read medians of 0.456-0.578 (a request 0.438-0.629; twelve runs).  The
# program in FLOAT32 reads 0.0 at 30,000 positions (``tools/serve_parity.py``),
# so this is bf16's own: the scores round the 2,048th place swap on the
# rounding of the activations the indexer reads, a quarter of the positions
# serve the reference's second best, and the logits are plain.  1.38 times the
# largest median; both controls are past every scale (999.99: more tokens
# flipped, 76-78% and 99.8%, than any noise explains).
NOISE_LIMIT = 0.8
judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def attention_counters(srv, snaps, steps):
    """What the caches cost between two snapshots, from the lengths alone
    (``resident.rows_between``): each request's decode steps in between a
    single-query row at its own position in every layer, its prompt tokens
    chunks of one sequence.
    ``index_*`` is the scores' part, ``indexed_attend_*`` the chosen rows'
    (``readers/keye_vl2.py:scope_roofline``); ``paged_gqa_*``, the names under
    which the resident kind leaves "the cache's reads" for ``step_mfu_pct``
    (``readers/paged_gqa.py:work``), is ALL of it here."""
    mcfg = srv.model.cfg
    ix = arith_ds.indexer_of(srv.cell.config["model"]["kwargs"])
    decode, chunks = resident.rows_between(srv, snaps)
    positions = resident.live_positions(decode, chunks)
    itemsize = srv.params["wte"].dtype.itemsize
    s_flops, s_bytes = arith_ds.score_rows(decode, chunks, mcfg.n_layer, ix, itemsize)
    a_flops, a_bytes = arith_ds.attend_rows(
        decode, chunks, mcfg.n_layer, mcfg.n_head, mcfg.kv_lora_rank,
        mcfg.qk_rope_dim, ix, itemsize)
    resident_keys = int((positions + 1).sum()) * mcfg.n_layer
    return dict(resident.row_counters(srv, steps, decode, chunks),
                index_flops=s_flops, index_bytes=s_bytes,
                indexed_attend_flops=a_flops, indexed_attend_bytes=a_bytes,
                paged_gqa_flops=s_flops + a_flops, paged_gqa_bytes=s_bytes + a_bytes,
                index_keys_scored=resident_keys, indexed_keys_resident=resident_keys,
                indexed_keys_attended=int(arith_ds.keys_attended(positions, ix).sum())
                * mcfg.n_layer)


# ---- the controls: what the limits must refuse ------------------------------------- #
def _index_rope_interleaved():
    """The indexer's queries and keys rotated in the ATTENTION's pairing,
    (0,1), (2,3), ..., where the model pairs lane ``i`` with ``i + 32``: the
    indexer's is the one call of ``apply_rope`` that names its lanes."""
    from deepspeed_tpu.models import gpt
    real = gpt.apply_rope

    def wrong(x, positions, theta=10000.0, rope_dim=None, interleaved=False, yarn=None):
        return real(x, positions, theta, rope_dim, interleaved or rope_dim is not None, yarn)
    return resident_stack.replaced(gpt, apply_rope=wrong)


def _weights_through(dtype):
    """Every matrix of the blocks served rounded through ``dtype``, IN PLACE
    (a rounded copy does not fit beside the arena): the tree the engine was
    built on is gone, and ``run`` makes the reference's weights again from
    the seed."""
    import jax
    import deepspeed_tpu
    real = deepspeed_tpu.init_serving
    # the barrier keeps a rounding: XLA takes a convert down and up again
    # inside one program for excess precision it may leave out
    low = lambda w: jax.lax.optimization_barrier(w.astype(dtype)).astype(w.dtype)
    matrix = lambda path: path[-1].key in ("wi", "wo", "wg") or path[-1].key.endswith("_w")
    rounded = jax.jit(lambda p: dict(p, blocks=jax.tree_util.tree_map_with_path(
        lambda path, w: low(w) if matrix(path) else w, p["blocks"])), donate_argnums=0)
    return resident_stack.replaced(deepspeed_tpu, init_serving=lambda model, params, config:
                                   real(model=model, params=rounded(params), config=config))


PLANTED = {None: contextlib.nullcontext,
           "weights-float8": functools.partial(_weights_through, "float8_e4m3fn"),
           "index-rope-interleaved": _index_rope_interleaved}


def run(cell, args, ctx):
    """``resident.run`` with this stack's count of the caches' work, its
    sample judged again by this module's limits."""
    fault = cell.traffic.get("planted")
    their_check = resident.check_sample

    def check(model, params, reference, samples):
        if fault == "weights-float8":                 # the served tree was donated
            import jax
            params = jax.jit(lambda key: jax.tree.map(
                lambda p: p.astype(cell.config["dtype"]), model.init_params(key)))(
                    jax.random.PRNGKey(jax_seed(args.seed)))
        # the reference's last layer runs the rows the check reads alone
        kw = dict(reference["kwargs"], rows_from=min(len(p) for p, _ in samples) - 1)
        return their_check(model, params, dict(reference, kwargs=kw), samples)

    with PLANTED[fault]():
        out = resident_stack.run(
            cell, args, ctx, logit_margin=LOGIT_MARGIN, noise_limit=NOISE_LIMIT,
            attention_counters=attention_counters, check_sample=check)
    if fault:
        out["notes"]["planted"] = fault
    return out
