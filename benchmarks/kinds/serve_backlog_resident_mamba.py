"""Traffic kind ``serve-backlog-resident-mamba``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window and its check of the sample
against one full pass of the plain reference are that module's, called, not
copied; what the kinds that call it share is ``lib/resident_stack.py``) for a
stack of Mamba-1 layers among multi-query attention layers (the Jamba
family), with

* the caches' work counted for THAT stack (:func:`attention_counters` over
  ``lib/arith_jamba.py``, in the place of the resident kind's, which counts
  pages in every layer): the pages a live row's two full layers read, the
  states its mamba layers read and write, the convolution states beside them;
* THREE limits on the comparison that decides ``correct``.  Two are the
  resident kind's, on the served tokens' LOGITS, found on this model by
  ``serve_backlog_resident_delta``'s method: bf16 as served on one side of
  each, every matrix through float8 on the other.  The third is on the
  mamba layers' STATE itself (:func:`state_gaps`): when the window closes, of
  ``check_requests`` slots still decoding (drawn by the seed) the first mamba
  layer's float32 state is held to the one the reference reaches over the
  same tokens.  That layer's input is the embedding, so what parts the two
  is the layer's own arithmetic and not 27 layers' rounding carried along; a
  state kept in bf16 (a decay of 0.999 a token rounds to none, so the slow
  channels stall), a state not carried into a chunk, a slot not started from
  zero all land on the far side of ``STATE_LIMIT``, where the logits' noise
  scale sees the first of them late or not at all (PERF.md section 6, PR 57);
* the CONTROLS those limits were read against, planted by ``--set
  planted='"<name>"'`` (:data:`PLANTED`): each must come out not correct.

What the trace says of the mamba layers is listed in ``BENCHMARK.json`` since
PR 68 (the four scopes' shares, the two kernels' rooflines over
``readers/jamba.py``, the states moved a step).

The readings each limit lies between are in PERF.md section 6 (PR 57).
"""

import contextlib
import functools
import statistics

import numpy as np

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import arith_jamba, resident_stack
from benchmarks.lib.build import jax_seed
from benchmarks.lib.cells import resolve

END_TO_END = resident.END_TO_END
# The GROSS limit on every served token's gap.  1.9 times the largest a bf16
# run has read as served (0.157-0.240 a run over 24 runs of the cell, a seed
# each), 0.71 of the least a REQUEST reads when a chunk forgets its
# carried state (0.63-3.49: 4 of 4 wrong) and a fifth of the least with every
# matrix through float8_e4m3fn (2.11-2.50).  A bf16 state reads 0.27-0.63 a
# run (over this limit in two runs of three): this limit is for a cache of garbage, not for precision.
LOGIT_MARGIN = 0.45
# The limit on precision, on the MEDIAN over the run's checked requests of
# the noise scale (``resident.noise_scale``).  bf16 runs as served read
# medians of 0.068-0.082 over those runs (a request 0.058-0.092: the scale is
# solved from a count of flipped positions, so it scatters by a tenth on its own); a chunk that
# forgets its carried state reads 0.175 (a request 0.129-0.401), every matrix
# through float8 is past every scale (999.99).  1.47 times the one, 0.69 of
# the other.  THE STATE KEPT IN bf16 IS NOT TOLD APART HERE: it read medians of
# 0.085, 0.145 and 0.101 (three seeds; 0.086 with the taps seeded at 0.02),
# two of them under this limit.  ``STATE_LIMIT`` is for that.
NOISE_LIMIT = 0.12
# The limit on the first mamba layer's state, on the MEDIAN over the kept
# slots of ``|served - reference| / |reference|`` (Frobenius, the layer's
# whole [16, 5120]).  bf16 runs as served read medians of 0.0019-0.0041
# (a slot 0.0018-0.0043; the layer's input is the embedding, so this is the
# rounding of ITS in_w, convolution, x_w and dt_w products in bf16, and it
# hardly moves with the seed); the state kept in bf16 reads 0.0143, 0.0298 and
# 0.0169 (three seeds; a slot 0.0080-0.0349; 0.0196 with the taps seeded at 0.02): a
# decay of 0.999 a token rounds to none, so the slow channels stall.  1.6
# times the one, 0.45 of the other's least.  Every matrix through float8
# reads 0.047.  A chunk that forgets its state reads 0.0069 (a slot 0.0036-
# 0.074): 1,000 decoded tokens on most of the state is forgotten either way,
# and the two limits on logits are what refuse THAT fault.
STATE_LIMIT = 0.0065


judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def attention_counters(srv, snaps, steps):
    """What the caches cost between two snapshots, from the lengths alone
    (``resident.rows_between``): each request's decode steps in between a
    single-query row at its own position in every full layer, its prompt
    tokens the chunks they ran as (a chunk's pages once a chunk); a mamba
    layer's state and convolution state moved once a decode row and once a
    prompt chunk.  ``paged_gqa_*``, the names under which the resident kind
    leaves "the cache's reads" for ``step_mfu_pct``
    (``readers/paged_gqa.py:work``), is ALL of it here: the pages, the states
    and the convolution states.  ``traced_step_decode_rows`` and
    ``traced_step_chunk_tokens`` are the live decode rows and the prompt
    tokens of each step that ran a program: what the two kernels of
    ``ops/pallas/selective_scan.py`` worked on, a layer
    (``readers/jamba.py``)."""
    kw = srv.cell.config["model"]["kwargs"]
    decode, chunks = resident.rows_between(srv, snaps)
    rows = resident.row_counters(srv, steps, decode, chunks)
    moves = len(decode) + len(chunks)
    ran = [st for st in steps if st[2] > 0 or st[3] > 0]
    kinds = arith_jamba.layer_kinds(kw)
    n_full, n_mamba = kinds.count("full"), kinds.count("mamba")
    itemsize = srv.params["wte"].dtype.itemsize
    flops, nbytes = arith_jamba.full_rows(decode, chunks, n_full, srv.block, kw, itemsize)
    m_flops, state, conv = arith_jamba.mamba_rows(
        rows["attention_rows_live"], moves, n_mamba, kw, itemsize)
    return dict(rows, paged_gqa_flops=flops + m_flops, paged_gqa_bytes=nbytes + state + conv,
                full_pages_bytes=nbytes, mamba_state_bytes_moved=state,
                mamba_conv_bytes_moved=conv, mamba_state_moves=moves * n_mamba,
                traced_step_decode_rows=[int(st[2]) for st in ran],
                traced_step_chunk_tokens=[int(st[3]) for st in ran])


def slots_kept(engine, k, seed):
    """Of the slots still decoding once the engine has landed every program
    it launched, ``k`` drawn by the seed: (the tokens the slot's states have
    taken in, every mamba layer's state of that slot ``[layers, S, N]`` as
    float32)."""
    decoding = [(slot, req) for slot, req in sorted(engine.sched.active.items())
                if req.prefilled >= len(req.prompt) and req.generated]
    pick = np.random.default_rng(seed).choice(
        len(decoding), replace=False, size=min(k, len(decoding)))
    states = engine._aux["mamba_state"]
    return [(np.asarray(req.context[:req.prefilled], np.int32),
             np.asarray(states[:, slot].astype(np.float32)))
            for slot, req in (decoding[i] for i in sorted(pick))]


def state_gaps(params, reference, kept):
    """A kept slot's ``|served - reference| / |reference|`` a mamba layer
    (Frobenius over the layer's whole state), the reference's states from
    ONE full pass over the slot's tokens (``reference["states"]``).  ->
    ``[slots kept, mamba layers]``."""
    import jax
    import jax.numpy as jnp
    kw = reference["kwargs"]
    q_block = int(kw.get("q_block", 1024))
    padded = -(-max(len(ids) for ids, _ in kept) // q_block) * q_block
    states_of = jax.jit(lambda p, ids, n: resolve(reference["states"])(p, ids, n, **kw))
    out = []
    for ids, served in kept:
        seq = np.zeros(padded, np.int32)
        seq[:len(ids)] = ids
        want = np.asarray(states_of(params, jnp.asarray(seq), len(ids)), np.float64)
        diff = served.transpose(0, 2, 1) - want                  # the program keeps [S, N]
        out.append(np.sqrt((diff ** 2).sum((1, 2)) / (want ** 2).sum((1, 2))))
    return np.asarray(out)


# ---- the controls: what the limits must refuse ------------------------------------- #
def _state_in(dtype):
    """The mamba layers' state kept in ``dtype`` (both kernels of
    ``ops/pallas/selective_scan.py`` take a bfloat16 state for this)."""
    from deepspeed_tpu.models import hybrid
    real = hybrid.init_aux
    return resident_stack.replaced(hybrid, init_aux=lambda *a: {
        name: leaf.astype(dtype if name == "mamba_state" else leaf.dtype)
        for name, leaf in real(*a).items()})


def _chunk_forgets_state():
    """A prompt chunk scanned from zero whatever state its slot carries."""
    from deepspeed_tpu.ops.pallas import selective_scan
    real = selective_scan.mamba_chunk_scan
    return resident_stack.replaced(
        selective_scan, mamba_chunk_scan=lambda h, *a: real(h * 0, *a))


def _weights_through(dtype):
    """Every matrix of the blocks served rounded through ``dtype``, IN PLACE
    (a rounded copy does not fit beside the arena and the states): the tree
    the engine was built on is gone, and ``run`` makes the reference's
    weights again from the seed."""
    import jax
    import deepspeed_tpu
    real = deepspeed_tpu.init_serving
    # the barrier keeps a rounding: XLA takes a convert down and up again
    # inside one program for excess precision it may leave out
    low = lambda w: jax.lax.optimization_barrier(w.astype(dtype)).astype(w.dtype)
    rounded = jax.jit(lambda p: dict(p, blocks=jax.tree_util.tree_map_with_path(
        lambda path, w: low(w) if path[-1].key.endswith("_w") else w, p["blocks"])),
        donate_argnums=0)
    return resident_stack.replaced(deepspeed_tpu, init_serving=lambda model, params, config:
                                   real(model=model, params=rounded(params), config=config))


PLANTED = {None: contextlib.nullcontext,
           "state-bfloat16": functools.partial(_state_in, "bfloat16"),
           "chunk-forgets-state": _chunk_forgets_state,
           "weights-float8": functools.partial(_weights_through, "float8_e4m3fn")}


def run(cell, args, ctx):
    """``resident.run`` with this stack's count of the caches' work, its
    sample judged again by this module's limits, the kept slots' states held
    to the reference's."""
    kept, gaps = [], []
    fault = cell.traffic.get("planted")
    their_check = resident.check_sample

    class Keeping(resident.Resident):
        def close(self):
            super().close()            # every launched program's row has landed
            kept.extend(slots_kept(self.engine, int(cell.traffic["check_requests"]),
                                   args.seed))

    def check(model, params, reference, samples):
        if fault == "weights-float8":                 # the served tree was donated
            import jax
            params = jax.jit(lambda key: jax.tree.map(
                lambda p: p.astype(cell.config["dtype"]), model.init_params(key)))(
                    jax.random.PRNGKey(jax_seed(args.seed)))
        if kept:
            gaps.extend(state_gaps(params, reference, kept))
        return their_check(model, params, reference, samples)

    with PLANTED[fault]():
        out = resident_stack.run(
            cell, args, ctx, logit_margin=LOGIT_MARGIN, noise_limit=NOISE_LIMIT,
            attention_counters=attention_counters, Resident=Keeping, check_sample=check)
    notes = out["notes"]
    if fault:
        notes["planted"] = fault
    if not notes["checked"]:
        return out
    first = [float(g[0]) for g in gaps]
    median = statistics.median(first) if first else None    # no slot decoding: not correct
    wrong = sum(g > STATE_LIMIT for g in first) if first and median > STATE_LIMIT else 0
    notes.update(state_gaps_first_layer=first, state_gap_median=median,
                 state_limit=STATE_LIMIT, states_wrong=wrong,
                 state_gap_a_layer=[float(g) for g in np.median(gaps, axis=0)] if gaps else [])
    out["compared"].update(mamba_state_gap_median=[median, STATE_LIMIT],
                           slots_whose_state_is_wrong=[wrong, 0])
    out.update(failed=out["failed"] + wrong,
               correct=bool(out["correct"] and first and wrong == 0))
    return out
