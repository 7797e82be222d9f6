"""Traffic kind ``train-fixed-seq``: whole optimizer steps on batches of one
sequence length, a new seeded batch each step with the input pipeline inside
the window.

The window follows PR 21's checked timer (``bench.py:_window_timer``): the
host clock from the first dispatch to the one fetch that ends the last step,
no device sync in between.  So that the window can end by the clock, at most
``in_flight`` steps are dispatched ahead: before step ``i`` is dispatched the
host waits for the loss of step ``i - in_flight``, which never lets the
device's queue run empty (the device was idle 0.2% of a window so driven, my
chip run PR 23).  ``train_tokens_per_s`` counts the whole steps and divides by
the time they spanned, not by ``--seconds``.
"""

import collections
import math
import time

import numpy as np

from benchmarks.lib import arith, draws
from benchmarks.lib.build import jax_seed, model_from
from benchmarks.lib.cells import resolve
from benchmarks.lib.device import memory_peak_bytes, span

END_TO_END = ("train_tokens_per_s",)
# first loss near ln(vocab): ln(50257) = 10.82, plus the spread of the
# initialisation (after chip_smoke.LN_VOCAB_BAND, scaled to the vocabulary)
FIRST_LOSS_BAND = (-0.55, 0.7)


def reference_loss(engine, config, inputs, labels):
    """Mean next-token loss of the plain float32 reference on the WHOLE first
    batch, at the engine's initial weights.  One sequence per chip at a time:
    ``[micro, chips, seq]`` mapped over ``micro``, the chips' sequences side
    by side; a layer's weights are made whole only while that layer runs, so
    GPT-2 1.5B's float32 copy never exists at once."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = engine.mesh
    n = mesh.devices.size
    ref = config["reference"]
    loss_sum, kw = resolve(ref["loss_sum"]), ref["kwargs"]
    whole = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(tuple(mesh.axis_names), None))
    gather = lambda p: jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, whole), p)
    S = inputs.shape[-1]
    fold = lambda a: np.ascontiguousarray(
        a.reshape(n, -1, S).transpose(1, 0, 2))        # [micro, chips, seq]
    ids, lab = fold(inputs), fold(labels)

    def total(params, ids, lab):
        def one(xy):
            x, y = (jax.lax.with_sharding_constraint(a, rows) for a in xy)
            return jax.vmap(lambda a, b: loss_sum(
                params, a, b, gather=gather, **kw))(x, y).sum()
        return jax.lax.map(one, (ids, lab)).sum() / ids.size

    place = NamedSharding(mesh, P(None, tuple(mesh.axis_names), None))
    out = jax.jit(total)(engine.state.params, jax.device_put(ids, place),
                         jax.device_put(lab, place))
    return float(out)


def run(cell, args, ctx):
    import deepspeed_tpu
    cfg, mix = cell.config, cell.traffic
    chips, seq = ctx["device"]["count"], int(mix["seq"])
    micro = int(mix["micro_per_chip"])
    model = model_from(cfg, cfg["train"].get("model_kwargs"))
    vocab = model.cfg.vocab_size
    ds = dict(cfg["train"]["ds_config"], train_micro_batch_size_per_gpu=micro)
    with ctx["phase"]("weights"):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=ds, seed=jax_seed(args.seed))
    batches = draws.ZipfBatches(args.seed, vocab, micro * chips, seq)
    first = batches()
    with ctx["phase"]("reference"):
        ref_loss = reference_loss(engine, cfg, first[0][0], first[1][0])
    with ctx["phase"]("compile_or_load"):
        losses = [float(engine.train_batch(batch=first))]
    with ctx["phase"]("warmup"):
        for _ in range(int(mix["warmup_steps"]) - 1):
            losses.append(engine.train_batch(batch=batches()))
        losses = [float(l) for l in losses]

    in_flight = int(mix["in_flight"])
    tracer, trace = ctx["tracer"], None
    trace_at = args.seconds - ctx["trace_seconds"]
    pending, steps = collections.deque(), 0
    ctx["compiles"].mark()
    t0 = time.perf_counter()
    ctx["setup_done"](t0)
    while time.perf_counter() - t0 < args.seconds:
        if tracer and not tracer.on and time.perf_counter() - t0 >= trace_at:
            tracer.start()
        with span("bench.next_batch"):
            batch = batches()
        with span("bench.train_batch"):
            loss = engine.train_batch(batch=batch)
        steps += 1
        pending.append(loss)
        if len(pending) > in_flight:
            with span("bench.wait_step"):
                pending.popleft().block_until_ready()
    with span("bench.fetch"):
        last = float(loss)
    t1 = time.perf_counter()
    if tracer and tracer.on:
        trace = tracer.stop()
    compiles = ctx["compiles"].in_window()

    tokens = steps * micro * chips * seq
    lo, hi = (math.log(vocab) + d for d in FIRST_LOSS_BAND)
    tol = float(mix["loss_tolerance"])
    checks = {
        "losses_finite": all(math.isfinite(l) for l in losses + [last]),
        "first_loss_near_ln_vocab": lo < losses[0] < hi,
        "loss_fell": last < losses[0] - tol,
        "first_loss_agrees_with_reference":
            abs(losses[0] - ref_loss) <= float(mix["reference_tolerance"]),
    }
    mcfg = model.cfg
    n_params = arith.gpt2_param_count(mcfg.n_embd, mcfg.n_layer,
                                      mcfg.padded_vocab, mcfg.n_positions)
    engine.close()
    return {
        "correct": all(checks.values()),
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": tokens / (t1 - t0)},
        "counters": {
            "compiles_in_window": compiles,
            "train_step_ms": 1e3 * (t1 - t0) / steps,
            "tokens_per_s": tokens / (t1 - t0),
            "flops_per_token": arith.train_flops_per_token(
                n_params, mcfg.n_layer, mcfg.n_embd, seq),
            "memory_peak_bytes": memory_peak_bytes(),
            "flash_shape": {"batch": micro, "heads": mcfg.n_head, "seq": seq,
                            "head_dim": mcfg.head_dim},
        },
        "trace": trace,
        "compared": {"first_loss_minus_reference_abs": [abs(losses[0] - ref_loss),
                                                        float(mix["reference_tolerance"])],
                     "first_loss_above_ln_vocab": [losses[0] - math.log(vocab),
                                                   list(FIRST_LOSS_BAND)],
                     "loss_fall_at_least": [losses[0] - last, tol]},
        "notes": {"checks": checks, "warmup_losses": losses, "last_loss": last,
                  "reference_first_loss": ref_loss,
                  "first_loss_minus_reference": losses[0] - ref_loss,
                  "steps": steps, "window_s": t1 - t0, "n_params": n_params},
    }
