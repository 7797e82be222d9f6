"""One ``gpt_paged_step`` of an MoE family as the program runs it, the
stacked expert bank ``[L, experts, ...]`` handed to ``grouped_matmul`` whole
with the layer's index, against the SAME step with each layer's bank sliced
out of the stack by hand and handed over as one layer's: the two read the
same numbers, so logits, arena and expert counts are equal bit for bit.
Shared by ``test_olmoe.py``, ``test_smallthinker.py``, ``test_mistral4.py``
and ``test_trinity.py`` (a period of one layer, of four, one with ``held``
experts, and one behind a dense lead)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt import GPT
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.serving.kv_cache import init_arena

PATHS = ["kernel", "ragged_dot"]


def bank_in_place_equals_bank_sliced(cfg, params, path, kernels, monkeypatch,
                                     rows=6, vocab=500):
    """``path``: the kernel through the interpreter (``cfg``'s widths must
    be ones it takes), or ``ragged_dot``.  Every third row carries no
    request; the others write block ``row + 1`` of every layer group."""
    kernels(*(["grouped_matmul"] if path == "kernel" else []))
    model, P, BS = GPT(cfg), len(cfg.pattern), 8
    live = np.arange(rows) % 3 != 2
    ids = jax.random.randint(jax.random.PRNGKey(7), (rows, 1), 0, vocab)
    table = np.zeros((rows, 4), np.int32)
    table[:, 0] = (np.arange(rows) + 1) * live
    write = jnp.asarray(table[:, :1])
    args = (params, ids, jnp.zeros((rows,), jnp.int32),
            *init_arena(cfg, rows + 1, BS, dtype=jnp.float32),
            (jnp.asarray(table),) * P, (write,) * P, jnp.zeros((rows, 1), jnp.int32))
    step = lambda: jax.jit(functools.partial(model.paged_step,
                                             with_expert_counts=True))(*args)
    calls = []              # (the weight operand's shape, kernel or not)
    real_call, real_ragged, real = gm._call, jax.lax.ragged_dot, gm.grouped_matmul
    monkeypatch.setattr(gm, "_call", lambda a, w, s, layer: calls.append(
        (w.shape, True)) or real_call(a, w, s, layer))
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda a, w, s: calls.append(
        (w.shape, False)) or real_ragged(a, w, s))
    in_place = step()
    stack = params["blocks"]["moe"]["experts"]["wi"].shape
    # the bank's layers as traced: ONE, whatever walks them (the scan's
    # body, or behind a dense lead the loop before it): a block's tail is a
    # jitted function of the stacks and the layer's index
    lead, traced = cfg.moe_dense_layers, 1
    assert len(calls) == 2 * traced and all(
        kernel == (path == "kernel") for _, kernel in calls)
    # the kernel is handed the stack of ALL layers; ragged_dot the layer,
    # indexed inside grouped_matmul
    assert {len(shape) for shape, _ in calls} == {4 if path == "kernel" else 3}
    assert path != "kernel" or calls[0][0] == stack

    def sliced_by_hand(lhs, rhs, sizes, layer):
        return real(lhs, jax.lax.dynamic_index_in_dim(rhs, layer, 0, False), sizes)

    monkeypatch.setattr(dropless, "grouped_matmul", sliced_by_hand)
    del calls[:]
    sliced = step()
    assert [shape[0] for shape, kernel in calls if kernel] == [1] * (
        2 * traced if path == "kernel" else 0)
    for got, want in zip(in_place, sliced):
        if want is not None:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    logits, kp, _, counts = in_place
    assert float(jnp.abs(kp).max()) > 0 and np.isfinite(np.asarray(logits)).all()
    assert int(counts.sum()) == int(live.sum()) * cfg.moe_top_k * (cfg.n_layer - lead)
