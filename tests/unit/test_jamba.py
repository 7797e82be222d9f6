"""The Jamba family on the serving path (the ``mamba`` and ``full`` mixers of
``models/hybrid.py``, multi-query attention of 20 heads on ONE K/V head), at
a tiny size on the CPU in float32, against the plain reference
(``benchmarks/lib/reference_jamba.py``): prefill in chunks (a chunk enters
with a carried state), then decode through the pages and the two states; both
kernels of ``ops/pallas/selective_scan.py`` through the interpreter against
the recurrence written out token by token; each part of the mixer left out
FAILS the comparison; a slot reused and a request preempted; the published
parameter count; and what ``init_serving`` and the dense paths refuse."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.lib import reference_jamba as ref
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.gpt import GPT, jamba_config
from deepspeed_tpu.ops.pallas import selective_scan
from deepspeed_tpu.serving.kv_cache import init_arena
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, jitted, served_tokens

# mamba x 2, full, mamba x 2; 20 queries on one K/V head
WIDTHS = dict(vocab_size=512, n_positions=256, n_embd=64, n_layer=5, n_head=20,
              n_kv_head=1, head_dim=16, intermediate_size=128, attn_layer_period=5,
              attn_layer_offset=2, mamba_dt_rank=8)
REF = dict(n_layer=5, attn_layer_period=5, attn_layer_offset=2, n_head=20, n_kv_head=1,
           head_dim=16, mamba_inner=128, mamba_d_state=16, mamba_dt_rank=8,
           vocab_size=512, q_block=32)
BS, SLOTS, CHUNK, MB = 16, 3, 8, 16
SERVING = dict(max_batch_size=SLOTS, prefill_chunk=CHUNK, block_size=BS,
               num_blocks=64, dtype="float32")
# float32 against float32 on both sides: what is left is the order of the
# sums (the states on the sublanes against [channels, states], pages against
# one pass, the blocks of the matrices), carried through five pre-norm layers
# to logits of order 1: under 2e-5 in every case below.  bf16 in the mamba
# layers' state alone reads over 2e-3, in the weights over 1e-2
TOL = 1e-4


@pytest.fixture(scope="module")
def tiny():
    model = GPT(jamba_config(**WIDTHS, dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def loud(tiny):
    """The same model with the leaves that seeded weights leave quiet AT THIS
    SIZE made loud (the seeded taps, bias and step weights are of order 1
    already, ``hybrid.init_blocks``; a matrix at std 0.02 over 64 or 128 rows
    is a sixth of what it is over the published 2,560 or 5,120): input and
    step-lane weights whose norms matter, every gain and ``D`` different from
    1, and an output projection that carries the mixer's result to the
    logits."""
    model, params = tiny
    rng = np.random.default_rng(7)
    around_one = lambda a: jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)
    blocks = {m: dict(leaves) for m, leaves in params["blocks"].items()}
    mamba, full = blocks["mamba"], blocks["full"]
    mamba["in_w"] = mamba["in_w"] * 10.0
    mamba["x_w"] = mamba["x_w"] * 10.0
    mamba["out_w"] = mamba["out_w"] * 5.0
    mamba["skip_d"] = around_one(mamba["skip_d"])
    for leaves in (mamba, full):
        for name in leaves:
            if name.endswith("_g"):
                leaves[name] = around_one(leaves[name])
    return model, dict(params, blocks=blocks, lnf_g=around_one(params["lnf_g"]))


def reference_logits(params, seq, **kw):
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    fn = jitted(ref.jamba_logits, **dict(REF, **kw))
    return np.asarray(fn(params, jnp.asarray(ids)))[:len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


# ``round_through=`` rounds the mamba layers' state through that type after
# every step; ``forget=STATE`` zeroes it before a chunk that is not the first
STATE = ("mamba_state",)
driver = functools.partial(Driver, slots=SLOTS, chunk=CHUNK, block_size=BS,
                           blocks_a_slot=MB, leaves=STATE)


# ---- the served logits against the reference's full forward pass ---------------- #
# a prompt of 2.5 chunks (the second and the third enter with a carried state
# and a carried convolution state); boundaries at every offset of the
# convolution's reach; a token a chunk
CHUNKS = {"whole": (8, 8, 4), "ragged": (5, 1, 1, 8, 3, 2), "single": (1,) * 6}


@pytest.mark.parametrize("weights", ["seeded", "loud"])
@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_prefill_then_decode_agree_with_the_reference(tiny, loud, weights, chunks):
    model, params = tiny if weights == "seeded" else loud
    seq = _ids(44, seed=len(chunks))
    got = driver(model, params).sequence(seq, CHUNKS[chunks])
    want = reference_logits(params, seq)
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.5


@pytest.mark.parametrize("part", ref.ALL_PARTS + ("carried_state",))
def test_a_part_left_out_fails_the_comparison(loud, part):
    """The reference without the convolution's bias, without one of the three
    inner norms, without ``D`` or without the gate is another model; so is a
    program whose chunks do not carry the state in."""
    model, params = loud
    seq = _ids(44, seed=3)
    if part == "carried_state":
        got = driver(model, params, forget=STATE).sequence(seq, CHUNKS["whole"])
        want = reference_logits(params, seq)
    else:
        got = driver(model, params).sequence(seq, CHUNKS["whole"])
        assert np.abs(got - reference_logits(params, seq)).max() < TOL
        want = reference_logits(params, seq, parts=tuple(
            p for p in ref.ALL_PARTS if p != part))
    assert np.abs(got - want).max() > 100 * TOL


def test_a_bf16_state_fails_the_tolerance(loud):
    """The planted lower precision: the mamba layers' state alone rounded
    through bf16 after every step."""
    model, params = loud
    seq = _ids(44, seed=5)
    got = driver(model, params, round_through=jnp.bfloat16).sequence(seq, CHUNKS["ragged"])
    assert np.abs(got - reference_logits(params, seq)).max() > 20 * TOL


def test_bf16_weights_fail_the_tolerance(loud):
    model, params = loud
    seq = _ids(44, seed=5)
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = driver(model, rounded).sequence(seq, CHUNKS["whole"])
    assert np.abs(got - reference_logits(params, seq)).max() > 50 * TOL


def test_a_step_with_decode_rows_and_a_chunk_together(loud):
    """Two sequences decode while a third's prompt runs in the chunk rows of
    the same steps, in another slot: every row's logits are its own
    sequence's, and so is every slot's state."""
    model, params = loud
    a, b, c = _ids(60, 1), _ids(40, 2), _ids(40, 3)
    d = driver(model, params)
    d.sequence(a[:30], (8, 8, 8, 6), slot=0)
    d.sequence(b[:11], (8, 3), slot=1)
    got = {0: [], 1: [], 2: []}
    for i, start in enumerate(range(0, len(c), CHUNK)):
        rows = d.step(decode=[(0, a[30 + i], 30 + i), (1, b[11 + i], 11 + i)],
                      chunk=(2, start, c[start:start + CHUNK]))
        got[0].append(rows[0][None]), got[1].append(rows[1][None])
        got[2].append(rows[SLOTS:SLOTS + CHUNK])
    n = len(c) // CHUNK
    for slot, seq, lo in ((0, a, 30), (1, b, 11), (2, c, 0)):
        want = reference_logits(params, seq)[lo:lo + (len(c) if slot == 2 else n)]
        assert np.abs(np.concatenate(got[slot]) - want).max() < TOL, slot


# ---- the two kernels against the recurrence --------------------------------------- #
def _recurrence(h, c, dt, B, C, A, D):
    """The specification's recurrence, a token at a time in float64: ``h [S,
    N]`` -> (y ``[T, N]``, h)."""
    h, ys = np.asarray(h, np.float64), []
    for t in range(c.shape[0]):
        h = np.exp(dt[t][None] * A) * h + (dt[t] * c[t])[None] * B[t][:, None]
        ys.append((C[t][:, None] * h).sum(0) + D * c[t])
    return np.stack(ys), h


def _tokens(T, S, N, seed):
    r = np.random.default_rng(seed)
    return (r.normal(size=(T, N)), np.exp(r.uniform(np.log(1e-3), np.log(0.3), (T, N))),
            r.normal(size=(T, S)), r.normal(size=(T, S)),
            -np.exp(r.normal(size=(S, N))), r.normal(size=N))


def test_the_kernels_gates():
    ok = selective_scan.kernel_shape_ok
    assert ok(384, 16, 5120, jnp.float32) and ok(512, 16, 5120, jnp.float32)
    assert ok(8, 16, 512, jnp.bfloat16) and not ok(8, 8, 512, jnp.bfloat16)
    assert not ok(SLOTS, 16, 512, jnp.float32)        # slots that are no sublane tile
    assert not ok(8, 16, 128, jnp.float32)            # the tiny preset's channels
    assert not ok(8, 16, 512, jnp.float16)


@pytest.mark.parametrize("N", [512, 1024])
def test_the_state_update_kernel_is_the_recurrence(kernels, N):
    """``mamba_state_update`` through the interpreter against the reference
    beside it and against the recurrence: sixteen slots (two grid steps), a
    row that is not live and the other layers' states left to the bit."""
    S, n = 16, 16
    c, dt, B, C, A, D = _tokens(n, S, N, seed=N)
    r = np.random.default_rng(1)
    state = jnp.asarray(r.normal(size=(3, n, S, N)), jnp.float32)
    live = jnp.asarray(np.arange(n) != 5)
    args = [jnp.asarray(a, jnp.float32) for a in (c, dt, B, C, A, D)]
    # the rule is read while a program is traced, and a traced function is
    # kept by its identity: a new function a side
    kernels()
    reference = lambda *a: selective_scan.mamba_state_update(*a)
    want_s, want_y = jax.jit(reference)(state, 1, *args, live)
    assert "optimization_barrier" in str(jax.make_jaxpr(reference)(state, 1, *args, live))
    kernels("mamba_state_update")
    kernel = lambda *a: selective_scan.mamba_state_update(*a)
    got_s, got_y = jax.jit(kernel)(state, 1, *args, live)
    assert "mamba_state_update" in str(jax.make_jaxpr(kernel)(state, 1, *args, live))
    assert np.abs(np.asarray(got_s) - np.asarray(want_s)).max() < 1e-5
    assert np.abs(np.asarray(got_y) - np.asarray(want_y)).max() < 1e-4
    for s in (got_s, want_s):
        assert (s[0] == state[0]).all() and (s[2] == state[2]).all() and (s[1, 5] == state[1, 5]).all()
    for row in (0, 7, 8, 15):
        y, h = _recurrence(state[1, row], c[row][None], dt[row][None], B[row][None],
                           C[row][None], A, D)
        assert np.abs(np.asarray(got_y)[row] - y[0]).max() < 1e-4
        assert np.abs(np.asarray(got_s)[1, row] - h).max() < 1e-4


@pytest.mark.parametrize("live", [24, 17, 1])
def test_the_chunk_scan_kernel_is_the_recurrence(kernels, live):
    """``mamba_chunk_scan`` through the interpreter against the reference
    beside it and against the recurrence: a chunk entered with a non-zero
    state whose live length is short of the chunk; the rows past the live
    length write nothing and decay nothing."""
    S, N, T = 16, 1024, 24
    c, dt, B, C, A, D = _tokens(T, S, N, seed=live)
    h = np.random.default_rng(2).normal(size=(S, N))
    args = [jnp.asarray(a, jnp.float32) for a in (h, c, dt, B, C, A, D)]
    alive = jnp.arange(T) < live
    kernels()
    want_h, want_y = jax.jit(lambda *a: selective_scan.mamba_chunk_scan(*a))(*args, alive)
    kernels("mamba_chunk_scan")
    kernel = lambda *a: selective_scan.mamba_chunk_scan(*a)
    got_h, got_y = jax.jit(kernel)(*args, alive)
    assert "mamba_chunk_scan" in str(jax.make_jaxpr(kernel)(*args, alive))
    y, out = _recurrence(h, c[:live], dt[:live], B[:live], C[:live], A, D)
    for got in ((got_h, got_y), (want_h, want_y)):
        assert np.abs(np.asarray(got[1])[:live] - y).max() < 2e-4
        assert np.abs(np.asarray(got[0]) - out).max() < 2e-4
    assert np.abs(y).max() > 1.0


def test_the_walk_runs_both_kernels_where_their_gates_admit(kernels):
    """A stack of 512 channels, eight slots and a chunk of eight: both
    kernels run inside the scan over layers (the decode rows' on the stacked
    states in place), and the served logits are the reference's."""
    model = GPT(jamba_config(**dict(WIDTHS, mamba_expand=8), dtype="float32"))
    assert selective_scan.kernel_shape_ok(8, 16, 512, jnp.float32)
    params = model.init_params(jax.random.PRNGKey(1))
    seq = _ids(24, seed=2)
    kernels("mamba_state_update", "mamba_chunk_scan")
    d = driver(model, params, slots=8)
    text = str(jax.make_jaxpr(lambda *a, **kw: model.paged_step(params, *a, chunk=CHUNK, **kw))(
        jnp.zeros((16, 1), jnp.int32), jnp.zeros(16, jnp.int32), d.kp, d.vp,
        jnp.zeros((16, MB), jnp.int32), jnp.zeros((16, 1), jnp.int32),
        jnp.zeros((16, 1), jnp.int32), aux=d.aux, slots=jnp.zeros(16, jnp.int32),
        live=jnp.zeros(16, bool)))
    assert "mamba_state_update" in text and "mamba_chunk_scan" in text
    got = d.sequence(seq, (8, 8), slot=3)
    want = reference_logits(params, seq, mamba_inner=512)
    assert np.abs(got - want).max() < TOL


# ---- what the pages and a slot keep --------------------------------------------------- #
def test_a_decode_rows_states_k_and_v_are_one_whole_sequence_pass(loud):
    """Token by token through the states, or all 40 tokens as ONE chunk: the
    same K and V in the pages, the same state and the same last three input
    rows left in the slot."""
    model, params = loud
    seq = _ids(40, seed=11)
    steps = driver(model, params)
    steps.sequence(seq, (5,))                     # 5 prefilled, 35 decode rows
    whole = driver(model, params, chunk=40)
    whole.sequence(seq, (40,))
    pages = slice(1, 1 + 3)                       # slot 0's first three blocks
    for a, b in ((steps.kp, whole.kp), (steps.vp, whole.vp)):
        a, b = np.asarray(a[:, pages]).reshape(1, -1, 16), np.asarray(b[:, pages]).reshape(1, -1, 16)
        assert np.abs(a[:, :40] - b[:, :40]).max() < TOL
        assert np.abs(b[:, :40]).max() > 0.1
    for name, shape in (("mamba_state", (4, 16, 128)), ("mamba_conv", (4, 3, 128))):
        a, b = (np.asarray(d.aux[name][:, 0]) for d in (steps, whole))
        assert a.shape == shape and np.abs(a - b).max() < TOL and np.abs(b).max() > 0.01, name
    # the convolution's state is the last three input rows, the oldest first
    before = driver(model, params, chunk=40)
    before.sequence(seq[:39], (39,))
    conv = np.asarray(whole.aux["mamba_conv"][:, 0])
    assert np.abs(np.asarray(before.aux["mamba_conv"][:, 0, 1:]) - conv[:, :2]).max() < TOL
    # the other slots' states were never written
    assert not np.asarray(whole.aux["mamba_state"][:, 1:]).any()


def test_the_leaves_are_two_stacks_and_the_states_a_slot(tiny):
    model, params = tiny
    cfg, blocks = model.cfg, params["blocks"]
    assert set(blocks) == {"mamba", "full"}
    assert cfg.mixers == ("mamba", "mamba", "full", "mamba", "mamba")
    assert cfg.ffns == ("mlp",) * 5 and not cfg.norm_after and not cfg.qk_norm
    assert hybrid.layer_runs(cfg) == [("mamba", 0, 2), ("full", 0, 1), ("mamba", 2, 2)]
    assert not any(kind.rope for kind in cfg.pattern)
    mamba, full = blocks["mamba"], blocks["full"]
    assert mamba["in_w"].shape == (4, 64, 256) and mamba["conv_w"].shape == (4, 4, 128)
    assert mamba["x_w"].shape == (4, 128, 8 + 32) and mamba["dt_w"].shape == (4, 8, 128)
    assert mamba["scan_a_log"].shape == (4, 16, 128) and mamba["out_w"].shape == (4, 128, 64)
    # Mamba's own initialisation: A = -(n + 1), D = 1, steps in [1e-3, 1e-1]
    assert np.allclose(np.exp(np.asarray(mamba["scan_a_log"]))[0, :, 7], np.arange(1, 17))
    assert (np.asarray(mamba["skip_d"]) == 1).all()
    step = np.asarray(jax.nn.softplus(mamba["dt_b"]))
    assert 1e-3 * 0.999 < step.min() < 2e-3 and 5e-2 < step.max() < 1e-1 * 1.001
    assert set(full) == {"qkv_w", "out_w", "ln1_g", "ln2_g", "fc_w", "proj_w"}
    assert full["qkv_w"].shape == (1, 64, 320 + 2 * 16) and "lm_head" not in params
    specs = model.partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    # the full layer owns plain pages of its one K/V head; a mamba layer a
    # float32 state with the channels on the lanes and three input rows a slot
    assert cfg.arena_layout == (1, 1, (16, 16)) and cfg.page_groups == (None,)
    kp, vp = init_arena(cfg, 10, 16)
    assert kp.shape == vp.shape == (1, 10, 16, 16)
    aux = hybrid.init_aux(cfg, 10, 16, SLOTS, jnp.bfloat16)
    assert set(aux) == {"mamba_state", "mamba_conv"}
    assert aux["mamba_state"].shape == (4, SLOTS, 16, 128) and aux["mamba_state"].dtype == jnp.float32
    assert aux["mamba_conv"].shape == (4, SLOTS, 3, 128) and aux["mamba_conv"].dtype == jnp.bfloat16


# ---- through the engine ---------------------------------------------------------------- #
def served(model, params, prompts, new, **serving):
    return served_tokens(model, params, prompts, new, **dict(SERVING, **serving))


reference_tokens = functools.partial(serving_helpers.reference_tokens, reference_logits)


def test_the_engine_serves_the_references_tokens_in_one_program(loud):
    model, params = loud
    prompts = [_ids(n, seed=n) for n in (50, 13, 29)]
    (tokens, eng) = served(model, params, prompts, (20, 30, 25))
    assert eng.compiled_programs() == 1
    # K and V of one head of 16 a token in the one full layer; nothing a
    # token in a mamba layer
    assert eng.cache_bytes_per_token == 2 * 16 * 4
    assert eng._k_pages.shape == (1, 64, BS, 16)
    for p, got in zip(prompts, tokens):
        best, gap = reference_tokens(params, p, got)
        assert got == best and gap == 0.0


def test_a_slot_reused_by_a_new_sequence_starts_from_zero_states(loud):
    """One slot: the second request runs where the first left its states,
    and is served what it gets on an engine of its own."""
    model, params = loud
    a, b = _ids(37, seed=5), _ids(22, seed=6)
    (both, eng) = served(model, params, [a, b], (20, 30), max_batch_size=1)
    assert eng.step_count > 0
    (alone, _) = served(model, params, [b], (30,), max_batch_size=1)
    assert both[1] == alone[0] == reference_tokens(params, b, alone[0])[0]


def test_a_preempted_request_resumes_to_the_same_tokens(loud):
    """An arena too small for three requests to grow together: the youngest
    is preempted, its pages go back, its states are rebuilt by the re-prefill
    (a chunk at position 0 starts from zero whatever the slot holds), and
    every request is served the tokens it gets alone; ``state_slots_reset``
    counts the first chunks, ``mamba_state_moves`` the states a step moved."""
    model, params = loud
    prompts = [_ids(n, seed=40 + n) for n in (70, 60, 50)]
    alone = [served(model, params, [p], (40,))[0][0] for p in prompts]

    def moves(st):
        assert st["mamba_state_moves"] == 4 * (st["decode_batch"] + (st["prefill_tokens"] > 0))
        assert st["mamba_state_bytes"] == 4 * SLOTS * 16 * 128 * 4
        assert st["mamba_conv_bytes"] == 4 * SLOTS * 3 * 128 * 4
    assert serving_helpers.preempted(model, params, prompts, 40, moves,
                                     **dict(SERVING, num_blocks=17)) == alone


def test_a_snapshot_restores_by_recompute(loud):
    """``snapshot()`` carries no state, no input row and no K or V:
    ``restore()`` prefills prompt and generated tokens again from zero."""
    model, params = loud
    p = _ids(45, seed=8)
    (whole, _) = served(model, params, [p], (30,))
    assert serving_helpers.restored_tokens(model, params, p, 30, 11, **SERVING) == whole[0]


# ---- the published parameter count ------------------------------------------------------ #
def test_the_published_config_counts_the_issues_parameters():
    mlp, norms = 3 * 2560 * 8192, 2 * 2560
    mixer = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560 + 160 + 16 + 16)
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert (mixer, mixer + mlp + norms, attention + mlp + norms) == (
        41_241_792, 104_161_472, 76_682_240)
    whole = GPT(jamba_config())
    assert whole.num_params() == (26 * (mixer + mlp + norms) + 2 * (attention + mlp + norms)
                                  + 65_536 * 2560 + 2560) == 3_029_337_472
    shapes = jax.eval_shape(whole.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 2560 == 3_029_337_472
    cfg = whole.cfg
    assert (cfg.ln_eps, cfg.padded_vocab, cfg.n_positions) == (1e-6, 65_536, 262_144)
    assert [i for i, m in enumerate(cfg.mixers) if m == "full"] == [7, 21]
    assert hybrid.layer_runs(cfg) == [("mamba", 0, 7), ("full", 0, 1), ("mamba", 7, 13),
                                      ("full", 1, 1), ("mamba", 20, 6)]
    assert not cfg.untied_head and cfg.cache_lanes == (128, 128) and cfg.arena_layout[0] == 2
    aux = jax.eval_shape(lambda: hybrid.init_aux(cfg, 16, 16, 384, jnp.bfloat16))
    assert aux["mamba_state"].shape == (26, 384, 16, 5120)       # the channels on the lanes
    assert aux["mamba_conv"].shape == (26, 384, 3, 5120)
    a_slot = (aux["mamba_state"].size * 4 + aux["mamba_conv"].size * 2) // 384
    assert a_slot == 26 * (327_680 + 30_720) == 9_318_400
    with pytest.raises(AssertionError, match="a mamba layer's channels and widths"):
        jamba_config(mamba_dt_rank=0)


# ---- what is refused, by the mechanism's name --------------------------------------------- #
@pytest.mark.parametrize("knob, mechanism", [
    ({"prefix_cache": True}, "prefix_cache shares full blocks"),
    ({"kv_tiering": True}, "kv_tiering spills"),
])
def test_init_serving_refuses_what_carries_no_state(tiny, knob, mechanism):
    model, params = tiny
    with pytest.raises(ValueError) as e:
        deepspeed_tpu.init_serving(model=model, params=params,
                                   config={"serving": dict(SERVING, **knob)})
    assert mechanism in str(e.value)
    assert "4 mamba layers hold a recurrent state and a convolution state a slot" in str(e.value)
    assert "no block of K and V carries" in str(e.value)


@pytest.mark.parametrize("path", ["forward", "generate", "loss"])
def test_the_dense_paths_refuse_the_stack_by_what_they_lack(tiny, path):
    model, params = tiny
    said = serving_helpers.dense_path_refusal(model, params, path, _ids(16, 0))
    assert "no selective scan (nor its backward)" in said
    assert "4 mamba layers" in said and "init_serving()" in said
