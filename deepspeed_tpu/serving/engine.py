"""Continuous-batching serving engine over the paged KV arena.

The inference stack's ``generate()`` serves one static batch per call; this
engine serves a *stream*: requests join and leave the decode batch every
step without recompilation.  The trick is shape discipline — exactly ONE
program is ever compiled, and a step runs it ONCE: ``[max_batch_size +
prefill_chunk, 1]`` tokens over the arena.

* rows ``0 .. max_batch_size - 1`` are the **decode** slots — every active
  sequence advances one token;
* rows ``max_batch_size ..`` are the step's **prompt chunk** (chunked
  prefill: at most one chunk of at most ``prefill_chunk`` tokens a step, so
  a long prompt never holds the decode rows back for more than a chunk) —
  token ``i`` of the chunk is a row of its own at position ``start + i``
  with the request's block table, through everything but attention.  A
  layer's attention takes the chunk PACKED, ``Sq`` consecutive tokens a row
  under the table and the position of the first (``Sq`` from the shapes:
  ``ops/pallas/decode_attention.py:paged_chunk_queries``), so a key of the
  prompt is read once a packed row and not once a token.  Every layer
  scatters all new K/V before it attends, so token ``i`` sees keys
  ``0 .. start + i``, its own chunk's included.

Rows that carry nothing (an idle slot, the chunk's rows past its tokens,
all of them in a step without a chunk) carry trash-block write coordinates
and all-trash block tables, so what a step holds is pure traced *data*.

The block tables are STATE of the step program, not an input: one
``[max_batch_size, width]`` int32 table a layer group lives on the device
beside the arena (all groups in one flat array, :class:`StepLayout`), row
``s`` the table of the sequence in slot ``s``, donated to the step and
returned by it.  A table changes by an entry every ``block_size`` tokens, so
a step uploads ONE small int32 array (:meth:`ServingEngine._pack`): a token,
a position, a slot and a live flag for every row, the slots whose row goes
back to trash, and the entries the host-side :class:`PagedKVAllocator`
recorded since the last step.  The program applies them, gathers each row's
table by its slot, and computes the write coordinates from the table and the
position (:func:`unpack_step`), so ``model.paged_step`` gets what whole
tables built on the host would give it, value for value.  The arena
arrays are donated back to the step on accelerators, so the KV cache is
updated in place.  The e2e contract (tests/unit/serving): greedy outputs
are token-identical to sequential ``generate()``, even across
preempt→evict→recompute cycles, because recompute re-prefills a prefix of
the identical deterministic stream.

A step is not always host, device, host.  The program takes the token array
of the program before it as an input, ON THE DEVICE, and a row whose token
the host has not seen yet says which entry of that array it is
(:func:`unpack_step`), so the host can launch program n+1 while the chip runs
program n and fetch n's token row behind the launch: everything but the
token VALUES (positions, slots, pages, the next prompt chunk, who ends by
``max_new_tokens``) it knows when it launches.  At most one program is ahead,
and only while no arrival could have changed the next program anyway
(:meth:`ServingEngine._stays_ahead`); whatever is unusual lands the row in
flight first (:meth:`ServingEngine._drain`).

Decoding is greedy (the sampler the sequential path uses at
``temperature=0``, including the padded-vocab mask); sampled decoding is
future work and is rejected at ``submit()``.
"""

import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from deepspeed_tpu.comm.bounded import BoundedCollective, CollectiveTimeout
from deepspeed_tpu.runtime.offload import StagingError
from deepspeed_tpu.serving.config import DeepSpeedServingConfig
from deepspeed_tpu.serving.kv_cache import (ArenaExhausted, PagedKVAllocator,
                                            init_arena)
from deepspeed_tpu.serving.kv_tiering import KVTieringManager
from deepspeed_tpu.serving.prefix_cache import PrefixCache
from deepspeed_tpu.serving.scheduler import (DECODE, EXPIRED, FINISHED,
                                             SHED_LEVELS, SLO_PRIORITY,
                                             AdmissionController,
                                             DeadlineExceeded, Request,
                                             ServingScheduler, ShedError)
from deepspeed_tpu.telemetry.tracing import maybe_span
from deepspeed_tpu.testing.fault_injection import (FaultInjected, fault_point,
                                                   release_wedges)
from deepspeed_tpu.utils.logging import log_dist


#: The leaf spans that tile ``ServingEngine.step()``, in the order the work
#: happens in a step that is NOT dispatched ahead (PERF.md § 3 copies this
#: list).  On the profiler's line they are SIBLINGS under the caller's own
#: span, with no ``serve.step`` round them: an idle gap of the device is then
#: named by the phase the host was in.
#: A step launches at most ONE program, so it opens one ``dispatch``: the
#: ``serve.decode.*`` one when the program carries a decode row (stat
#: ``batch``: every live row, chunk tokens included), the ``serve.prefill.*``
#: one when it carries a prompt chunk alone; both have ``chunk_tokens``.  A
#: ``fetch`` and the ``commit`` spans behind it belong to the program whose
#: row they bring in, under that program's names and stats.  In a step
#: dispatched ahead (:meth:`ServingEngine._stays_ahead`) they are the program
#: BEFORE this step's, and they open behind this step's ``dispatch``: admit,
#: grow, build, dispatch (n+1), fetch (n), commit (n), stats.  The step in
#: which the engine stops being ahead opens the pair twice (n, then n+1); the
#: step in which it starts opens none.
#: The engine numbers the programs it launches, 1, 2, 3 ...
#: (``ServingEngine.programs_launched`` is the last number given), and the
#: stat ``program`` is on every span that touches one: its ``dispatch``, its
#: ``fetch``, its ``commit`` spans, whatever step opens them.  The ``fetch``
#: that lands a program's row carries the program's own durations too
#: (:data:`PROGRAM_STATS`): one such event a landed program.  A program an
#: incident abandons (:meth:`ServingEngine._recover_incident`) leaves no such
#: event, and its number is not given again.
#: ``submit()`` is ``serve.submit`` (stat ``rid``); a request's first token
#: leaves one zero-length ``serve.first_token`` with its waits as stats and
#: the ``program`` whose row brought the token: a request's spans join on
#: ``rid``, and its chunks' programs on ``rid`` and ``program``.
#: ``serve.stats`` carries what the step's tables and attention cost
#: (``table_edits``, ``table_reloads``, ``upload_bytes``, ``tile_runs_pct``,
#: ``chunk_queries_per_row``, ``attention_rows``; under an indexer over a
#: latent cache ``chunk_keys_extent`` and ``chunk_keys_passed``: the keys of
#: the extent of its table the step's chunk was compiled for and the keys in
#: the tiles its masked pass walked, each times the indexed layers, so their
#: ratio says how far the skip past the chunk's last position engages),
#: ``dense_rows`` (the rows
#: the program took through the model's dense matrices: every slot's, and the
#: chunk's only where the step carries one; ``_rows_that_carry``),
#: ``dispatched_ahead`` (1:
#: this step's program was launched before the row of the program before it
#: was on the host), ``program`` (the one this step launched; absent where it
#: launched none) and, in a step that follows a step with a program, the
#: step's turn-round as DURATIONS on the engine's one clock
#: (:data:`TURNAROUND_STATS`): they need no alignment with any other line of
#: a trace, and they are on the main thread's line whatever thread ran the
#: fetch.
SERVE_STEP_SPANS = (
    "serve.admit",              # deadlines, shed ladder, sched.admit
    "serve.grow",               # sort, ensure_capacity, decode_batch
    "serve.prefill.build",      # the tables' edits, the chunk's rows, packed
    "serve.decode.build",       # the decode slots' rows, packed
    "serve.prefill.dispatch",   # the one upload and the call of the step
    "serve.prefill.fetch",      # the token row back on the host
    "serve.decode.dispatch",
    "serve.decode.fetch",
    "serve.prefill.commit",     # the prompt's last chunk: the first token
    "serve.decode.commit",
    "serve.stats",              # ledger, stats dict, gauges, emit
)

#: What a step says of its own turn-round, in ``step()``'s stats and on
#: ``serve.stats``, from four stamps of ``ServingEngine._clock``: ``t_enter``
#: and ``t_exit`` at ``step()``'s first and last line, ``t_launch`` when the
#: call of the step program has returned (upload made, program enqueued) and
#: ``t_result`` when a token row is on the host.  The host is SETTLED at the
#: last of the two it took: nothing of the chip's is left for it to wait for
#: or to hand over.  For the step that launched program n, ``settled`` being
#: when the step before it was last so:
#:
#: * ``turnaround_ms`` = ``t_launch(n) - t_result(n-1)``: the chip has no
#:   program of this engine; the sum of the next three.  **0.0 in a step
#:   dispatched ahead**: program n-1 was still the chip's when n was launched,
#:   so there is no such stretch, and the next three are host time the chip
#:   did not wait for;
#: * ``commit_ms`` = ``t_exit(before) - settled``: commit and stats of the
#:   step before (from its last row; from its launch, where it fetched none);
#: * ``outside_ms`` = ``t_enter - t_exit(before)``: the caller, between two
#:   ``step()`` calls;
#: * ``prepare_ms`` = ``t_launch(n) - t_enter``: admit, grow, build, upload,
#:   launch;
#: * ``result_wait_ms`` = the last ``t_result`` of this step ``- t_launch(n)``:
#:   the host waiting behind its launch.  Not ahead, the row is n's own: the
#:   program's device time and both wires.  Ahead, it is n-1's: what was left
#:   of that program, about a device step less the host's three parts (a
#:   launch that waits for the program before it shows here as nothing left
#:   to wait for, and in ``prepare_ms``).  0.0 where the step fetched no row
#:   behind its launch (the first step ahead).
#:
#: The first step, a step after one that ran no program or left the engine
#: with no request, and the first step after an incident's re-jit carry none:
#: there the chip waited for work or for the compiler, not for the host's
#: turn-round.
TURNAROUND_STATS = ("turnaround_ms", "commit_ms", "outside_ms", "prepare_ms",
                    "result_wait_ms")

#: What a PROGRAM says of itself when its row lands: what the one ``fetch``
#: span that landed it (on whichever thread fetched) gains at its end, beside
#: the ``program``, ``chunk_tokens`` and ``batch`` it opened with.  A step is
#: not the unit: it launches program k and lands k-1, or k-1 and k, or none.
#: ``ahead`` is 1 where the program was launched before the row of the program
#: before it was on the host; the durations are on ``ServingEngine._clock``,
#: from the program's own two stamps and the stamp of the row landed before
#: it:
#:
#: * ``device_ms`` = ``t_result(k) - max(t_launch(k), t_result(k-1))``
#:   (no row before it: from its launch).  Launched ahead, the period between
#:   two rows while the chip is never without a program: the program's device
#:   time, both stamps behind the same wire.  Not ahead, its device time and
#:   both wires.  Over consecutive programs ``sum(device_ms) +
#:   sum(turnaround_ms) = t_result(last) - t_result(first)``;
#: * ``host_ms`` = ``commit_ms + outside_ms + prepare_ms`` of the step that
#:   launched it: the host's part for this program, hidden or not.  Absent
#:   where that step carries no turn-round (the chip waited for work or for
#:   the compiler).
PROGRAM_STATS = ("ahead", "device_ms", "host_ms")


class StepLayout(NamedTuple):
    """Shapes of the step's one upload and of the table state it edits.

    The state is one flat int32 array: group ``g``'s ``[slots, widths[g]]``
    table at ``offsets[g]``, row-major.  The upload is one flat int32 array:
    ``[rows, 4]`` (token, position, slot, live), then ``[slots]`` (1: the
    slot's row goes back to trash, before any entry), then ``[edits, 2]``
    (address in the state, block), padded with addresses past the state's
    end, which the scatter drops.  The rows behind the slots are the step's
    prompt chunk, a token a row here and in the program, which the model
    packs for attention alone (``paged_step``'s ``chunk``)."""
    slots: int                  # decode rows; rows of each table
    rows: int                   # slots + prefill_chunk: tokens of the program
    widths: Tuple[int, ...]     # columns of each layer group's table
    offsets: Tuple[int, ...]    # where each group's table starts in the state
    block_size: int
    edits: int                  # entries one upload can change

    @classmethod
    def of(cls, alloc: PagedKVAllocator, slots: int, chunk: int):
        """``edits``: one admission of the longest sequence the tables hold
        (it is handed its prompt's blocks at once), and beside it a block a
        group for every decode row and a chunk's blocks."""
        grow = slots + -(-chunk // alloc.block_size) + 1
        offsets = tuple(slots * sum(alloc.widths[:g])
                        for g in range(alloc.n_groups))
        return cls(slots, slots + chunk, alloc.widths, offsets,
                   alloc.block_size, sum(alloc.widths) + alloc.n_groups * grow)

    @property
    def state_size(self) -> int:
        return self.slots * sum(self.widths)

    @property
    def packed_size(self) -> int:
        return 4 * self.rows + self.slots + 2 * self.edits


def unpack_step(lay: StepLayout, packed, previous, state):
    """The traced head of the step: one upload, the token array of the
    program before and the table state ->
    ``(ids, positions, state, tables, write_blocks, write_offsets)``, the
    state with this step's edits in it and the rest as
    ``model.paged_step`` takes them.  A row's token ``-(1 + i)`` stands for
    ``previous[i]``: the token the program before made in its row ``i``,
    which the host had not seen when it packed this one (``i``: the row's own
    slot where the sequence decoded there, ``slots + n_chunk - 1`` where its
    prompt ended in that program's chunk).  A row that is not live reads and
    writes the trash block through an all-trash table."""
    import jax.numpy as jnp
    TRASH = PagedKVAllocator.TRASH
    R, S = lay.rows, lay.slots
    rows = packed[:4 * R].reshape(R, 4)
    ids, positions, slot = rows[:, 0:1], rows[:, 1], rows[:, 2]
    ids = jnp.where(ids < 0, previous.reshape(-1)[jnp.maximum(-1 - ids, 0)], ids)
    live = rows[:, 3:4] != 0
    cleared = packed[4 * R:4 * R + S, None] != 0
    edits = packed[4 * R + S:].reshape(lay.edits, 2)
    views = lambda flat: [flat[at:at + S * w].reshape(S, w)
                          for at, w in zip(lay.offsets, lay.widths)]
    state = jnp.concatenate([jnp.where(cleared, TRASH, t).reshape(-1)
                             for t in views(state)])
    state = state.at[edits[:, 0]].set(edits[:, 1], mode="drop")
    logical = positions // lay.block_size
    tables, write_blocks = [], []
    for table, w in zip(views(state), lay.widths):
        table = jnp.where(live, table[slot], TRASH)                # [R, w]
        tables.append(table)
        write_blocks.append(jnp.take_along_axis(
            table, (logical % w)[:, None], axis=1))
    write_offsets = jnp.where(live, positions[:, None] % lay.block_size, 0)
    return (ids, positions, state, tuple(tables), tuple(write_blocks),
            write_offsets)


class ServeStepTimeout(RuntimeError):
    """A compiled serve step exceeded
    ``serve_step_timeout_s``.  Raised *after* the engine has recovered
    in-process (programs re-jitted, arena rebuilt, every in-flight request
    requeued for recompute) — ``run()``/``result()`` keep driving; a bare
    ``step()`` caller sees the incident."""

    def __init__(self, message, op=None, deadline_s=None, step=None):
        super().__init__(message)
        self.op = op
        self.deadline_s = deadline_s
        self.step = step


class _Flight(NamedTuple):
    """A program that is launched and whose token row is not committed yet:
    what :meth:`ServingEngine._land` needs to fetch the row and commit it, and
    what the program behind it needs to take its tokens on the device."""
    tokens: Any                 # the device array: a token a row, then an MoE
                                # model's expert counts
    phase: str                  # names its dispatch/fetch pair and fault point
    at: Dict[str, Any]          # that pair's span stats
    decode: List[Tuple[Request, int]]       # each decode row's (request, slot)
    chunk: Optional[Tuple[Request, int, bool, Dict[str, int]]]  # (request,
                                # slot, the prompt's last?, rid/start/tokens)
    feeds: Dict[int, int]       # rid -> the row whose token is its next input
    t_launch: float
    ahead: int                  # 1: launched with the program before in flight
    host_ms: Optional[float]    # the host's part for it (PROGRAM_STATS)

    @property
    def number(self) -> int:
        """The program's: 1 for the first the engine ever launched."""
        return self.at["program"]


class ServeFuture:
    """Handle for one submitted request.  ``result()`` drives the engine's
    step loop until this request finishes (single-threaded serving — there
    is no background thread; whoever waits, steps)."""

    def __init__(self, engine: "ServingEngine", request: Request):
        self._engine = engine
        self.request = request

    @property
    def done(self) -> bool:
        return self.request.state == FINISHED

    @property
    def token_ids(self) -> List[int]:
        """Generated tokens so far (excludes the prompt)."""
        return list(self.request.generated)

    def result(self, max_steps: int = 100_000,
               timeout_s: Optional[float] = None) -> List[int]:
        """Drive until this request finishes.  ``timeout_s`` bounds the
        wait in wall-clock seconds (checked at step boundaries — pair it
        with ``serve_step_timeout_s`` so a wedged *dispatch* cannot park
        the caller inside one step forever).  Raises
        :class:`DeadlineExceeded` if the request's own SLO deadline
        cancelled it."""
        eng = self._engine
        deadline = (None if timeout_s is None
                    else eng._clock() + float(timeout_s))
        for _ in range(max_steps):
            if self.done:
                return self.token_ids
            if self.request.state == EXPIRED:
                raise DeadlineExceeded(
                    f"request {self.request.rid} missed its "
                    f"{self.request.slo!r}-class deadline and was cancelled")
            if deadline is not None and eng._clock() >= deadline:
                raise TimeoutError(
                    f"request {self.request.rid} unfinished after "
                    f"{timeout_s}s")
            try:
                if eng._ends_in_flight(self.request):
                    eng._drain()    # its last token is on its way: no launch
                else:               # stands between the caller and the row
                    eng.step()
            except ServeStepTimeout:
                # the engine already recovered (state requeued for
                # recompute); keep driving under the same bounds
                continue
        raise TimeoutError(
            f"request {self.request.rid} unfinished after {max_steps} steps")


class _TieringAdapter:
    """Bridges the scheduler's request-level spill/restage hooks to the
    :class:`KVTieringManager`'s rid/block-level API, and owns the
    ``kv_spill``/``kv_restage`` telemetry.  Only blocks a sequence has
    actually *written* (``blocks_for_tokens(prefilled)``) are spilled —
    a growth block allocated for the next token holds garbage."""

    def __init__(self, engine: "ServingEngine"):
        self.engine = engine
        self.mgr = engine.tiering

    def spill(self, req: Request):
        eng = self.engine
        n = eng.alloc.blocks_for_tokens(req.prefilled)
        blocks = eng.alloc.owned_blocks(req.rid)[:n]
        tier = self.mgr.spill(req.rid, blocks, eng._k_pages, eng._v_pages,
                              req.prefilled)
        if tier is not None:
            eng._emit("kv_spill", {
                "rid": req.rid, "slo": req.slo, "tier": tier,
                "blocks": len(blocks), "tokens": req.prefilled,
                "bytes": self.mgr.chunk_bytes(eng._k_pages, len(blocks)),
            }, step=eng.step_count)
        return tier

    def begin_restage(self, req: Request) -> None:
        self.mgr.begin_restage(req.rid)

    def restage_ready(self, req: Request) -> bool:
        return self.mgr.restage_ready(req.rid)

    def restage(self, req: Request) -> bool:
        eng = self.engine
        n = eng.alloc.blocks_for_tokens(req.spilled_tokens)
        dest = eng.alloc.owned_blocks(req.rid)[:n]
        try:
            fault_point("serve.restage", rid=req.rid)
            eng._k_pages, eng._v_pages, info = self.mgr.restage(
                req.rid, eng._k_pages, eng._v_pages, dest)
        except (KeyError, StagingError, FaultInjected) as e:
            # unreadable/missing chunk: drop the record and recompute —
            # the destructive-evict contract still yields identical tokens
            self.mgr.discard(req.rid)
            eng._emit("kv_restage", {"rid": req.rid, "ok": False,
                                     "error": str(e)}, step=eng.step_count)
            return False
        eng._emit("kv_restage", {
            "rid": req.rid, "ok": True, "source": info["source"],
            "ready": info["ready"], "wait_ms": info["wait_s"] * 1000.0,
            "blocks": info["blocks"], "tokens": info["tokens"],
            "bytes": info["bytes"],
        }, step=eng.step_count)
        return True

    def discard(self, req: Request) -> None:
        self.mgr.discard(req.rid)

    def describe_tiers(self) -> str:
        return self.mgr.describe()


class ServingEngine:
    """``submit()/step()/run()`` over a model implementing ``paged_step``
    (the GPT family, ``models/gpt.py:gpt_paged_step``)."""

    def __init__(self, model, config: Optional[DeepSpeedServingConfig] = None,
                 params=None, seed: Optional[int] = None, telemetry=None,
                 tracer=None):
        import jax
        import jax.numpy as jnp
        cfg = config or DeepSpeedServingConfig()
        self._config = cfg
        self.telemetry = telemetry
        self.tracer = tracer
        # live metrics plane: gauges + step-time histograms are updated
        # directly (host wall-clock / scheduler counts, zero device syncs);
        # event-derived metrics (TTFT, preemptions, restages) flow through
        # the hub's MetricsSink on the periodic flush below — one source
        # of truth per metric, no double counting.
        self.registry = getattr(telemetry, "registry", None)
        if self.registry is not None:
            r = self.registry
            self._g_queue = r.gauge("serve_queue_depth")
            self._g_active = r.gauge("serve_active")
            self._g_blocks = r.gauge("serve_blocks_in_use")
            self._g_blocks_total = r.gauge("serve_blocks_total")
            self._g_blocks_total.set(cfg.num_blocks)
            self._g_host_bytes = r.gauge("serve_kv_host_bytes")
            self._g_nvme_bytes = r.gauge("serve_kv_nvme_bytes")
            self._g_prefix_rate = r.gauge("prefix_hit_rate")
            self._h_step = r.histogram("serve_step_ms")
            self._h_turnaround = r.histogram("serve_turnaround_ms")
            self._h_program = r.histogram("serve_program_ms")
            self._g_relaid_leaves = r.gauge("serve_relaid_leaves")
            self._g_relaid_bytes = r.gauge("serve_relaid_bytes")
        self.dtype = cfg.jnp_dtype
        assert hasattr(model, "paged_step") and hasattr(model, "cfg"), (
            "ServingEngine needs a model with .cfg and .paged_step(...) "
            "(the GPT family)")
        # serve in the configured dtype without mutating the caller's model
        if model.cfg.dtype != self.dtype:
            import copy
            import dataclasses
            model = copy.copy(model)
            model.cfg = dataclasses.replace(model.cfg, dtype=self.dtype)
        self.module = model
        mcfg = model.cfg
        # experts of an MoE model (0: dense).  Every step routes the rows
        # that carry no request too (idle slots, idle chunk rows): a router
        # with a capacity would let them push live tokens out of an expert
        self._moe_experts = int(getattr(mcfg, "moe_num_experts", 0))
        # the rows of expert counts a program hands back behind its tokens:
        # one summed over layers, or a hybrid stack's row a layer
        self._moe_count_rows = (sum(f != "mlp" for f in mcfg.ffns)
                                if mcfg.hybrid else 1)
        if self._moe_experts and mcfg.moe_router != "dropless":
            raise ValueError(
                f"init_serving: the {mcfg.moe_router!r} router drops tokens "
                f"beyond an expert's capacity, and a serve step's idle slots "
                f"and idle chunk rows count against it; serve an MoE model "
                f"with moe_router='dropless'")

        if params is None:
            assert hasattr(model, "init_params"), (
                "pass params= or a model with init_params(rng)")
            params = model.init_params(
                jax.random.PRNGKey(cfg.seed if seed is None else seed))
        self.params = params

        # ---- paged arena + control plane --------------------------------- #
        self.max_blocks_per_seq = (cfg.max_blocks_per_seq
                                   or -(-mcfg.n_positions // cfg.block_size))
        # a layer group a kind of the model's layer pattern, named by its
        # window: ``num_blocks`` blocks of ALL layers are ``num_blocks *
        # groups`` pages of one group's layers each, one pool
        self._windows = mcfg.page_groups
        self._hybrid = mcfg.hybrid
        if self._hybrid:
            from deepspeed_tpu.models import hybrid
            for on, mechanism, what in (
                    (cfg.prefix_cache, "prefix_cache", "shares full blocks of "
                     "K and V between sequences"),
                    (cfg.kv_tiering, "kv_tiering", "spills a sequence's blocks "
                     "of K and V and restores them")):
                if on:
                    raise ValueError(
                        f"init_serving: {mechanism} {what}; "
                        f"{hybrid.what_no_block_carries(mcfg)}, which no "
                        f"block of K and V carries (a preempted request is "
                        f"recomputed)")
            if "sparse" in mcfg.mixers and cfg.prefill_chunk % mcfg.sparse.stride:
                raise ValueError(
                    f"init_serving: prefill_chunk {cfg.prefill_chunk} is not "
                    f"whole strides of {mcfg.sparse.stride} compressed keys")
        if mcfg.indexed_layers:
            from deepspeed_tpu.models import hybrid
            if not hybrid.indexed_chunk_tile(cfg.prefill_chunk, mcfg.indexer.heads):
                raise ValueError(
                    f"init_serving: prefill_chunk {cfg.prefill_chunk} is not "
                    f"whole tiles of queries that select their tokens together "
                    f"(models/hybrid.py:indexed_chunk_tile)")
            if cfg.kv_tiering or cfg.prefix_cache:
                raise ValueError(
                    "init_serving: kv_tiering and prefix_cache spill and share "
                    "blocks of K and V; this model's indexed layers hold a "
                    "cache of index keys the selection scores, which no block "
                    "of K and V carries")
        if len(mcfg.cache_lanes) != 2 and (cfg.kv_tiering or cfg.prefix_cache):
            raise ValueError(
                "init_serving: kv_tiering and prefix_cache spill and share "
                "blocks of a K and a V array; this model's cache spec is "
                f"{mcfg.cache_lanes} (a latent cache)")
        if len(self._windows) > 1 and (cfg.kv_tiering or cfg.prefix_cache):
            raise ValueError(
                "init_serving: kv_tiering and prefix_cache share and spill "
                "blocks of ONE table a sequence; this model's layer pattern "
                f"keeps {len(self._windows)} (windows {self._windows}), and "
                "a window group gives its blocks back")
        # which attention the one program gets, a plan a page group (static
        # per engine; the step builds the same from the arena's shapes).  The
        # stats of every step are GROUP 0's: the paged kernel's pages a tile
        # (0 on the einsum path), and how its calls take the prompt chunk,
        # ``chunk_queries_per_row`` consecutive tokens a row, so they run
        # ``attention_rows`` rows where the program holds ``slots + chunk``
        # tokens.  The allocator takes ONE ``run_blocks``, the blocks it lays
        # down together: the ``run_pages`` of the groups whose kernel fetches
        # a tile of consecutive pages with one copy, a full group's and a
        # window group's ring alike (a plan that copies page by page says 0)
        self._run_blocks, _, plans = mcfg.paged_layout(
            cfg.block_size, self.max_blocks_per_seq, cfg.prefill_chunk, self.dtype)
        self.paged_tile_pages = plans[0].tile_pages
        self.chunk_queries_per_row = queries = plans[0].chunk_queries
        self.attention_rows = (cfg.max_batch_size + cfg.prefill_chunk
                               // queries) * plans[0].rows_a_token
        # bytes the arena holds a token a layer (every array of the cache
        # spec)
        self.cache_bytes_per_token = (sum(mcfg.cache_lanes)
                                      * np.dtype(self.dtype).itemsize)

        self.alloc = self._new_allocator()
        self.sched = ServingScheduler(cfg, self.alloc, cfg.max_batch_size)
        self.sched.on_preempt = self._on_preempt
        self._k_pages, self._v_pages = init_arena(
            mcfg, cfg.num_blocks, cfg.block_size, dtype=self.dtype)
        self._layout = StepLayout.of(self.alloc, cfg.max_batch_size,
                                     cfg.prefill_chunk)
        self._tables = self._empty_tables()
        # what a hybrid stack caches beside K and V (compressed keys, the
        # linear and the delta layers' states, the convolutions' last rows):
        # donated state of the step like the arena
        self._aux = self._new_aux()

        # ---- tiered spill/restage + prefix sharing (both opt-in) ---------- #
        self.tiering: Optional[KVTieringManager] = None
        self.prefix: Optional[PrefixCache] = None
        if cfg.kv_tiering:
            self.tiering = KVTieringManager(
                offload_dir=cfg.kv_offload_dir,
                host_cache_bytes=cfg.kv_host_cache_bytes,
                spill_budget_bytes=cfg.kv_spill_budget_bytes,
                spill_chunk_blocks=cfg.kv_spill_chunk_blocks,
                ring_depth=cfg.kv_ring_depth)
            self.sched.tiering = _TieringAdapter(self)
        if cfg.prefix_cache:
            self.prefix = PrefixCache(self.alloc,
                                      max_blocks=cfg.prefix_cache_blocks)
            self.sched.prefix_cache = self.prefix
            self.sched.on_prefix_hit = self._on_prefix_hit

        # ---- the (single) jitted step ------------------------------------ #
        layout = self._layout

        def step_fn(params, packed, previous, kp, vp, state, aux=None):
            ids, positions, state, tables, wb, wo = unpack_step(
                layout, packed, previous, state)
            more = {"with_expert_counts": True} if self._moe_experts else {}
            if aux is not None:
                # a hybrid stack's step takes its state, and each row's slot
                # and whether it carries a sequence, and gives the state back
                rows = packed[:4 * layout.rows].reshape(layout.rows, 4)
                more.update(aux=aux, slots=rows[:, 2], live=rows[:, 3] != 0)
            logits, kp, vp, *counts = model.paged_step(
                params, ids, positions, kp, vp, tables, wb, wo,
                chunk=layout.rows - layout.slots, **more)
            if aux is not None:
                aux, *counts = counts
            if mcfg.padded_vocab != mcfg.vocab_size:
                vmask = jnp.arange(mcfg.padded_vocab) < mcfg.vocab_size
                logits = jnp.where(vmask[None, None], logits, -1e30)
            # an MoE model's expert counts ride behind the token row in the
            # one flat int32 array the host fetches (no second transfer), and
            # that the next program takes as its ``previous``
            tokens = jnp.concatenate(
                [jnp.argmax(logits, axis=-1).astype(jnp.int32).reshape(-1),
                 *(c.reshape(-1) for c in counts)])
            return tokens, kp, vp, state, aux

        # arena and table donation = in-place update; CPU can't donate (jax
        # warns and copies), so only donate on real accelerators.  The token
        # array is never donated: the host fetches it behind the next launch
        donate = (3, 4, 5) + (6,) * (self._aux is not None)
        if jax.default_backend() == "cpu":
            donate = ()
        self._raw_step_fn = step_fn
        self._donate = donate
        self._step_fn = jax.jit(step_fn, donate_argnums=donate)

        # the program launched and not committed (at most one between two
        # ``step()`` calls), the token array of the last program launched
        # (zeros before the first: no row can ask for one), and how many steps
        # launched theirs before the row of the one before was on the host
        self._flight: Optional[_Flight] = None
        self._previous = self._no_tokens()
        self.steps_dispatched_ahead = 0
        # the number of the last program launched (the first is 1)
        self.programs_launched = 0

        # ---- resilience plane -------------------------------------------- #
        self._clock = time.monotonic
        # when the host was last settled, the ``t_launch`` or ``t_result`` it
        # took last (None: the step before left no work, or the program is
        # not compiled yet), and the last step's ``t_exit``: what the next
        # step's TURNAROUND_STATS are measured from
        self._t_settled: Optional[float] = None
        self._t_exit = 0.0
        # the ``t_result`` of the row landed last: where the device time of
        # the next program starts, if it was launched before that
        self._t_row = float("-inf")
        self.admission = AdmissionController(cfg)
        # bounded fetch: a wedged compiled program raises ServeStepTimeout
        # instead of parking the engine thread forever.  on_timeout releases
        # fault-injection wedges so the abandoned worker drains instead of
        # leaking (mirrors comm/recovery.py).  The launch runs inline: it
        # compiles, and XLA compilation is legitimate work that routinely
        # exceeds a steady-state step deadline
        self._bounded: Optional[BoundedCollective] = None
        if cfg.serve_step_timeout_s and cfg.serve_step_timeout_s > 0.0:
            self._bounded = BoundedCollective(
                deadline_s=float(cfg.serve_step_timeout_s),
                on_timeout=lambda err: release_wedges())
        self.incident_count = 0
        self.last_recovery_s = 0.0
        self._incident: Optional[Dict[str, Any]] = None  # /healthz latch

        self._rid_counter = 0
        self._expert_counts = np.zeros((0,), np.int32)   # of the last program
        self._futures: Dict[int, ServeFuture] = {}
        self.step_count = 0
        self.tokens_generated = 0
        self._started = time.monotonic()
        self._closed = False
        self._owns_telemetry = False    # init_serving flips for dict-built hubs
        # goodput ledger (telemetry/ledger.py): reuse the hub's ledger in
        # serve mode — step() attributes wall time, finished requests feed
        # the per-SLO tokens-within-TTFT-bound accounting
        self.ledger = getattr(telemetry, "ledger", None)
        self._restage_wait_ms = 0.0
        if self.ledger is not None:
            self.ledger.mode = "serve"
            self.ledger.slo_ttft_bounds_ms.update(
                {str(k): float(v)
                 for k, v in (cfg.slo_ttft_bound_ms or {}).items()})
            self.ledger.mark()
        obs = getattr(telemetry, "obs_server", None)
        if obs is not None:
            obs.add_health_check("serve_arena", self._arena_health)
            obs.add_health_check("serve_incident", self._incident_health)
        log_dist(
            f"ServingEngine ready: slots={cfg.max_batch_size}, "
            f"arena={cfg.num_blocks}x{cfg.block_size} tok "
            f"(max {self.max_blocks_per_seq} blocks/seq), "
            f"prefill_chunk={cfg.prefill_chunk}, dtype={self.dtype.__name__}, "
            f"relaid_leaves={self.relaid_leaves}, relaid_bytes={self.relaid_bytes}",
            ranks=[0])

    # ------------------------------------------------------------------ #
    @property
    def params(self):
        """The tree the step reads: the model's SERVING tree of the weights
        the engine was given (``model.serving_params``: a latent stack's
        up-projections transposed, every other leaf the caller's own), in the
        engine's dtype.  Assigning a tree in checkpoint layout is the one way
        weights enter; ``relaid_leaves`` and ``relaid_bytes`` say what the
        engine holds in a layout of its own (0 and 0: the caller's arrays)."""
        return self._params

    @params.setter
    def params(self, params) -> None:
        import jax
        import jax.numpy as jnp
        self._params, relaid = self.module.serving_params(jax.tree.map(
            lambda p: jnp.asarray(p, self.dtype)
            if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating) else p,
            params))
        self.relaid_leaves, self.relaid_bytes = len(relaid), sum(relaid.values())
        if self.registry is not None:
            self._g_relaid_leaves.set(self.relaid_leaves)
            self._g_relaid_bytes.set(self.relaid_bytes)

    # ------------------------------------------------------------------ #
    def _new_allocator(self) -> PagedKVAllocator:
        cfg = self._config
        return PagedKVAllocator(cfg.num_blocks * len(self._windows),
                                cfg.block_size, self.max_blocks_per_seq,
                                windows=self._windows, chunk=cfg.prefill_chunk,
                                run_blocks=self._run_blocks)

    def _new_aux(self):
        """A hybrid stack's compressed keys and states and an indexer's index
        keys, zeroed (None for a model that caches K and V, or a latent,
        alone).  Nothing else ever zeroes a state: a prompt chunk at position 0
        starts from zero whatever the slot holds."""
        from deepspeed_tpu.models import hybrid
        cfg = self._config
        return hybrid.init_aux(self.module.cfg, cfg.num_blocks, cfg.block_size,
                               cfg.max_batch_size, self.dtype) or None

    def _hybrid_stats(self, rows) -> Dict[str, int]:
        """What a hybrid stack's layers did in a step, from its rows'
        positions (``rows``: the upload's ``[rows, 4]`` view): slots whose
        state started from zero (a chunk at position 0) and bytes of state
        held (``state_bytes`` the linear layers', ``cca_state_bytes`` the cca
        layers', ``delta_state_bytes`` and ``delta_conv_bytes`` the delta
        layers', ``mamba_state_bytes`` and ``mamba_conv_bytes`` the mamba
        layers'); ``delta_state_moves`` / ``mamba_state_moves``: the delta /
        mamba layers' states read and written, one a live decode row a layer
        and one a layer for the step's chunk; of the sparse layers, keys the live rows attended against
        the keys resident before them, summed over sparse layers and K/V
        heads, live rows at or under ``dense_len`` and, summed over sparse
        layers, live rows past it (the rows whose table a layer's selection
        wrote); of the indexed layers,
        index keys the live rows scored (every key at or before them), keys
        they attended (``topk`` at most) and keys resident before them,
        summed over indexed layers, the bytes of index keys held, live rows at
        or under ``topk`` keys (``index_rows_all``: they take every key) and,
        summed over indexed layers, live rows past it (``index_rows_selected``:
        the rows whose keys a layer's selection chose); of a
        latent model's indexed layers in a step with a chunk, the keys of the
        extent of its table the chunk was compiled for and the keys of it the
        masked pass walked (``chunk_keys_extent``, ``chunk_keys_passed``)."""
        from deepspeed_tpu.models import hybrid
        mcfg = self.module.cfg
        first = rows[self._config.max_batch_size]
        out = {"state_slots_reset": int(first[3] != 0 and first[1] == 0)}
        out.update({name + "_bytes": int(a.nbytes) for name, a in self._aux.items()
                    if name.endswith(("state", "delta_conv", "mamba_conv"))})
        if mcfg.indexed_layers:
            t = rows[rows[:, 3] != 0, 1]
            per = mcfg.indexed_layers
            resident = int((t + 1).sum()) * per         # every one of them is scored
            every = int((t + 1 <= mcfg.indexer.topk).sum())
            out.update(
                index_keys_scored=resident, indexed_keys_resident=resident,
                indexed_keys_attended=int(hybrid.indexed_keys_attended(mcfg, t).sum()) * per,
                index_key_bytes=int(self._aux["ki"].nbytes),
                index_rows_all=every, index_rows_selected=(len(t) - every) * per)
            if mcfg.kv_lora_rank and first[3] != 0:     # a latent's chunk: its last position
                chunk = rows[self._config.max_batch_size:]
                out.update(self._chunk_keys(int(chunk[chunk[:, 3] != 0, 1].max())))
        for mixer, stat in (("delta", "delta_state_moves"), ("mamba", "mamba_state_moves")):
            if mixer in mcfg.mixers:
                decoding = int((rows[:self._config.max_batch_size, 3] != 0).sum())
                out[stat] = (decoding + int(first[3] != 0)) * mcfg.mixers.count(mixer)
        if "sparse" in mcfg.mixers:
            t = rows[rows[:, 3] != 0, 1]
            layers = mcfg.mixers.count("sparse")
            per = layers * mcfg.kv_heads
            dense = int((t + 1 <= mcfg.sparse.dense_len).sum())
            out.update(
                sparse_keys_attended=int(hybrid.keys_attended(mcfg, t).sum()) * per,
                sparse_keys_resident=int((t + 1).sum()) * per,
                sparse_rows_dense=dense,
                sparse_rows_selected=(len(t) - dense) * layers)
        return out

    def _chunk_keys(self, last: int) -> Dict[str, int]:
        """What a latent model's indexed layers passed over for a prompt
        chunk whose last position is ``last``, from that position alone: the
        keys of the extent the chunk's branch was compiled for
        (``models/gpt.py:CHUNK_EXTENTS``) and the keys in the tiles the
        masked pass walked (``ops/pallas/indexed_attention.py``), each times
        the indexed layers."""
        from deepspeed_tpu.models import gpt, hybrid
        from deepspeed_tpu.ops.pallas.indexed_attention import latent_keys_walked
        mcfg, BS = self.module.cfg, self._config.block_size
        widths = hybrid.chunk_extent_widths(self.max_blocks_per_seq, gpt.CHUNK_EXTENTS)
        extent = widths[min(last // (widths[0] * BS), len(widths) - 1)] * BS
        dr = mcfg.qk_rope_dim
        walked = latent_keys_walked(
            self._config.prefill_chunk, mcfg.n_head, mcfg.head_dim - dr, dr,
            mcfg.v_head_dim, extent, self.dtype, last)
        return {"chunk_keys_extent": extent * mcfg.indexed_layers,
                "chunk_keys_passed": walked * mcfg.indexed_layers}

    def _no_tokens(self):
        """What a program before the first would have handed on: the token
        array's shape, zeros, on the device (a transfer: nothing compiles)."""
        import jax
        return jax.device_put(np.zeros(
            (self._layout.rows + self._moe_experts * self._moe_count_rows,),
            np.int32))

    def _empty_tables(self):
        """The table state of an engine nobody is in: all trash, on the
        device (a transfer: nothing compiles)."""
        import jax
        return jax.device_put(np.full((self._layout.state_size,),
                                      PagedKVAllocator.TRASH, np.int32))

    def _span(self, name, **args):
        return maybe_span(name, self.tracer, **args)

    def _emit(self, kind, payload, step=None):
        if (self.ledger is not None and kind == "kv_restage"
                and payload.get("ok")):
            # exposed restage wait attributes to offload_stall on next step
            self._restage_wait_ms += float(payload.get("wait_ms", 0.0))
        if self.telemetry is not None:
            self.telemetry.emit(kind, payload, step=step)

    def _arena_health(self):
        """`/healthz` contribution: arena + tier occupancy (always ``ok``
        on its own — oversubscription is a designed-for state; the gauges
        give the operator the occupancy picture)."""
        st = self.sched.stats()
        total = int(self._config.num_blocks)
        used = int(st.get("blocks_in_use", 0))
        out = {"ok": True, "blocks_in_use": used, "blocks_total": total,
               "occupancy": round(used / total, 4) if total else 0.0,
               "active": int(st.get("active", 0)),
               "queue_depth": int(st.get("queue_depth", 0))}
        if self.tiering is not None:
            ts = self.tiering.stats()
            for key in ("kv_host_bytes", "kv_nvme_bytes"):
                if key in ts:
                    out[key] = ts[key]
        return out

    def _incident_health(self):
        """`/healthz` contribution: unhealthy while a serve incident is
        latched — a wedged step recovered in-process but the engine has
        not yet completed a clean step.  The latch clears on the first
        clean step after recovery."""
        out = {"ok": self._incident is None,
               "incidents": self.incident_count,
               "last_recovery_s": round(self.last_recovery_s, 4)}
        if self._incident is not None:
            out.update({k: self._incident[k] for k in ("step", "phase")})
        return out

    # ---- request lifecycle robustness --------------------------------- #
    def _expire_deadlines(self):
        """Cancel every request whose per-class deadline has passed —
        called at the step boundary with no row of such a request in flight,
        so a cancellation never races a compiled dispatch.  Frees arena
        blocks + staged tier copies and
        books the accumulated prefill as wasted compute."""
        if not self._config.deadline_ms:
            return
        now = self._clock()
        expired = self.sched.expired(now)
        if self._flight is not None and any(r.slot >= 0 for r in expired):
            # a request with a slot may have a row in flight: its token lands
            # first, and a request that this finishes is not cancelled
            self._drain()
            expired = self.sched.expired(now)
        for req in expired:
            wasted = req.prefilled
            self.sched.cancel(req)
            if self.ledger is not None:
                self.ledger.note_serve_expired(req.slo, wasted)
            self._emit("serve_expired", {
                "rid": req.rid, "slo": req.slo,
                "age_ms": (now - req.arrival) * 1000.0,
                "deadline_ms": (req.deadline_at - req.arrival) * 1000.0,
                "generated": len(req.generated),
                "wasted_prefill_tokens": wasted,
            }, step=self.step_count)

    def _update_admission(self):
        """Advance the shed ladder from the queue-age and TTFT-burn
        signals; rung changes are telemetered (and gauge-fed via the
        MetricsSink on flush)."""
        age = self.sched.oldest_wait_s(self._clock())
        state = "ok"
        mon = getattr(self.telemetry, "slo_monitor", None)
        if mon is not None:
            try:
                state = mon.state_for_metric("serve_ttft_ms")
            except Exception:
                state = "ok"
        prev = self.admission.level
        level = self.admission.evaluate(age, state)
        if level != prev:
            self._emit("serve_shed", {
                "event": "level", "level": level,
                "from": SHED_LEVELS[prev], "to": self.admission.level_name,
                "queue_age_ms": age * 1000.0, "ttft_state": state,
            }, step=self.step_count)

    # ---- launch, bounded fetch, commit + incident recovery -------------- #
    def _dispatch(self, phase: str, packed, reload, stats):
        """Launch one compiled step over the step's one upload ``packed``
        (:meth:`_pack`; ``reload``: the tables whole, when the edits did not
        fit it) and the token array of the program before, and return its own
        token array, still on the device, with the stamp ``t_launch``
        (:data:`TURNAROUND_STATS`).  Inline, under the ``dispatch`` span:
        nothing here waits for the chip (the program before may still be
        running: its outputs are this one's donated inputs), and the first
        call, and the first after an incident's re-jit, compiles.
        ``phase`` names the span pair and the fault point: ``decode`` when
        the program carries a decode row, ``prefill`` when it carries a
        prompt chunk alone."""
        import jax
        with self._span(f"serve.{phase}.dispatch", **stats):
            state = self._tables if reload is None else jax.device_put(reload)
            (tokens, self._k_pages, self._v_pages, self._tables,
             self._aux) = self._step_fn(
                 self.params, packed, self._previous, self._k_pages,
                 self._v_pages, state, self._aux)
            self._previous = tokens
            return tokens, self._clock()

    def _fetch(self, flight: _Flight):
        """The token row of a launched program on the host, with the stamp
        ``t_result``, under the ``serve_step_timeout_s`` deadline (inline when
        unbounded).  This device sync is exactly where a wedged program parks
        the thread, so it is the bounded callable: the ``fetch`` span goes to
        the worker thread with it, and the stamp is taken there and handed
        back with the program's record (:data:`PROGRAM_STATS`), which the span
        carries as stats known at its end.  A fetch over its deadline raises
        :class:`ServeStepTimeout` AFTER the in-process recovery
        (:meth:`_recover_incident`), and the worker it abandoned writes no
        record if it ever comes back."""
        phase, tokens, incidents = flight.phase, flight.tokens, self.incident_count

        def work():
            with self._span(f"serve.{phase}.fetch", **flight.at) as sp:
                fault_point("serve.step", step=self.step_count, phase=phase)
                # (inline fetch alone: under a deadline a wedged step
                # would leave the abandoned worker spinning for good)
                if self._config.poll_token_row and self._bounded is None:
                    tokens.copy_to_host_async()
                    while not tokens.is_ready():
                        time.sleep(0)
                row, t_result = np.asarray(tokens).reshape(-1), self._clock()
                record = {"ahead": flight.ahead, "device_ms": (
                    t_result - max(flight.t_launch, self._t_row)) * 1e3}
                if flight.host_ms is not None:
                    record["host_ms"] = flight.host_ms
                if self.incident_count == incidents:
                    sp.set(**record)
                return row, t_result, record["device_ms"]
        if self._bounded is None:
            return work()
        try:
            return self._bounded.run(work, op=phase, noun="serve step")
        except CollectiveTimeout as e:
            err = ServeStepTimeout(
                f"serve {phase} step {self.step_count} exceeded its "
                f"{e.deadline_s:.3f}s deadline", op=phase,
                deadline_s=e.deadline_s, step=self.step_count)
            self._recover_incident(err)
            raise err from e

    def _land(self, flight: _Flight) -> Dict[str, float]:
        """Fetch a launched program's token row and commit it: the chunk's
        last row yields the first token of a request whose prompt ended in
        it, every decode row its sequence's next.  The positions moved when
        the program was launched (:meth:`_launched`); only the token VALUES
        waited for the row.  A row whose request no longer holds that slot
        (it finished on an EOS the host saw a step late) is dropped.
        -> the row's :meth:`_moe_stats`."""
        row, t_result, device_ms = self._fetch(flight)
        self._t_settled = self._t_row = t_result
        if self.registry is not None:
            self._h_program.observe(device_ms)
        n = row.size - self._moe_experts * self._moe_count_rows
        tokens, self._expert_counts = row[:n], row[n:]
        moe_stats = self._moe_stats()
        holds = lambda req, slot: self.sched.active.get(slot) is req
        if flight.chunk is not None:
            req, slot, last, chunk = flight.chunk
            with self._span("serve.prefill.commit", program=flight.number, **chunk):
                # the chunk holding the last context token also yields the
                # next token — first-token latency includes no extra decode
                # step
                if last and holds(req, slot):
                    self._append_token(req, flight.number, int(
                        tokens[self._layout.slots + chunk["tokens"] - 1]))
        if flight.decode:
            with self._span("serve.decode.commit", batch=len(flight.decode),
                            program=flight.number, **moe_stats):
                for req, slot in flight.decode:
                    if holds(req, slot):
                        self._append_token(req, flight.number, int(tokens[slot]))
        return moe_stats

    def _drain(self) -> Dict[str, float]:
        """Land the row in flight, if there is one (-> its
        :meth:`_moe_stats`, or nothing).  Whatever is unusual
        does this FIRST and then goes on as an engine that is never ahead
        would: a preemption, a table reload, a restage, a deadline's
        cancellation of a request with a slot, :meth:`snapshot`,
        :meth:`close`, the last step of :meth:`ServeFuture.result`."""
        flight, self._flight = self._flight, None
        return self._land(flight) if flight is not None else {}

    def _ends_in_flight(self, req: Request) -> bool:
        """Whether the row in flight holds ``req``'s last token by
        ``max_new_tokens``: the host knows that without the row, and the
        program behind it has no row for ``req``."""
        return (self._flight is not None and req.rid in self._flight.feeds
                and len(req.generated) + 1 >= req.max_new_tokens)

    def _decode_ready(self) -> List[Request]:
        """The sequences with a decode row in the next program."""
        return [r for r in self.sched.decode_batch()
                if not self._ends_in_flight(r)]

    def _stays_ahead(self) -> bool:
        """Whether the program just launched stays in flight when ``step()``
        returns, so that the next one is launched before its row is fetched:
        exactly when an arrival in the meantime could not change the next
        program anyway.  The waiting queue is not empty, or no slot is free,
        or an admitted request still has prompt left (``next_prefill`` gives
        the one prompt lane to the oldest such request: a newcomer would wait
        behind it whatever happened).  Else (lane idle, queue empty, a slot
        free) the step is launch, fetch, commit, and whoever arrives next
        meets an engine that has seen every token."""
        sched = self.sched
        return bool(sched.waiting or not sched._free_slots
                    or any(r.needs_prefill for r in sched.active.values()))

    def _recover_incident(self, err: ServeStepTimeout):
        """In-process recovery from a wedged compiled step: drop the
        (possibly poisoned) executables and arena, rebuild from allocator
        + tier metadata, and requeue every in-flight request with
        ``prefilled=0`` — the preemption recompute contract, so the token
        streams continue identically.  Spilled host/NVMe copies of
        *waiting* requests survive (they never touch the device arena).
        Latches ``/healthz`` unhealthy until the first clean step."""
        import jax
        t0 = self._clock()
        self.incident_count += 1
        cfg, mcfg = self._config, self.module.cfg
        self._emit("serve_incident", {
            "event": "begin", "phase": err.op, "step": self.step_count,
            "deadline_s": err.deadline_s, "incident": self.incident_count,
            "in_flight": len(self.sched.active),
        }, step=self.step_count)
        if self.ledger is not None:
            # resident KV is about to be discarded: its prefill recomputes
            for r in self.sched.active.values():
                self.ledger.note_wasted_prefill(r.slo, r.prefilled)
        if self.tiering is not None:
            # no in-flight copy-ring task may still reference the arena
            # arrays we are about to drop
            self.tiering.drain()
        self._step_fn = jax.jit(self._raw_step_fn,
                                donate_argnums=self._donate)
        self._t_settled = None      # fresh jit: the next launch recompiles
        # whatever was launched is lost with the arena; its tokens are
        # computed again with the rest of each request
        self._flight, self._previous = None, self._no_tokens()
        self.alloc = self._new_allocator()
        self._k_pages, self._v_pages = init_arena(
            mcfg, cfg.num_blocks, cfg.block_size, dtype=self.dtype)
        self._tables = self._empty_tables()     # nobody has a slot again
        self._aux = self._new_aux()
        if self.prefix is not None:
            # cached pins point at pre-incident arena content: rebuild
            self.prefix = PrefixCache(self.alloc,
                                      max_blocks=cfg.prefix_cache_blocks)
            self.sched.prefix_cache = self.prefix
        requeued = self.sched.requeue_for_recovery(self.alloc)
        self._incident = {"at": t0, "step": self.step_count,
                          "phase": err.op}
        self.last_recovery_s = self._clock() - t0
        if self.ledger is not None:
            # the wedge wait (the expired deadline) plus the rebuild are
            # incident seconds, not productive step time
            self.ledger.note_comm_recovery(
                (err.deadline_s or 0.0) + self.last_recovery_s)
        self._emit("serve_incident", {
            "event": "recovered", "phase": err.op, "step": self.step_count,
            "requeued": len(requeued), "lost": 0,
            "recovery_s": self.last_recovery_s,
            "deadline_s": err.deadline_s, "incident": self.incident_count,
        }, step=self.step_count)

    def _on_preempt(self, victim: Request):
        if self.ledger is not None and not victim.spilled:
            # eviction without a spill record: the prefill is recomputed
            # from scratch on resume — those tokens are wasted work
            self.ledger.note_wasted_prefill(victim.slo, victim.prefilled)
        self._emit("serve_preempt", {
            "rid": victim.rid, "slo": victim.slo,
            "generated": len(victim.generated),
            "preemptions": victim.preemptions,
            "spilled": victim.spilled,
        }, step=self.step_count)

    def _on_prefix_hit(self, req: Request, blocks: List[int]):
        self._emit("prefix_hit", {
            "rid": req.rid, "slo": req.slo, "blocks": len(blocks),
            "tokens": len(blocks) * self._config.block_size,
            "prompt_tokens": len(req.prompt),
        }, step=self.step_count)

    def compiled_programs(self) -> int:
        """Number of XLA programs behind the serving step: 1 once anything
        ran, whatever the traffic (decode rows and the prompt chunk share
        the one ``[max_batch_size + prefill_chunk, 1]`` trace)."""
        return int(self._step_fn._cache_size())

    # ------------------------------------------------------------------ #
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               slo: str = "standard", temperature: float = 0.0) -> ServeFuture:
        """Queue one request; returns a :class:`ServeFuture`."""
        with self._span("serve.submit") as sp:
            if temperature:
                raise NotImplementedError(
                    "serving is greedy-only in this PR (temperature=0)")
            if slo not in SLO_PRIORITY:
                raise ValueError(
                    f"unknown slo class {slo!r}; expected one of "
                    f"{sorted(SLO_PRIORITY)} (a typo here would otherwise "
                    "silently demote the request to 'standard')")
            cfg, mcfg = self._config, self.module.cfg
            if not self.admission.admit_ok(slo):
                self._emit("serve_shed", {
                    "event": "rejected", "slo": slo,
                    "level": self.admission.level,
                    "level_name": self.admission.level_name,
                    "queue_depth": len(self.sched.waiting),
                }, step=self.step_count)
                raise ShedError(
                    f"admission ladder at {self.admission.level_name!r} is "
                    f"shedding {slo!r}-class requests (retry later or raise "
                    "the class)", slo=slo, level=self.admission.level)
            prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
            assert prompt, "empty prompt"
            mnt = int(max_new_tokens or cfg.max_new_tokens_default)
            # brownout rung: degrade before rejecting
            mnt = self.admission.cap_new_tokens(mnt)
            total = len(prompt) + mnt
            if total > mcfg.n_positions:
                raise ValueError(f"prompt+max_new_tokens {total} exceeds "
                                 f"n_positions {mcfg.n_positions}")
            if (self.alloc.blocks_for_tokens(total) > self.max_blocks_per_seq
                    or self.alloc.pages_for_tokens(total, total)
                    > self.alloc.num_blocks - 1):
                raise ArenaExhausted(
                    f"request needs {self.alloc.blocks_for_tokens(total)} "
                    f"blocks ({self.alloc.pages_for_tokens(total, total)} "
                    f"pages); a table holds {self.max_blocks_per_seq}, the "
                    f"arena {self.alloc.num_blocks - 1} pages")
            self._rid_counter += 1
            req = Request(rid=self._rid_counter, prompt=prompt,
                          max_new_tokens=mnt, slo=slo, arrival=self._clock())
            dl = float((cfg.deadline_ms or {}).get(slo, 0.0) or 0.0)
            if dl > 0.0:
                req.deadline_at = req.arrival + dl / 1e3
            sp.set(rid=req.rid)
            self.sched.submit(req)
            fut = ServeFuture(self, req)
            self._futures[req.rid] = fut
            self._emit("serve_request", {
                "event": "submitted", "rid": req.rid, "slo": slo,
                "prompt_tokens": len(prompt), "max_new_tokens": mnt,
                "queue_depth": len(self.sched.waiting),
            }, step=self.step_count)
            return fut

    # ------------------------------------------------------------------ #
    def step(self) -> Dict[str, Any]:
        """One engine step: expire deadlines, advance the shed ladder,
        admit, grow the decode-ready sequences, then launch ONE program over
        every decode-ready sequence and one prompt chunk.  A request whose
        prompt ends in this step's chunk decodes from the next program on.

        What the step fetches and commits depends on what the scheduler
        observes (:meth:`_stays_ahead`), not on any setting.  With the lane
        idle, the queue empty and a slot free it is its own program's row:
        launch, fetch, commit, and every token is on the host when the step
        returns.  Under a backlog the program stays in flight instead, and
        the NEXT step launches its program first and then fetches and commits
        this one's row, so the row's way back, the commit, the caller's time
        between two calls and the next admit, grow, build, upload and launch
        all pass while the chip runs: the spans open as admit, grow, build,
        dispatch (n+1), fetch (n), commit (n), stats.  A request then holds
        its slot and its pages until the commit of its last token, one step
        longer; an EOS is seen one step late and the extra row's token is
        dropped.  What does not fit this (:meth:`_drain`) lands the row in
        flight first.  Returns the step stats (of the program this step
        launched, under its number ``program``; ``tokens_generated`` counts
        what is committed).  A wedged program raises :class:`ServeStepTimeout`
        from the fetch of its row, *after* in-process recovery (see
        :meth:`_recover_incident`)."""
        t_enter, settled = self._clock(), self._t_settled
        with self._span("serve.admit") as sp:
            self._expire_deadlines()
            self._update_admission()
            if self._flight is not None and self._admit_is_unusual():
                self._drain()
            sp.set(admitted=len(self.sched.admit(self._clock())))
        n_chunk, ahead, host, turnaround, launched_stats = 0, 0, {}, {}, {}
        table_stats = {"table_edits": 0, "table_reloads": 0, "upload_bytes": 0}
        with self._span("serve.grow") as sp:
            # growth pass, oldest/strongest first: each decode step
            # writes one token per sequence, so capacity must exist
            # before the batch is built; eviction here removes victims
            # from `active`, which is why the chunk is chosen after it.
            # A growth that may need a victim lands the row in flight
            # first (a decode row grows where its token opens a block, by
            # at most a page a group)
            decode = sorted(self._decode_ready(),
                            key=lambda r: (r.priority, r.admit_seq))
            given_back = self.alloc.given_back_ever
            for r in decode:
                if (self._flight is not None
                        and r.prefilled % self.alloc.block_size == 0
                        and self.alloc.free_pages < self.alloc.n_groups):
                    self._drain()
                if r.state == DECODE:      # not evicted by an earlier r
                    self.sched.ensure_capacity(r, r.prefilled + 1)
            # the step's prompt chunk asks too: a window group holds a
            # long prompt a ring at a time (a table that only grows has
            # it all since admission, and this asks for nothing)
            pf = self.sched.next_prefill()
            if pf is not None:
                if (self._flight is not None and not self.alloc.can_allocate(
                        pf[0].rid, pf[1] + pf[2])):
                    self._drain()
                self.sched.ensure_capacity(pf[0], pf[1] + pf[2])
            decode = self._decode_ready()
            sp.set(batch=len(decode), pages_full=self.alloc.pages_full,
                   pages_window=self.alloc.pages_window,
                   pages_given_back=self.alloc.given_back_ever - given_back)
        with self._span("serve.prefill.build") as sp:
            runs = pf is not None or bool(decode)   # else: no program
            if runs:
                packed, rows, reload, table_stats = self._pack()
                if reload is not None and self._flight is not None:
                    self._drain()
                    decode = self._decode_ready()   # less who saw an EOS
            if pf is not None:
                req, start, n_chunk = pf
                chunk = {"rid": req.rid, "start": start, "tokens": n_chunk}
                sp.set(**chunk)
                self._chunk_rows(rows, req, start, n_chunk)
        if decode:
            with self._span("serve.decode.build", batch=len(decode)):
                self._decode_rows(rows, decode)
        # dispatched ahead: the chip still has the program before
        idle_since = None if self._flight is not None else self._t_settled
        if runs:
            # one dispatch a program, and later one fetch: named for the
            # decode rows when it carries any (`batch`: every live row)
            phase, at = (("decode", {"batch": len(decode) + n_chunk})
                         if decode else ("prefill", chunk))
            self.programs_launched += 1
            launched_stats = {"program": self.programs_launched}
            at = dict(at, chunk_tokens=n_chunk, **launched_stats)
            ahead = int(self._flight is not None)
            tokens, t_launch = self._dispatch(phase, packed, reload, at)
            self._t_settled = t_launch
            if settled is not None:      # the host's parts, known at the launch
                host = {"commit_ms": (self._t_exit - settled) * 1e3,
                        "outside_ms": (t_enter - self._t_exit) * 1e3,
                        "prepare_ms": (t_launch - t_enter) * 1e3}
            launched = _Flight(
                tokens, phase, at, [(r, r.slot) for r in decode],
                pf and (req, req.slot, start + n_chunk >= req.prefill_len, chunk),
                self._launched(decode, pf), t_launch, ahead,
                sum(host.values()) if host else None)
            if self._aux is not None:
                table_stats.update(self._hybrid_stats(rows))
        moe_stats = self._drain()       # the program before: behind the launch
        if runs and self._stays_ahead():
            self._flight = launched
        elif runs:
            moe_stats = self._land(launched)
        if host:
            turnaround = dict(
                host, turnaround_ms=(0.0 if idle_since is None
                                     else (t_launch - idle_since) * 1e3),
                result_wait_ms=(self._t_settled - t_launch) * 1e3)
            if self.registry is not None:
                self._h_turnaround.observe(turnaround["turnaround_ms"])
        # how attention took the step: the queries a row of the chunk held
        # (0: no chunk in the step) and the rows its calls ran; the rows that
        # went through the dense matrices (the chunk's only with a chunk);
        # whether the step was dispatched ahead, and its own turn-round, where
        # there was one; the program it launched
        self.steps_dispatched_ahead += ahead
        on_span = dict(
            table_stats, tile_runs_pct=self._tile_runs_pct(),
            chunk_queries_per_row=self.chunk_queries_per_row if n_chunk else 0,
            attention_rows=self.attention_rows if runs else 0,
            dense_rows=(self._layout.rows if n_chunk
                        else self._layout.slots) if runs else 0,
            dispatched_ahead=ahead, **launched_stats, **turnaround)
        with self._span("serve.stats", **on_span):
            stats = self._close_step(len(decode), n_chunk, int(runs),
                                     dict(moe_stats, **on_span))
        if not runs or not self.sched.has_work:
            self._t_settled = None      # from here the chip waits for WORK
        self._t_exit = self._clock()
        if self.registry is not None:
            self._h_step.observe((self._t_exit - t_enter) * 1e3)
        return stats

    def _admit_is_unusual(self) -> bool:
        """Whether admission may do more than hand free slots to waiting
        requests: restage a spilled request's blocks into the arena, or
        preempt a weaker class for a stronger one (``slo_preemption``).  Both
        land the row in flight first."""
        waiting = self.sched.waiting
        if not waiting or not self.sched._free_slots:
            return False
        if self.tiering is not None and any(r.spilled for r in waiting):
            return True
        return bool(self._config.slo_preemption and self.sched.active) and (
            min(r.priority for r in waiting)
            < max(r.priority for r in self.sched.active.values()))

    def _launched(self, decode: List[Request], pf) -> Dict[int, int]:
        """What the host knows of a program the moment it is launched, without
        its row: its K/V is resident before anything later runs, so every
        position moves now, and a prompt whose last chunk this was decodes
        from the next program on.  -> the program's ``feeds``: for each
        request whose next input is a token of this program, the row that
        makes it."""
        feeds = {}
        for r in decode:
            r.prefilled += 1            # the fed token's KV
            feeds[r.rid] = r.slot
        if pf is not None:
            req, _, n = pf
            req.prefilled += n
            if req.prefilled >= req.prefill_len:
                if self.prefix is not None and not self.admission.brownout:
                    # the prompt's full blocks hold valid KV for every later
                    # program: pin them for later requests sharing this
                    # prefix (idempotent re-insert; paused under brownout —
                    # pinning competes with admission for blocks exactly
                    # when the arena is the bottleneck)
                    self.prefix.insert(req.prompt,
                                       self.alloc.owned_blocks(req.rid))
                req.state = DECODE
                feeds[req.rid] = self._layout.slots + n - 1
        return feeds

    def _tile_runs_pct(self) -> float:
        """Of the tiles the live sequences' tables hold, a window group's
        ring as a full group's table, the share that are whole runs of
        consecutive pages: what the attention kernel fetches with one copy
        where it can (the allocator's own counts, nothing read from the
        device; ``paged_mla_attention`` and ``paged_gqa_attention``).  0
        where the kernel copies page by page (``run_blocks`` 1: no tile is
        counted)."""
        held = self.alloc.tiles_held
        return 100.0 * self.alloc.tiles_run / held if held else 0.0

    def _moe_stats(self) -> Dict[str, float]:
        """How the last program's live rows spread over the experts the
        router chooses among (summed over layers); nothing for a dense
        model.  ``moe_experts_touched``: the experts with an assignment, of
        the sum over layers, or where the program counts a layer apart (a
        hybrid stack) of each layer and of the experts its bank HOLDS: what
        the step reads of the bank here (a whole bank holds them all)."""
        by_layer = self._expert_counts.reshape(-1, self._moe_experts or 1)
        counts = by_layer.sum(axis=0)
        if not counts.size or not counts.any():
            return {}
        first, held = self.module.cfg.bank_experts
        here = by_layer[:, first:first + held] if self._hybrid else by_layer
        return {"moe_load_max_over_mean": float(counts.max() / counts.mean()),
                "moe_experts_touched": int((here > 0).sum()),
                # of the live rows' assignments, those on experts held here
                "moe_assignments": int(counts.sum()),
                "moe_assignments_held": int(counts[first:first + held].sum())}

    def _close_step(self, decode_batch: int, prefill_tokens: int,
                    programs: int,
                    of_the_program: Dict[str, Any]) -> Dict[str, Any]:
        """What a clean step ends with: the incident latch, the ledger, the
        stats dict (``of_the_program``: what ``step()`` counted and timed of
        its program, in it and so in the record), gauges and the periodic
        ``serve_step`` record."""
        if self._incident is not None:
            # first clean step after an incident: release the latch
            self._emit("serve_incident", {
                "event": "cleared", "phase": self._incident["phase"],
                "incident_step": self._incident["step"],
            }, step=self.step_count)
            self._incident = None
        self.step_count += 1
        if self.ledger is not None:
            self.ledger.on_step(self.step_count,
                                offload_wait_s=self._restage_wait_ms / 1e3)
            self._restage_wait_ms = 0.0
        stats = dict(self.sched.stats(), decode_batch=decode_batch,
                     prefill_tokens=prefill_tokens, programs=programs,
                     tokens_generated=self.tokens_generated,
                     shed_level=self.admission.level,
                     incidents=self.incident_count,
                     paged_tile_pages=self.paged_tile_pages,
                     elapsed_ms=(time.monotonic() - self._started) * 1000.0,
                     **of_the_program)
        if self.tiering is not None:
            stats.update(self.tiering.stats())
        if self.prefix is not None:
            stats.update(self.prefix.stats())
        if self.registry is not None:
            for gauge, key in ((self._g_queue, "queue_depth"),
                               (self._g_active, "active"),
                               (self._g_blocks, "blocks_in_use"),
                               (self._g_host_bytes, "kv_host_bytes"),
                               (self._g_nvme_bytes, "kv_nvme_bytes")):
                v = stats.get(key)
                if isinstance(v, (int, float)):
                    gauge.set(v)
            lookups = stats.get("prefix_lookups")
            if lookups:
                self._g_prefix_rate.set(
                    int(stats.get("prefix_hits", 0)) / int(lookups))
        if (self.telemetry is not None and self._config.telemetry_every
                and self.step_count % self._config.telemetry_every == 0):
            self._emit("serve_step", stats, step=self.step_count)
            if self.registry is not None:
                # drain the emit buffer so event-derived metrics (TTFT,
                # restage, preemption) stay live for /metrics scrapes,
                # then run the pod fold at its own cadence
                self.telemetry.flush()
                self.telemetry.maybe_snapshot(self.step_count)
        return stats

    def run(self, max_steps: int = 1_000_000) -> int:
        """Drive until every queued/active request finishes (expired
        requests leave the queue by cancellation).  A ServeStepTimeout
        incident does not abort the drain — the engine recovered before
        raising, so the loop keeps going; the step bound still applies
        (wedged attempts count toward it).  Returns the number of
        completed engine steps."""
        start = self.step_count
        steps = 0
        while self.sched.has_work:
            if steps >= max_steps:
                raise TimeoutError(f"serving drain exceeded {max_steps} steps")
            try:
                self.step()
            except ServeStepTimeout:
                pass       # recovered in-process; requests are requeued
            steps += 1
        return self.step_count - start

    def close(self):
        """Release the resilience + tiering backends: stop the bounded
        dispatch worker, drain the tiering copy ring, close the staging
        pool (and an owned tempdir / telemetry hub).  Idempotent."""
        if self._closed:
            return
        self._closed = True
        try:
            self._drain()       # no token of a launched program is lost
        except ServeStepTimeout:
            pass                # recovered: its requests wait, as after run()
        if self._bounded is not None:
            self._bounded.shutdown()
        if self.tiering is not None:
            self.tiering.drain()
            self.tiering.close()
        if self._owns_telemetry and self.telemetry is not None:
            try:
                self.telemetry.close()
            except Exception:
                pass

    # ---- warm restart -------------------------------------------------- #
    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready warm-restart state: the scheduler queue + per-request
        progress — prompts, generated-so-far, remaining deadline — but NOT
        KV bytes (recompute on restore keeps the snapshot tiny and the
        token streams identical).  Take it between steps (a row in flight
        lands first, so every token a program has made is in it); an
        elastic-agent relaunch feeds it to :meth:`restore` on a fresh
        engine."""
        try:
            self._drain()
        except ServeStepTimeout:
            pass                # recovered: every request waits, none lost
        now = self._clock()
        in_flight = sorted(
            list(self.sched.waiting) + list(self.sched.active.values()),
            key=lambda r: r.submit_seq)
        reqs = []
        for r in in_flight:
            reqs.append({
                "rid": r.rid,
                "prompt": [int(t) for t in r.prompt],
                "generated": [int(t) for t in r.generated],
                "max_new_tokens": int(r.max_new_tokens),
                "slo": r.slo,
                "age_s": now - r.arrival,
                "deadline_remaining_s": (
                    None if r.deadline_at is None else r.deadline_at - now),
                "preemptions": int(r.preemptions),
            })
        return {"schema": 1, "requests": reqs,
                "rid_counter": int(self._rid_counter),
                "step_count": int(self.step_count)}

    def restore(self, snap: Dict[str, Any]) -> List[ServeFuture]:
        """Resume a :meth:`snapshot` on this (idle) engine: every request
        re-enters the waiting queue with ``prefilled=0`` — admission
        re-prefills prompt + generated-so-far, so greedy decoding
        continues the identical stream.  Remaining deadlines are
        re-anchored to this engine's clock (already-expired ones cancel on
        the first step).  Returns the new futures in submit order."""
        assert not self.sched.waiting and not self.sched.active, (
            "restore() needs an idle engine (fresh or fully drained)")
        now = self._clock()
        futures = []
        for d in snap.get("requests", []):
            req = Request(rid=int(d["rid"]),
                          prompt=[int(t) for t in d["prompt"]],
                          max_new_tokens=int(d["max_new_tokens"]),
                          slo=str(d.get("slo", "standard")),
                          arrival=now - float(d.get("age_s", 0.0)))
            req.generated = [int(t) for t in d.get("generated", [])]
            req.preemptions = int(d.get("preemptions", 0))
            rem = d.get("deadline_remaining_s")
            if rem is not None:
                req.deadline_at = now + float(rem)
            self.sched.submit(req)
            fut = ServeFuture(self, req)
            self._futures[req.rid] = fut
            futures.append(fut)
        self._rid_counter = max(self._rid_counter,
                                int(snap.get("rid_counter", 0)))
        return futures

    # ------------------------------------------------------------------ #
    def _pack(self):
        """The step's one upload (:class:`StepLayout`), with no row live yet
        and what the allocator recorded since the last program in it
        -> (the flat array, its ``[rows, 4]`` view for the row builders, the
        tables whole or None, the step's table stats).  When the edits do
        not fit (several long admissions in one step) the tables go whole,
        beside an upload that edits nothing."""
        lay = self._layout
        packed = np.zeros((lay.packed_size,), np.int32)
        rows = packed[:4 * lay.rows].reshape(lay.rows, 4)
        cleared, edits = self.alloc.drain_edits()
        at = 4 * lay.rows + lay.slots
        packed[at::2] = lay.state_size              # past the end: dropped
        reload = None
        if len(edits) > lay.edits:
            reload = np.concatenate([t.reshape(-1) for t in
                                     self.alloc.slot_tables(lay.slots)])
        else:
            packed[4 * lay.rows:at][cleared] = 1
            if edits:
                g, slot, col, block = np.asarray(edits, np.int32).T
                offsets, widths = np.asarray(lay.offsets), np.asarray(lay.widths)
                packed[at:at + 2 * len(edits):2] = (
                    offsets[g] + slot * widths[g] + col)
                packed[at + 1:at + 2 * len(edits):2] = block
        stats = {"table_edits": 0 if reload is not None
                 else len(cleared) + len(edits),
                 "table_reloads": int(reload is not None),
                 "upload_bytes": packed.nbytes + (
                     0 if reload is None else reload.nbytes)}
        return packed, rows, reload, stats

    def _chunk_rows(self, rows, req: Request, start: int, n: int):
        """One prompt chunk into the rows behind the slots, a token a row,
        all reading the table of the request's slot; stamps the residency's
        first."""
        if req.prefill_started_at is None:
            req.prefill_started_at = self._clock()
        req.prefill_chunks += 1
        first = self._config.max_batch_size
        rows[first:first + n, 0] = req.context[start:start + n]
        rows[first:first + n, 1] = np.arange(start, start + n)
        rows[first:first + n, 2:] = req.slot, 1

    def _decode_rows(self, rows, reqs: List[Request]):
        """Every decode-ready sequence into the row of its slot: the
        context's last token (without building the context) at the position
        behind what is resident.  Where that token is a row of the program in
        flight, the row says which (:func:`unpack_step`)."""
        feeds = self._flight.feeds if self._flight is not None else {}
        slots = [r.slot for r in reqs]
        rows[slots, 0] = [-1 - feeds[r.rid] if r.rid in feeds
                          else (r.generated or r.prompt)[-1] for r in reqs]
        rows[slots, 1] = [r.prefilled for r in reqs]
        rows[slots, 2] = slots
        rows[slots, 3] = 1

    def _append_token(self, req: Request, program: int, tok: int):
        req.generated.append(tok)
        self.tokens_generated += 1
        if req.first_token_at is None:
            req.first_token_at = self._clock()
            # the program's own TTFT, split where it was spent; the three
            # waits sum to first_token_at - arrival
            ms = lambda a, b: (b - a) * 1e3
            with self._span(
                    "serve.first_token", rid=req.rid, program=program,
                    chunks=req.prefill_chunks,
                    queue_ms=ms(req.arrival, req.admitted_at),
                    lane_wait_ms=ms(req.admitted_at, req.prefill_started_at),
                    prefill_ms=ms(req.prefill_started_at, req.first_token_at)):
                pass
        if req.done(self._config.eos_token_id):
            req.finished_at = self._clock()
            self.sched.finish(req)
            ttft = req.first_token_at - req.arrival
            latency = req.finished_at - req.arrival
            if self.ledger is not None:
                self.ledger.note_serve_request(req.slo, ttft * 1000.0,
                                               len(req.generated))
            self._emit("serve_request", {
                "event": "finished", "rid": req.rid, "slo": req.slo,
                "prompt_tokens": len(req.prompt),
                "new_tokens": len(req.generated),
                "ttft_ms": ttft * 1000.0,
                "latency_ms": latency * 1000.0,
                "tokens_per_sec": len(req.generated) / max(latency, 1e-9),
                "preemptions": req.preemptions,
            }, step=self.step_count)


def init_serving(model=None, config=None, **kwargs):
    """Module-level helper in the ``deepspeed.init_inference`` style: merge
    a ``{"serving": {...}}`` (or flat) config dict + kwargs.  The nested
    form is collapsed FIRST and kwargs applied after, so engine kwargs
    (``params=``, ``telemetry=``, ...) are never silently discarded by a
    full ds_config — and explicit kwargs always win over config keys."""
    cfg_dict = dict(config or {})
    if "serving" in cfg_dict:
        cfg_dict = dict(cfg_dict["serving"])
    cfg_dict.update(kwargs)
    params = cfg_dict.pop("params", None)
    telemetry = cfg_dict.pop("telemetry", None)
    tracer = cfg_dict.pop("tracer", None)
    seed = cfg_dict.pop("model_seed", None)
    owns_telemetry = False
    if isinstance(telemetry, dict):
        from deepspeed_tpu.runtime.config import DeepSpeedTelemetryConfig
        from deepspeed_tpu.telemetry import TelemetryHub
        tcfg = DeepSpeedTelemetryConfig(**telemetry)
        telemetry = TelemetryHub.from_config(tcfg) if tcfg.enabled else None
        owns_telemetry = telemetry is not None
    cfg = DeepSpeedServingConfig(**cfg_dict)
    eng = ServingEngine(model, config=cfg, params=params, seed=seed,
                        telemetry=telemetry, tracer=tracer)
    # a hub built here from a config dict has no other owner: the engine
    # closes it (final flush + ops-server shutdown) on close()
    eng._owns_telemetry = owns_telemetry
    return eng
