"""What the two serving kinds share: the engine built from a configuration
file, the one loop that submits what is due and steps the engine, the stamps
the benchmark takes from outside, and the check of served tokens against the
plain reference.

The loop is the only driver of the engine (``ServingEngine`` has no thread of
its own: whoever waits, steps), so a request that falls due while a step runs
is submitted when that step ends.  That lateness is part of what a caller of
this engine sees; TTFT is timed from when the request was DUE, so it is
counted, and it is reported as ``gen_lateness_p99_ms.ttft``.
"""

import time

import numpy as np

from benchmarks.lib import arith
from benchmarks.lib.build import jax_seed, model_from
from benchmarks.lib.cells import resolve
from benchmarks.lib.device import span

# A served token must be the plain reference's best within this margin of
# logit.  After chip_smoke.TIE_TOL: at random weights the two best logits are
# routinely one bf16 step (0.0156) apart, and the paged bf16 program and the
# float32 reference round along different paths, so a near-tie may fall
# either way; a cache of garbage gave gaps of 2.1 to 2.4 (PERF.md, PR 21).
TIE_TOL = 0.0625


class Sent:
    """One request as the benchmark sees it."""
    __slots__ = ("due", "submitted", "future", "prompt", "max_new", "measured",
                 "prefill_seen", "refused")

    def __init__(self, due, prompt, max_new, measured):
        self.due, self.prompt, self.max_new = due, prompt, int(max_new)
        self.measured, self.submitted, self.future = measured, None, None
        self.prefill_seen, self.refused = None, False

    @property
    def request(self):
        return self.future.request if self.future is not None else None


class Serving:
    def __init__(self, cell, args, ctx):
        import jax
        import jax.numpy as jnp
        import deepspeed_tpu
        cfg = cell.config
        self.cell, self.ctx = cell, ctx
        self.model = model_from(cfg)
        mcfg = self.model.cfg
        dtype = jnp.dtype(cfg["dtype"])
        with ctx["phase"]("weights"):
            # on the device, in one jitted call from the seed, in the type
            # they are served in
            self.params = jax.jit(lambda key: jax.tree.map(
                lambda p: p.astype(dtype), self.model.init_params(key)))(
                    jax.random.PRNGKey(jax_seed(args.seed)))
            serve = cfg["serve"]
            serving = dict(serve["serving"])
            from deepspeed_tpu.serving.config import DeepSpeedServingConfig
            block = serving.get("block_size", DeepSpeedServingConfig().block_size)
            lanes = mcfg.kv_heads * mcfg.head_dim
            per_block = 2 * mcfg.n_layer * block * lanes * dtype.itemsize
            serving["num_blocks"] = int(serve["arena_bytes"]) // per_block
            # the queue admits the traffic's whole backlog (the program's
            # default bound where the backlog is no deeper)
            serving.setdefault("max_queue", max(
                DeepSpeedServingConfig().max_queue,
                int(cell.traffic.get("backlog_requests", 0))))
            self.engine = deepspeed_tpu.init_serving(
                model=self.model, params=self.params, config={"serving": serving})
        self.slots = int(serving["max_batch_size"])
        self.block, self.lanes = block, lanes
        self.num_blocks = serving["num_blocks"]
        self.chunk = int(self.engine._config.prefill_chunk)
        self.sent, self.steps = [], []
        self.waiting_prefill = []
        self.clock = time.monotonic        # the engine's own clock

    # ---- driving -------------------------------------------------------- #
    def warm(self):
        """Both programs (one prompt chunk; one decode step over all slots),
        compiled or loaded from the cache, before anything is timed."""
        rng = np.random.default_rng(0)
        with self.ctx["phase"]("compile_or_load"):
            prompt = rng.integers(0, self.model.cfg.vocab_size, 24)
            self.engine.submit(prompt, max_new_tokens=3).result()

    def submit(self, s):
        with span("bench.submit"):
            try:
                s.future = self.engine.submit(s.prompt, max_new_tokens=s.max_new)
            except Exception:
                s.refused = True
            s.submitted = self.clock()
        self.sent.append(s)
        if not s.refused:
            self.waiting_prefill.append(s)

    def step(self, record=True):
        t0 = self.clock()
        with span("bench.engine_step"):
            stats = self.engine.step()
        t1 = self.clock()
        still = []
        for s in self.waiting_prefill:
            if s.request.prefilled > 0:
                s.prefill_seen = t0
            else:
                still.append(s)
        self.waiting_prefill = still
        if record:
            self.steps.append((t0, t1, stats["decode_batch"],
                               stats["prefill_tokens"], stats["tokens_generated"],
                               stats["blocks_in_use"], stats["preemptions"]))
        return stats

    @property
    def has_work(self):
        return self.engine.sched.has_work

    def snapshot(self):
        """rid -> (prompt tokens, tokens resident, tokens generated) of every
        request sent so far: two of these bracket the traced window for the
        paged kernel's byte count."""
        return {s.request.rid: (len(s.request.prompt), s.request.prefilled,
                                len(s.request.generated))
                for s in self.sent if s.request is not None}

    # ---- after the window ------------------------------------------------ #
    def step_counters(self, steps):
        a = np.asarray([(t1 - t0, d, p, b, pre) for t0, t1, d, p, _, b, pre in steps])
        return {
            "serve_step_ms": 1e3 * float(np.median(a[:, 0])),
            "serve_step_max_ms": 1e3 * float(a[:, 0].max()),
            "decode_batch_mean": float(a[:, 1].mean()),
            "steps": len(steps),
            "steps_with_prefill": int((a[:, 2] > 0).sum()),
            "kv_blocks_peak_pct": 100.0 * float(a[:, 3].max()) / (self.num_blocks - 1),
            "preemptions": float(a[-1, 4] - a[0, 4]),
        }

    def slow_steps(self, steps, t0, factor=4.0):
        """Steps that took over ``factor`` times the median, as (seconds into
        the window, seconds taken, prompt tokens, decode batch): a stall of
        the host or the device shows here with its moment."""
        med = float(np.median([st[1] - st[0] for st in steps]))
        return [(round(st[0] - t0, 3), round(st[1] - st[0], 3), st[3], st[2])
                for st in steps if st[1] - st[0] > factor * med][:20]

    def paged_model(self, before, after, decode_steps):
        """Operations and bytes the paged kernel needed between two
        snapshots, per layer-call summed over rows, from the lengths alone:
        every decode step reads, for each live row, the blocks that hold its
        resident tokens (an idle slot reads its one trash block); every
        prompt chunk reads the blocks up to its end."""
        mcfg = self.model.cfg
        row = lambda resident, sq: arith.paged_attention_row(
            resident, sq, self.block, self.lanes, mcfg.n_head, mcfg.head_dim,
            self.params["wte"].dtype.itemsize)
        flops = nbytes = live_rows = 0
        for rid, (plen, res1, gen1) in after.items():
            _, res0, gen0 = before.get(rid, (plen, 0, 0))
            # prompt chunks run in between: starts res0, res0+chunk, .. < plen
            if gen0 == 0 and res0 < plen:
                for start in range(res0, min(res1, plen), self.chunk):
                    f, b = row(start, self.chunk)
                    flops, nbytes = flops + f, nbytes + b
            # decode steps in between: each generated token but the one the
            # last prompt chunk yields; resident lengths end at res1 - 1
            d = (gen1 - gen0) - (1 if gen0 == 0 and gen1 > 0 else 0)
            for resident in range(res1 - d, res1):
                f, b = row(resident, 1)
                flops, nbytes = flops + f, nbytes + b
            live_rows += max(d, 0)
        idle_rows = decode_steps * self.slots - live_rows
        f, b = row(0, 1)
        return (flops + idle_rows * f) * mcfg.n_layer, \
            (nbytes + idle_rows * b) * mcfg.n_layer

    @staticmethod
    def step_rows(steps):
        """The live rows (decode rows + prompt tokens) of each step."""
        return [int(st[2] + st[3]) for st in steps]

    def paged_counters(self, snaps, steps):
        """``paged_model`` over the traced stretch, as counters, and the
        stretch's rows a step (``readers/step_share.py``)."""
        n_dec = sum(1 for st in steps if st[2] > 0)
        flops, nbytes = self.paged_model(snaps["before"], snaps["after"], n_dec)
        return {"paged_flops": flops, "paged_bytes": nbytes,
                "traced_step_rows": self.step_rows(steps)}

    def check_sample(self, candidates, k, rng):
        """A seeded sample of ``k`` requests with tokens, held to the plain
        reference: every generated token must be the reference's best within
        ``TIE_TOL`` of logit, teacher-forced through one full forward pass.
        -> (number checked, number wrong, largest gap)."""
        import jax
        import jax.numpy as jnp
        with_tokens = [s for s in candidates
                       if s.request is not None and len(s.request.generated) > 0]
        if not with_tokens:
            return 0, 0, 0.0
        pick = rng.choice(len(with_tokens), size=min(k, len(with_tokens)),
                          replace=False)
        ref = self.cell.config["reference"]
        logits_fn, kw = resolve(ref["logits"]), ref["kwargs"]
        P = self.model.cfg.n_positions
        ids = np.zeros((len(pick), P), np.int32)
        lo = np.zeros(len(pick), np.int32)
        hi = np.zeros(len(pick), np.int32)
        for j, i in enumerate(pick):
            r = with_tokens[i].request
            seq = list(r.prompt) + list(r.generated)
            ids[j, :len(seq)] = seq
            lo[j], hi[j] = len(r.prompt) - 1, len(seq) - 1

        def gaps(params, ids, lo, hi):
            def one(a):
                row, l, h = a
                lg = logits_fn(params, row, **kw)                  # [P, V]
                nxt = jnp.roll(row, -1)
                gap = lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
                pos = jnp.arange(row.shape[0])
                return jnp.where((pos >= l) & (pos < h), gap, 0.0).max()
            return jax.lax.map(one, (ids, lo, hi))

        worst = np.asarray(jax.jit(gaps)(self.params, jnp.asarray(ids),
                                         jnp.asarray(lo), jnp.asarray(hi)))
        return len(pick), int((worst > TIE_TOL).sum()), float(worst.max())

    def close(self):
        self.engine.close()
