"""InferenceEngine (reference ``deepspeed/inference/engine.py:89``).

The reference wraps an HF torch model, swaps its transformer blocks for
fused CUDA modules (module_inject), builds an inference TP process group,
and optionally captures CUDA graphs.  TPU-native redesign:

* "Injection" = choosing the model's fused decode path: a model here is
  an object implementing the DECODE PROTOCOL —
  ``init_params(rng)`` / ``partition_specs()`` (optional) /
  ``apply_with_cache(params, input_ids, cache) -> (logits, cache)`` /
  ``init_cache(batch, max_len)`` / ``generate(...)`` — which the GPT
  family implements via ``gpt_apply_with_cache`` (KV cache per layer,
  the analogue of ``inference_context.h``'s workspace).
* TP: parameters are placed by the model's partition specs over a mesh
  whose ``tensor`` axis has ``tensor_parallel.tp_size`` devices — the
  AutoTP analogue (``module_inject/auto_tp.py:13``) is that specs are
  *derived from the model structure*, not hand-listed per architecture.
* CUDA graphs -> jit: each (batch, seq) decode program is compiled once
  and replayed; ``enable_cuda_graph`` is accepted and ignored.
* Checkpoint loading accepts the training engine's checkpoints
  (``load_checkpoint``) for the same model.
"""

import inspect
import time
from collections import OrderedDict
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.telemetry.tracing import maybe_span
from deepspeed_tpu.utils.logging import log_dist


class InferenceEngine:

    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None,
                 params=None, mesh=None, seed: int = 0, policy=None,
                 telemetry=None, tracer=None):
        self._config = config or DeepSpeedInferenceConfig()
        # per-request latency/throughput records; None (the default) keeps
        # serving fully async — no block_until_ready is ever issued
        self.telemetry = telemetry
        # span tracing; None falls back to the process-global tracer (set
        # by a co-resident training engine or by the serving harness)
        self.tracer = tracer
        self._request_count = 0
        self.dtype = self._config.jnp_dtype
        # dtype="int8" means weight-only int8 (reference quantizes injected
        # weights when config.dtype == torch.int8, GroupQuantizer
        # ``module_inject/replace_module.py:138``); compute stays bf16
        self.quantize_weights = (self.dtype == jnp.int8
                                 and self._config.quant.enabled
                                 and self._config.quant.weight.enabled)
        if self.dtype == jnp.int8:
            self.dtype = jnp.bfloat16

        # ---- foreign-model injection (reference :180-204 → module_inject)
        # an HF torch model is converted to the fused scan decode path;
        # its weights become the params pytree (TP slicing = sharding).
        # ``policy`` is the custom-architecture escape hatch (reference
        # ``injection_policy`` kwarg); caller-supplied ``params`` win over
        # the weights derived from the HF state dict.
        from deepspeed_tpu.module_inject.replace_module import (inject_hf_model,
                                                                is_hf_model)
        if is_hf_model(model):
            model, injected = inject_hf_model(model, policy=policy,
                                              dtype=self.dtype)
            params = injected if params is None else params
            log_dist("module_inject: replaced HF model with fused decode path",
                     ranks=[0])
        self.module = model

        # ---- mesh: inference TP group (reference :261) ----------------- #
        if mesh is None:
            if mesh_lib.has_mesh():
                mesh = mesh_lib.get_mesh()
            else:
                tp = max(int(self._config.tensor_parallel.tp_size), 1)
                n = jax.device_count()
                assert n % tp == 0, f"tp_size {tp} does not divide {n} devices"
                spec = mesh_lib.MeshSpec(tensor=tp, data=n // tp, device_count=n)
                mesh = spec.build()
                mesh_lib.set_mesh(mesh, spec)
        self.mesh = mesh

        # propagate inference dtype via a shallow model copy — never mutate
        # the caller's model (it may be shared with a training engine)
        if hasattr(model, "cfg") and hasattr(model.cfg, "dtype") \
                and model.cfg.dtype != self.dtype:
            import copy
            import dataclasses
            model = copy.copy(model)
            model.cfg = dataclasses.replace(model.cfg, dtype=self.dtype)
            self.module = model

        # ---- parameters ------------------------------------------------ #
        if params is None:
            assert hasattr(model, "init_params"), (
                "pass params= or a model with init_params(rng)")
            params = model.init_params(jax.random.PRNGKey(seed))
        specs = (model.partition_specs() if hasattr(model, "partition_specs")
                 else jax.tree.map(lambda _: PartitionSpec(), params))
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s or PartitionSpec()), specs,
            is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))
        params = jax.tree.map(lambda p: jnp.asarray(p, self.dtype)
                              if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating) else p,
                              params)
        if self.quantize_weights:
            # GroupQuantizer analogue: block matmul weights → int8 payload
            # + per-channel scales; the model dequantizes at the matmul
            # (models/gpt.py:_wget) so decode reads half the weight bytes
            from deepspeed_tpu.module_inject.quantization import (
                quantize_block_params, quantize_partition_specs)
            specs = quantize_partition_specs(specs, params)
            params = jax.jit(quantize_block_params)(params)
            self.param_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s or PartitionSpec()), specs,
                is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))
            log_dist("int8 weight quantization applied to injected blocks "
                     "(reference GroupQuantizer analogue)", ranks=[0])
        self.params = jax.device_put(params, self.param_shardings)
        # per-shape compiled-program caches, LRU-bounded by
        # config.program_cache_size (an adversarial mix of request shapes
        # must evict old programs, not grow device memory without limit)
        self._generate_fns: "OrderedDict[Any, Callable]" = OrderedDict()
        self._forward_fns: "OrderedDict[bool, Callable]" = OrderedDict()
        # input shapes traced into each forward jit since its last clear —
        # lets forward() evict lazily (only when a NEW shape would push the
        # inner cache past the cap) instead of dropping warm programs
        self._forward_seen: "dict[bool, set]" = {}
        self.program_cache_evictions = 0
        self._bucketed_generate = (
            hasattr(self.module, "generate")
            and "prompt_len" in inspect.signature(
                self.module.generate).parameters)
        log_dist(f"InferenceEngine ready: dtype={self.dtype.__name__}, "
                 f"tp={int(self.mesh.shape['tensor'])}, "
                 f"kernel_inject={self._config.replace_with_kernel_inject}", ranks=[0])

    # ------------------------------------------------------------------ #
    def load_checkpoint(self, load_dir, tag=None):
        """Load weights saved by the training engine (reference sharded-
        checkpoint load ``inference/engine.py:419``; resharding happens on
        restore, the TPU analogue of MP-resize via state_dict_factory)."""
        from deepspeed_tpu.runtime.checkpointing import load_params_only
        self.params = load_params_only(load_dir, tag, self.params,
                                       self.param_shardings, dtype=self.dtype)
        return self

    # ------------------------------------------------------------------ #
    def _span(self, name, **args):
        return maybe_span(name, self.tracer, **args)

    def _record_request(self, op, t0, out, new_tokens=0):
        """Per-request telemetry record.  Blocks on the request's own output
        (not the whole device) to get a true end-to-end latency; compiled
        here means telemetry-off serving never blocks at all."""
        if self.telemetry is None:
            return out
        # the decode span covers device-side token generation: it opens at
        # dispatch return and closes when the request's output is ready
        with self._span("inference.decode", op=op, new_tokens=new_tokens):
            jax.block_until_ready(out)
        dt = max(time.perf_counter() - t0, 1e-9)
        rec = {"op": op, "latency_ms": dt * 1000.0}
        if hasattr(out, "shape") and getattr(out, "ndim", 0) >= 1:
            rec["batch"] = int(out.shape[0])
        if new_tokens:
            rec["new_tokens"] = int(new_tokens)
            rec["tokens_per_sec"] = new_tokens / dt
        self._request_count += 1
        self.telemetry.emit("inference_request", rec, step=self._request_count)
        return out

    # ---- LRU program-cache plumbing ---------------------------------- #
    def _cache_get(self, cache: OrderedDict, key):
        fn = cache.get(key)
        if fn is not None:
            cache.move_to_end(key)
        return fn

    def _cache_put(self, cache: OrderedDict, key, fn, which: str):
        cache[key] = fn
        cap = max(1, int(self._config.program_cache_size))
        while len(cache) > cap:
            old_key, _ = cache.popitem(last=False)
            self._program_evicted(which, old_key)
        return fn

    def _program_evicted(self, which: str, key):
        self.program_cache_evictions += 1
        if self.telemetry is not None:
            self.telemetry.emit("program_cache_evict",
                                {"cache": which, "key": repr(key),
                                 "evictions": self.program_cache_evictions})

    def forward(self, input_ids, *args, attention_mask=None, **kwargs):
        """Full-sequence logits (one jitted program per input shape).
        ``attention_mask`` [B, S] is honored when the model's
        ``forward_logits`` accepts it (encoder serving with padded
        batches).  The compiled function is cached PER MASK PRESENCE —
        a masked call never reuses (or pays for) the maskless program."""
        input_ids = jnp.asarray(input_ids)
        model = self.module
        takes_mask = (hasattr(model, "forward_logits") and "attention_mask"
                      in inspect.signature(model.forward_logits).parameters)
        if attention_mask is not None and not takes_mask:
            raise ValueError("this model's forward path does not accept "
                             "attention_mask")
        use_mask = attention_mask is not None
        fn = self._cache_get(self._forward_fns, use_mask)
        if fn is None:

            def fwd(params, ids, mask=None):
                if hasattr(model, "forward_logits"):
                    if use_mask:
                        return model.forward_logits(params, ids,
                                                    attention_mask=mask)
                    return model.forward_logits(params, ids)
                logits, _ = model.apply_with_cache(
                    params, ids, model.init_cache(ids.shape[0], ids.shape[1]))
                return logits

            fn = jax.jit(fwd) if use_mask else jax.jit(lambda p, i: fwd(p, i))
            self._cache_put(self._forward_fns, use_mask, fn, "forward")
            self._forward_seen[use_mask] = set()
        # one jit holds one program per input shape; keep that inner cache
        # bounded too, but evict LAZILY: only a call that would trace a NEW
        # shape past the cap clears it — a steady-state workload sitting at
        # exactly the cap keeps replaying its warm programs
        seen = self._forward_seen.setdefault(use_mask, set())
        shape_key = (tuple(input_ids.shape), str(input_ids.dtype))
        if shape_key not in seen:
            if len(seen) >= max(1, int(self._config.program_cache_size)):
                fn.clear_cache()
                seen.clear()
                self._program_evicted("forward_shapes", use_mask)
            seen.add(shape_key)
        t0 = time.perf_counter()
        with self._span("inference.forward", batch=int(input_ids.shape[0]),
                        seq=int(input_ids.shape[1]), masked=use_mask):
            if use_mask:
                out = fn(self.params, input_ids, jnp.asarray(attention_mask))
            else:
                out = fn(self.params, input_ids)
            return self._record_request("forward", t0, out)

    __call__ = forward

    PROMPT_BUCKET = 64   # prompt lengths are padded up to multiples of this

    def generate(self, input_ids, max_new_tokens: int = 32, temperature: float = 0.0,
                 rng=None, **kwargs):
        """Autoregressive generation (reference patched ``generate`` :588).

        Prompt lengths are BUCKETED (right-padded to a multiple of
        ``PROMPT_BUCKET``, with the true length passed as a traced scalar):
        a serving workload compiles one program per (batch, bucket,
        max_new_tokens) instead of one per exact prompt length — the role
        the reference's fixed-workspace CUDA graphs play
        (``inference/engine.py:500-528``)."""
        input_ids = jnp.asarray(input_ids)
        B, S = input_ids.shape
        model = self.module
        bucketed = self._bucketed_generate
        if bucketed:
            S_pad = max(self.PROMPT_BUCKET,
                        -(-S // self.PROMPT_BUCKET) * self.PROMPT_BUCKET)
            limit = getattr(getattr(model, "cfg", None), "n_positions", None)
            if limit is not None and S_pad + max_new_tokens > limit:
                # padding would overflow the cache capacity — fall back to
                # the exact-shape program for this (rare, near-limit) call
                bucketed = False
        if bucketed:
            pad = jnp.zeros((B, S_pad - S), input_ids.dtype)
            ids = jnp.concatenate([input_ids, pad], axis=1)
            key = ((B, S_pad), max_new_tokens, float(temperature), "bucketed")
            fn = self._cache_get(self._generate_fns, key)
            if fn is None:
                def gen(params, ids, plen, r):
                    return model.generate(params, ids, max_new_tokens,
                                          rng=r, temperature=temperature,
                                          prompt_len=plen)
                fn = self._cache_put(self._generate_fns, key, jax.jit(gen),
                                     "generate")
            r = rng if rng is not None else jax.random.PRNGKey(self._config.seed)
            t0 = time.perf_counter()
            with self._span("inference.generate", batch=B, prompt_len=S,
                            max_new_tokens=max_new_tokens, bucketed=True):
                # prefill = host-side staging/dispatch of the fused
                # prefill+decode program; device-side completion is the
                # decode span inside _record_request
                with self._span("inference.prefill", batch=B, prompt_len=S,
                                bucket=S_pad):
                    out = fn(self.params, ids, jnp.asarray(S, jnp.int32), r)
                # drop the pad tail: [prompt | pad | new] -> [prompt | new]
                out = jnp.concatenate([out[:, :S], out[:, S_pad:]], axis=1)
                return self._record_request("generate", t0, out,
                                            new_tokens=B * max_new_tokens)
        key = (input_ids.shape, max_new_tokens, float(temperature))
        fn = self._cache_get(self._generate_fns, key)
        if fn is None:
            def gen(params, ids, r):
                return model.generate(params, ids, max_new_tokens,
                                      rng=r, temperature=temperature)

            fn = self._cache_put(self._generate_fns, key, jax.jit(gen),
                                 "generate")
        r = rng if rng is not None else jax.random.PRNGKey(self._config.seed)
        t0 = time.perf_counter()
        with self._span("inference.generate", batch=B, prompt_len=S,
                        max_new_tokens=max_new_tokens, bucketed=False):
            with self._span("inference.prefill", batch=B, prompt_len=S):
                out = fn(self.params, input_ids, r)
            return self._record_request("generate", t0, out,
                                        new_tokens=B * max_new_tokens)


def init_inference(model=None, config=None, **kwargs):
    """Module-level helper mirroring ``deepspeed.init_inference``
    (``deepspeed/__init__.py:215``): merge config dict + kwargs."""
    cfg_dict = dict(config or {})
    cfg_dict.update(kwargs)
    mesh = cfg_dict.pop("mesh", None)
    params = cfg_dict.pop("params", None)
    policy = cfg_dict.pop("injection_policy", cfg_dict.pop("policy", None))
    # "telemetry" is either a TelemetryHub instance (shared with a training
    # engine) or a telemetry config dict to build a standalone hub from
    telemetry = cfg_dict.pop("telemetry", None)
    tracer = cfg_dict.pop("tracer", None)
    if isinstance(telemetry, dict):
        from deepspeed_tpu.runtime.config import DeepSpeedTelemetryConfig
        from deepspeed_tpu.telemetry import TelemetryHub
        tcfg = DeepSpeedTelemetryConfig(**telemetry)
        telemetry = TelemetryHub.from_config(tcfg) if tcfg.enabled else None
    ds_config = DeepSpeedInferenceConfig(**cfg_dict)
    return InferenceEngine(model, config=ds_config, params=params, mesh=mesh,
                           policy=policy, telemetry=telemetry, tracer=tracer)
