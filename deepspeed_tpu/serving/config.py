"""Serving config — the ``"serving"`` block of the ds_config document.

The reference snapshot (v0.8.3) predates DeepSpeed-FastGen, so there is no
reference config surface to mirror; the knobs follow the same shape
philosophy as the rest of ``runtime/config.py``: one pydantic block, safe
defaults, every field documented where it is consumed.

Sizing guidance (README § Serving): ``block_size`` trades internal
fragmentation (last-block waste, avg block_size/2 tokens per sequence)
against block-table length and scatter/gather granularity — 16 suits toy
and CPU runs, 32–64 suits real HBM arenas.  ``num_blocks`` bounds the
arena: total cache bytes = n_layer * num_blocks * block_size * dtype_bytes *
the lanes of the model's cache spec (``GPTConfig.cache_lanes``: ``2 * kv_heads
* head_dim`` for K and V, 384 for a latent cache of 256 + 64;
``serving/kv_cache.py:arena_bytes``).
"""

from typing import Dict, Optional

from pydantic import Field

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class DeepSpeedServingConfig(DeepSpeedConfigModel):
    """``serving`` block — continuous batching + paged KV cache
    (``deepspeed_tpu/serving/``).  See README § Serving."""
    enabled: bool = False
    # ---- paged KV arena -------------------------------------------------- #
    block_size: int = 16          # tokens per physical KV block
    num_blocks: int = 256         # arena capacity in blocks (incl. trash)
    max_blocks_per_seq: int = 0   # 0 -> ceil(n_positions / block_size)
    # ---- continuous batching --------------------------------------------- #
    max_batch_size: int = 8       # decode slots (fixed compiled batch shape)
    prefill_chunk: int = 64       # chunked-prefill tokens per engine step
    max_queue: int = 1024         # waiting-queue bound; submit raises past it
    # ---- scheduling ------------------------------------------------------ #
    slo_preemption: bool = True   # higher SLO classes may evict lower ones
    # per-class TTFT bounds (ms) for the goodput ledger's tokens-within-
    # bound accounting; unset classes use telemetry/ledger.py defaults
    slo_ttft_bound_ms: Dict[str, float] = Field(default_factory=dict)
    max_new_tokens_default: int = 64
    eos_token_id: Optional[int] = None
    # ---- tiered KV (serving/kv_tiering.py) -------------------------------- #
    kv_tiering: bool = False          # spill preempted KV to host/NVMe
    kv_offload_dir: Optional[str] = None   # None -> private tempdir
    kv_host_cache_bytes: int = 1 << 30     # host-LRU tier budget
    kv_spill_budget_bytes: int = 0         # total spill cap; 0 = unbounded
    kv_spill_chunk_blocks: int = 8         # copy-ring chunk (blocks)
    kv_ring_depth: int = 2                 # outstanding D2H chunk gathers
    # ---- prefix cache (serving/prefix_cache.py) --------------------------- #
    prefix_cache: bool = False        # share full prompt blocks, refcounted
    prefix_cache_blocks: int = 0      # pinned-block cap; 0 = unbounded
    # ---- resilience (README § Serving resilience) -------------------------- #
    # per-class request deadline (ms from arrival); an expired request is
    # cancelled at the next step boundary, its blocks freed and its prefill
    # booked as wasted.  Unset/0 classes have no deadline.
    deadline_ms: Dict[str, float] = Field(default_factory=dict)
    # bounded step dispatch (comm/bounded.py): a compiled serve step that
    # exceeds this raises ServeStepTimeout and triggers in-process
    # recovery instead of hanging the engine forever.  0 = inline dispatch.
    serve_step_timeout_s: float = 0.0
    # adaptive admission ladder (scheduler.AdmissionController): the
    # oldest-waiting age that trips brownout; 2x trips batch-class shed,
    # 4x sheds standard too.  0 disables the queue-age signal (the
    # SLOMonitor TTFT-burn signal still drives the ladder when wired).
    queue_age_watermark_ms: float = 0.0
    brownout_max_new_tokens: int = 0  # brownout cap on max_new_tokens; 0 = off
    shed_recovery_steps: int = 16     # calm step evaluations per rung down
    # ---- the host's wait for a step ---------------------------------------- #
    # the thread that dispatched a step POLLS for its token row and does not
    # sleep on it.  A host that sleeps 30 of every 32 ms is woken late and
    # makes its turn-round on a core that was clocked down meanwhile: on a
    # shared machine that is +1.8 ms a step in one run of six (PERF.md § 6,
    # PR 47).  Costs a core; the GIL is let go every turn of the loop.  A
    # step under `serve_step_timeout_s` sleeps as before.
    poll_token_row: bool = False
    # ---- numerics / misc ------------------------------------------------- #
    dtype: str = "bfloat16"
    seed: int = 0
    telemetry_every: int = 8      # serve_step gauge cadence (engine steps)

    @property
    def jnp_dtype(self):
        import jax.numpy as jnp
        return {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
                "float16": jnp.float16, "fp16": jnp.float16,
                "float32": jnp.float32, "fp32": jnp.float32,
                "float": jnp.float32}[str(self.dtype)]
