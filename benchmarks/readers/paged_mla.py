"""Reader for the paged kernel of latent attention (``paged_mla_attention``
of ``ops/pallas/decode_attention.py``): its share of its roofline.  The keys
and rows are the kind's count of what the program ran
(``kinds/serve_backlog_resident.py:attention_counters``, left in the run's
counters under the names of the K/V kernel it was written for), the
operations and bytes of a key ``lib/arith_mla.py``'s, the widths the
configuration file's.  A program without the kernel (a parent commit, a
model with K and V) gives nothing to read: None, and the metric is left out
of the line."""

from benchmarks.lib import arith, arith_mla

KERNEL = "paged_mla_attention"


def work(run):
    """(operations, bytes) latent attention needed over the traced stretch:
    the kind's count of keys and rows at ``lib/arith_mla.py``'s cost of a
    key; None without the count or on a model with K and V."""
    import jax.numpy as jnp
    c, cfg = run["counters"], run["cell"].config
    if "paged_gqa_flops" not in c or "kv_lora_rank" not in cfg:
        return None
    layers = cfg["num_hidden_layers"]
    keys = arith_mla.keys_read(c["paged_gqa_flops"], cfg["num_attention_heads"],
                               cfg["head_dim"])
    rows = layers * (c["attention_rows_live"] + c["attention_rows_idle"])
    return arith_mla.latent_attention(
        keys, rows, cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_rope_head_dim"], jnp.dtype(cfg["dtype"]).itemsize)


def roofline(run):
    """The least time for the operations and bytes the kernel needed over
    the traced stretch over its time there."""
    t = run["trace"]
    needed = work(run) if t is not None else None
    if needed is None:
        return None
    took = t.op_seconds().get(KERNEL)
    if not took:
        return None
    bound_s, which = arith.roofline_seconds(*needed, run["peaks"])
    run["notes"].setdefault("roofline_bound", {})[KERNEL] = which
    return 100.0 * bound_s / took
