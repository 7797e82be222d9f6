"""Reader for the paged kernel of latent attention (``paged_mla_attention``
of ``ops/pallas/decode_attention.py``): its share of its roofline.  The kind
leaves the count of cached vectors READ (a decode row's keys once a row, a
prompt chunk's once for all its queries: the bytes) and of (query, key) PAIRS
(the operations) as ``attention_keys_read`` and ``attention_key_products``
(``kinds/serve_backlog_resident.py:attention_counters`` by whole pages,
``kinds/serve_backlog_resident_hyper.py`` to the key), both summed over
layers; what a read and a pair cost is ``lib/arith_mla.py``'s, the widths the
configuration file's.  A row without a request counts nothing.  A program
without the kernel (a parent commit, a model with K and V) gives nothing to
read: None, and the metric is left out of the line."""

from benchmarks.lib import arith, arith_mla

KERNEL = "paged_mla_attention"


def work(run):
    """(operations, bytes) latent attention needed over the traced stretch:
    the kind's reads, pairs and live rows at ``lib/arith_mla.py``'s cost of
    each; None without the count or on a model with K and V."""
    import jax.numpy as jnp
    c, cfg = run["counters"], run["cell"].config
    if "attention_keys_read" not in c or "kv_lora_rank" not in cfg:
        return None
    return arith_mla.latent_attention(
        c["attention_keys_read"], c["attention_key_products"],
        cfg["num_hidden_layers"] * c["attention_rows_live"],
        cfg["num_attention_heads"], cfg["kv_lora_rank"],
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
