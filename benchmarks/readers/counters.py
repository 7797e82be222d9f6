"""Readers of what the run counted itself (the program's counters and the
benchmark's own stamps).  A reader takes the run and returns a number, or
None where there is nothing to read."""


def counter(run, key, scale=1.0):
    value = run["counters"].get(key)
    return None if value is None else scale * float(value)


def mfu_pct(run):
    """tokens/s x the benchmark's own FLOPs per token over chips x the
    device kind's published bf16 peak.  Recomputation is not counted."""
    c = run["counters"]
    if "flops_per_token" not in c or run["peaks"] is None:
        return None
    return 100.0 * c["tokens_per_s"] * c["flops_per_token"] / (
        run["device"]["count"] * run["peaks"]["bf16_flops_per_s"])


def peak_hbm_pct(run):
    """The fullest chip's peak (arrays plus programs' temporaries) over what
    the runtime offers on that chip."""
    limit = run["device"].get("memory_limit_bytes")
    if not limit:
        return None
    return 100.0 * run["counters"]["memory_peak_bytes"] / limit
