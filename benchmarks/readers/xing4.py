"""Reader for the residual streams' mixes of a stack under hyper-connections
(Xing4.0): the time the chip's memory needs for what the mixes of the traced
stretch's steps MUST move (``lib/arith_xing4.py:mix_bytes``: the streams read
once and written once, the sublayer's input written and its output read, a
row a sublayer; ``phi`` once a sublayer a step) over the time under the three
``hc_*`` scopes there.  The steps read are the LAST ``Trace.program_runs()``
of them, as ``readers/step_share.py`` reads them.  A run without a trace, a
program that opens no such scope or a configuration without ``hyper`` gives
None and the metric is left out of the line."""

from benchmarks.lib import arith_xing4
from benchmarks.readers.program_spans import scope_share_pct

MIX_SCOPES = ("hc_coeff", "hc_pre", "hc_post")


def mix_bytes_pct(run):
    """100 x (the mixes' bytes at the chip's memory bandwidth) over the time
    under :data:`MIX_SCOPES`."""
    import jax.numpy as jnp
    t, cfg = run["trace"], run["cell"].config
    kw = cfg["model"]["kwargs"]
    if t is None or not kw.get("hyper"):
        return None
    share = scope_share_pct(run, list(MIX_SCOPES))
    rows = [r for r in run["counters"].get("traced_step_rows", ()) if r > 0]
    held = t.program_runs()
    kept = rows[-held:] if held else rows
    if not share or not kept:
        return None
    nbytes = arith_xing4.mix_bytes(sum(kept), len(kept), kw["n_layer"], kw["hyper"][0],
                                   kw["n_embd"], jnp.dtype(cfg["dtype"]).itemsize)
    return 100.0 * (nbytes / run["peaks"]["hbm_bytes_per_s"]) / (share / 100.0 * t.busy_s())
