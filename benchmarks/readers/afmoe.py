"""Readers for ANY expert bank, by what its configuration's ``step_work``
function says of it (first Trinity's, ``afmoe``: a bank that holds a SHARE of
the experts its router chooses among, behind a dense lead, so that the
configuration's ``num_experts`` (the experts HELD) and ``num_hidden_layers``
(dense lead included) are not what ``readers/moe.py:bank_least_seconds`` and
``readers/zaya.py`` take them for).  The counts here come from the file's
``step_work`` function (``{"layers", "experts", "held", "top_k", "hidden",
"width"}``: the expert layers, the router's width, the experts held), so a
whole bank in every layer (OLMoE) reads what ``readers/moe.py`` read of it to
the digit, and ``grouped_matmul_roofline`` is ONE entry for every cell whose
program runs the kernel.  A run without a trace, or a program
whose spans carry no such stat (a parent commit), gives None and the metric
is left out of the line."""

import types

import numpy as np

from benchmarks.lib.cells import resolve
from benchmarks.readers import moe


def _bank(run):
    cfg = run["cell"].config
    return resolve(cfg["step_work"]["weights"])(cfg["model"]["kwargs"])["bank"]


def experts_reached_pct(run):
    """Of the experts the router chooses among, the share a step's live rows
    reach in ONE expert layer; the held share's expected reach is the same
    share of the experts held (6.3 of 16 at 32 rows of top 4 of 256).  The
    engine's ``moe_experts_touched`` on ``serve.decode.commit`` is of the SUM
    over layers for a periodic stack (an expert counts once however many
    layers reach it), so a step's reading ``T`` of ``N`` experts over ``L``
    layers is inverted, layers taken as independent: ``1 - (1 - T/N)^(1/L)``.
    The mean over the traced stretch's decode steps."""
    stats = moe.span_stats(run) or {}
    values = [s["moe_experts_touched"] for s in stats.get(moe.LOAD_SPAN, [])
              if "moe_experts_touched" in s]
    if not values:
        return None
    bank = _bank(run)
    missed = 1.0 - np.minimum(np.asarray(values, np.float64) / bank["experts"], 1.0)
    return 100.0 * float(np.mean(1.0 - missed ** (1.0 / bank["layers"])))


def bank_least_seconds(run):
    """``moe.bank_least_seconds`` for a held bank behind a dense lead: that
    reader, given the EXPERT layers and the router's width where it reads the
    file's ``num_hidden_layers`` and ``num_experts``, times ``held /
    experts``: a call a program an expert layer, each the held share of what a
    whole bank of the router's width would need for the program's live rows
    (the expected reach and the assignments; the larger of two quotients
    scales with both)."""
    moe.span_stats(run)                 # read once, kept on the run itself
    bank = _bank(run)
    whole = types.SimpleNamespace(config=dict(
        run["cell"].config, num_hidden_layers=bank["layers"],
        num_experts=bank["experts"], num_experts_per_tok=bank["top_k"],
        hidden_size=bank["hidden"], intermediate_size=bank["width"]))
    found = moe.bank_least_seconds(dict(run, cell=whole))
    return found and (found[0] * bank["held"] / bank["experts"], found[1])


def grouped_matmul_roofline(run):
    """:func:`bank_least_seconds` over the self time of the kernel
    ``grouped_matmul`` (two calls an expert layer: gate|up, then down)."""
    t = run["trace"]
    took = t.op_seconds().get(moe.BANK_KERNEL) if t is not None else None
    least = bank_least_seconds(run) if took else None
    if least is None:
        return None
    run["notes"].setdefault("roofline_bound", {})[moe.BANK_KERNEL] = least[1]
    return 100.0 * least[0] / took
