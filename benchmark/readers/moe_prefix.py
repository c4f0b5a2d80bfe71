"""How much of the dropless buffer the expert layer touches for the rows
that are there (ops/moe.py: the layer runs on a prefix of the buffer chosen
from the routed count).

The program reports, per train step, the rows its experts computed
(`moe_rows_local`) and the rows of the prefixes they were computed on
(`moe_rows_touched`); both ride the metric ring and reach every unit's
record (`drivers/train_tokens.py` copies the epoch's extras).  A program
without the second counter (the parent of PR 30, which touches the whole
buffer whatever was routed) gives None.
"""

from __future__ import annotations


def touched_over_live(run):
    """Rows of the buffer the window's expert layers ran on over the rows
    routed to them: 1.0 would be work sized by the rows alone, positions x
    top_k over the live rows a layer is work sized by the buffer."""
    units = [u for u in run.window.units
             if u.get("moe_rows_touched") and u.get("moe_rows_local")]
    if not units:
        return None
    return (sum(u["moe_rows_touched"] for u in units)
            / sum(u["moe_rows_local"] for u in units))
