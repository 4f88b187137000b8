"""Score elements inside the band over score elements in the blocks the
shipped block plan visits for it, forward, per cent: the windowed attention
layers' own account of their plan (`pallas_kernels.flash_visits` for the
blocks `pick_flash_blocks` gives the call), read once a fit into
`telemetry.fit_log()` (`attention`) beside the window and the head counts —
50 at blocks of 512 under a window of 512, 80 at 128; 100 would be a plan
that visits nothing outside the band. The least over the layers. Left out
for a program or a model without the counter."""
from benchmark import span_reduce


def attention(run):
    fit = span_reduce.fit_entry(run)
    return (fit or {}).get("attention") or None


def read(run):
    a = attention(run)
    return None if a is None else 100.0 * min(x["band_fill"] for x in a)
