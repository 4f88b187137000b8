"""GB/s at which chip 0 sends its exchange: the bytes a step's forward and
backward send from one chip — the program's `exchange_bytes` counter summed
over the expert layers: rows out and back and their two cotangents, to the
other ranks alone — over the device seconds under
`dl4j.routedexperts/exchange` in those two passes (what a block's recompute
sends again is left out of both). An achieved rate, not a share of a peak:
`peaks.json` holds no interconnect peak. Left out without the counter or the
scope."""
from benchmark import harness, scope_reduce

_share = harness.module("metrics", "expert_exchange_share_of_step.train")


def read(run):
    sent = run.counters.get("exchange_bytes_per_step")
    acct = scope_reduce.scope_account(run)
    if not sent or acct is None:
        return None
    seconds = sum(forward + backward - recompute
                  for key, (forward, backward, recompute, _) in acct.rows.items()
                  if _share.exchange(*key))
    return sent * acct.steps / seconds / 1e9 if seconds else None
