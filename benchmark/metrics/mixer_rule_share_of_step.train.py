"""Per cent of the train step's device time under a recurrent mixer's
`rule`, both passes: the chunk rule alone — `chunk_gated_delta_rule`
(solve + scan), the `dl4j_kda_*` kernel pair or
`chunk_channel_gated_delta_rule`, `ssd_chunked` — without the convolutions,
gates, norm and re-tiling around it (`mixer_around_rule_share_of_step.train`).
Left out where no `rule` ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: parts[:1] == ("rule",))
