"""Per cent of the train step's device time under a recurrent mixer's
`rule`, both passes: the chunk rule alone — the `dl4j_gdn_*`, `dl4j_kda_*`
or `dl4j_ssd_*` kernel pair, or the XLA form each falls back to (the
divisor of `gdn_roofline` / `kda_roofline` / `ssd_roofline`) — without the convolutions,
gates, norm and re-tiling around it (`mixer_around_rule_share_of_step.train`).
Left out where no `rule` ran under a scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: parts[:1] == ("rule",))
