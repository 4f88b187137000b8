"""Per cent of the train step's device time a recurrent mixer spends
between its projections and AROUND its chunk rule, both passes: the parts
`conv` + `gates` + `norm_gate` + `retile` + `counters` of the kinds that open
a `rule` (`GatedDeltaNet`, `KimiDeltaAttention`, `Mamba2Mixer`). With
`mixer_rule_share_of_step.train` and the rows that carry no part (the row
loops' own slicing) it is what `delta_core_share_of_step` /
`kda_share_of_step` / `ssd_share_of_step` read. Left out where no `rule` ran
under a scope."""
from benchmark import scope_reduce

AROUND = ("conv", "gates", "norm_gate", "retile", "counters")


def read(run):
    acct = scope_reduce.scope_account(run)
    if acct is None:
        return None
    mixers = acct.mixers()
    return scope_reduce.share(
        run, lambda layer, kind, parts: kind in mixers and parts[:1] and parts[0] in AROUND)
