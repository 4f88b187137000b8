"""Per cent of the train step's device time under `dl4j.routedexperts`,
every part and both passes: the router's product and top-k (`route`), the
sort, the gathers into the sorted buffer, the two grouped products with the
activation between them (`product`: `expert_product_share_of_step.train`),
`combine`, the shared expert and the counters. Found by the program's names
(`benchmark/scope_reduce.py`). Left out where no routed experts ran under a
scope."""
from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, lambda layer, kind, parts: kind == "routedexperts")
