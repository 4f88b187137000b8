"""Per cent of the train step's device time, chip 0, under
`dl4j.routedexperts/exchange` — `out` (a rank's tokens to the ranks that hold
their experts) and `back` (the experts' rows to the tokens' rank), forward,
what a block's recompute sends again, and their transposes in the backward:
the `all_to_all`s between expert-parallel ranks and nothing else (the bucket
and the sorts on either side are `bucket`, `sort`, `gather`). Found by the
program's names (`benchmark/scope_reduce.py`). Left out where no exchange ran
under a scope (one rank, a program without the exchange)."""
from benchmark import scope_reduce


def exchange(layer, kind, parts):
    return kind == "routedexperts" and parts[:1] == ("exchange",)


def read(run):
    return scope_reduce.share(run, exchange)
