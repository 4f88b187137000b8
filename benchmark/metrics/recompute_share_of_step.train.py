"""Per cent of the train step's device time spent running forward work
AGAIN in the backward region: operations whose name stack holds
`rematted_computation` (what a `jax.checkpoint` — remat per block — runs a
second time; `benchmark/scope_reduce.py`), a grouped product the compiler
stripped of its stack booked by what made its operands. The lever of
ROADMAP S10: a value kept (`REMAT_KEEP`) leaves this column. Left out where
nothing was recomputed under a scope (a step without remat)."""
from benchmark import scope_reduce


def read(run):
    acct = scope_reduce.scope_account(run)
    if acct is None or not acct.step_s or not acct.recompute_s:
        return None
    return 100.0 * acct.recompute_s / acct.step_s
