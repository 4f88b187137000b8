"""Per cent of the train step's device time in the PRIMAL forward pass:
operations under any device scope whose name stack has no `transpose(`
wrapper and no part `grad` (`benchmark/scope_reduce.py`: the gradient
products a `custom_vjp`'s forward rule makes ahead of the backward pass —
the row-blocked head's dz, dx and dW since PR 45 — are the backward
region's). Under remat 'full' the backward region runs about as much again
as recompute (`recompute_share_of_step.train`). Left out for a program
without scopes."""
from benchmark import scope_reduce


def read(run):
    acct = scope_reduce.scope_account(run)
    if acct is None or not acct.step_s or not acct.scoped_s:
        return None
    return 100.0 * acct.forward_s / acct.step_s
