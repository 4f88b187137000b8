"""Per cent of the train step's device time under a looped stack's scope
(`dl4j.L<i>.loopedstack`), forward, recompute and backward: every application
of every nested block and the final norm, in every pass — what the loop costs,
beside `head_loss_share_of_step.train` (the loop's heads and the exit loss)
and the update. `scope_reduce.parse` names an operation by its INNERMOST layer
scope (the nested block's), so this reader hands `scope_reduce.account` the
metadata of the operations whose name stack holds the stack's own scope and
no other: what it then calls scoped is the loop. Left out for a program
without scopes, a run without a capture, or a step without a looped stack."""
import re

from benchmark import scope_reduce

LOOP = re.compile(r"dl4j\.L[A-Za-z0-9_-]+\.loopedstack\b")


def read(run):
    path = scope_reduce.capture_file(run.cell["name"]) if scope_reduce.program_has_seam() else None
    if path is None:
        return None
    inside = {name: kept for name, metas in scope_reduce.op_metadata(path).items()
              if (kept := [m for m in metas if LOOP.search(m["tf_op"])])}
    name, runs = run.trace.main_module()
    acct = scope_reduce.account(run.trace.ops[0], name, runs, inside) if inside else None
    if acct is None or not acct.step_s or not acct.scoped_s:
        return None
    return 100.0 * acct.scoped_s / acct.step_s
