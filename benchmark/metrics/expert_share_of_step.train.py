"""Device time of the routed experts — the grouped products (`ragged-dot*`:
XLA's own grouped-matmul kernels and their metadata), the top-k over the
router's outputs, and the sort, gather and scatter around the sorted buffer
— as a share of the device time of the train step program's runs, chip 0.
The trace names a gather or a scatter `fusion.N` like anything else, so an
operation belongs to the layer when it is a grouped product, when its
instruction mentions an array with the buffer's row count or the
assignment count, or when it is a sort over [tokens, experts]."""
import re

from benchmark import trace_reduce

GROUPED = re.compile(r"^ragged-dot")


def sizes(run):
    """(tokens, experts, assignments, buffer rows) of one expert layer,
    the last by the program's own rule (`RoutedExperts.capacity`); None
    without routed experts."""
    c = run.cfg
    if "num_experts_published" not in c:
        return None
    rows = run.counters["rows_per_step"] // run.cell["chips"]
    n = rows * c["input"]["seq_len"]
    k, held, all_ = c["num_experts_per_tok"], c["num_experts"], c["num_experts_published"]
    factor = c["program"]["args"].get("capacity_factor", 1.25)
    cap = -(-int(factor * n * k * held / all_) // 128) * 128
    return n, all_, n * k, min(cap, n * k)


def expert_seconds(run):
    s = sizes(run)
    if s is None:
        return None
    n, experts, assignments, cap = s
    buffer = re.compile(rf"\[({assignments}|{cap})[,\]]")
    router = f"[{n},{experts}]"
    ivs = []
    for start, end, name in run.trace.ops[0]:
        short = trace_reduce.short(name)
        if (GROUPED.match(short) or buffer.search(name)
                or (short.startswith("sort") and router in name)):
            ivs.append((start, end))
    return trace_reduce.total(trace_reduce.union(ivs)) / 1e9 or None


def read(run):
    _, runs = run.trace.main_module()
    step = sum(e - s for s, e in runs) / 1e9
    experts = expert_seconds(run)
    if not step or not experts:
        return None
    return 100.0 * experts / step
