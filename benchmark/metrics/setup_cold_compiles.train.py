"""Backend compilations before the window that the persistent cache did not
serve (`backend_compiles - cache_hits` over `init` and the fits before the
window's): 0 on a warm machine; not 0 says which run of a pair was not warm."""
from benchmark import harness

_setup = harness.module("metrics", "setup_program_s.train")


def read(run):
    acc = _setup.account(run)
    if acc is None:
        return None
    compiles = [acc[0]["init"]["compile"]] + [f["compile"] for f in acc[1]]
    return sum(c["backend_compiles"] - c["cache_hits"] for c in compiles)
