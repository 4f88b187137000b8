"""Per step of the window, the time the fit thread spent handing features,
labels and masks to the runtime (the program's `put` span: until
`device_put` returns, not until the bytes are on the chip)."""
from benchmark import span_reduce


def read(run):
    return span_reduce.phase_ms(run, "put")
