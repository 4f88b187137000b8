"""Device time of the flash-attention kernels (`dl4j_flash*`: forward, dq,
dkv) as a share of the device time of the train step program's runs, chip 0."""


def read(run):
    _, runs = run.trace.main_module()
    step = sum(e - s for s, e in runs) / 1e9
    flash = run.trace.kernel_seconds("dl4j_flash")
    if not step or not flash:
        return None
    return 100.0 * flash / step
