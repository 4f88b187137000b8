"""Median gap on chip 0's timeline between the end of one run of the train
step program and the start of the next: what the inner loop and the feed
leave the device waiting for."""
import statistics


def read(run):
    gaps = run.trace.module_gaps_ms()
    return statistics.median(gaps) if gaps else None
