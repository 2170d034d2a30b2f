"""exchange_ms_per_wave.dedup: device milliseconds of the collective
operations per wave of the traced window (mean over the chips): the probe
exchange and the sum of the per-row miss counts, whatever implements them.

A collective is an operation whose HLO opcode is all-to-all, all-reduce,
all-gather or collective-permute, or the -start / -done half of one of
their async forms. The compiled routed admission of `dedup_docs_d4` (v5e
2x2) holds `%all_to_all.3 s32[4,11536,2] all-to-all` (the (local, row)
send buffers) and `%psum.7 s32[16384] all-reduce` (the miss counts).
Without a trace, or with no collective in it, there is nothing to read.
"""
import re

COLLECTIVE = re.compile(
    r" (all-to-all|all-reduce|all-gather|collective-permute)(-start|-done)?$")


def collective_s(trace) -> float:
    """Device seconds of the collective operations, mean over chips."""
    return sum(v for name, v in trace.op_s.items() if COLLECTIVE.search(name))


def read(run):
    if run.trace is None or not run.waves:
        return None
    t = collective_s(run.trace)
    if t <= 0:
        return None
    return t / len(run.waves) * 1e3
