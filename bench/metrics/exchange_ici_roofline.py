"""exchange_ici_roofline: the probe exchange's share of the chips'
inter-chip (ICI) roofline in the traced window.

Bytes are what the exchange needs, whatever implements it: in a wave of n
items on D chips, each of the n x k probes whose bit another chip holds,
(D - 1) / D of them, crosses once as a 4-byte index, and the n per-row
miss counts come back as 4 bytes each. A chip's part is a D-th of that.
The share is those bytes over the chip's ICI peak (`ici_bits_per_s` / 8),
over the collective device time that `exchange_ms_per_wave.dedup` reads.
Without a trace, or with no collective in it, there is nothing to read.
"""
import importlib.util
import os


def _collective_s(trace) -> float:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "exchange_ms_per_wave.dedup.py")
    spec = importlib.util.spec_from_file_location("bench_exchange_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.collective_s(trace)


def exchange_bytes(n_items: int, k: int, chips: int) -> float:
    """Bytes a wave's exchange needs over all chips."""
    return n_items * k * (chips - 1) / chips * 4 + n_items * 4


def read(run):
    if run.trace is None or not run.waves:
        return None
    t = _collective_s(run.trace)
    if t <= 0:
        return None
    D, k = run.cell.chips, int(run.cell.cfg["k"])
    per_chip = sum(exchange_bytes(w.n, k, D) for w in run.waves) / D
    return 100.0 * per_chip / (run.peaks["ici_bits_per_s"] / 8) / t
