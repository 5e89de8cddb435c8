"""Share of the memory roofline reached by the batched SSSP sweep, in %.

The least bytes any implementation must move per sweep
(``bench.lib.roofline_sssp.sssp_min_bytes``: the weighted CSR read once,
each key's distance and parent written once) over the chip's HBM
bandwidth, divided by the device's busy time in the traced window.
Moves ``teps``.
"""
from bench.lib.roofline_sssp import sssp_min_bytes

UNIT = "%"


def read(run):
    f = run.facts
    busy = run.trace.busy_s()
    bandwidth = run.peaks.get("hbm_bytes_per_s")
    if not f.get("sweeps") or busy <= 0 or not bandwidth:
        return None
    least = f["sweeps"] * sssp_min_bytes(f["n"], f["m"], f["keys_per_sweep"])
    return 100.0 * (least / bandwidth) / busy
