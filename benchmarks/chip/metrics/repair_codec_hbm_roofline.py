"""Share of the HBM roofline the repair codec (BlockFixer's jitted XOR
and GF(256) products) reaches: the rebuilds' algorithm bytes (codec_bytes)
over the device time of those jitted modules, against the chip's HBM
bandwidth (peaks.py)."""

MODULES = ("_xor_jit", "_gf_matmul_jit")


def read(r):
    if r.trace is None:
        return None
    seconds = r.trace.module_seconds(MODULES)
    work = r.work.get("repair.xor", 0) + r.work.get("repair.gf256", 0)
    if seconds <= 0 or not work:
        return None
    return 100.0 * work / (r.peaks["hbm_bytes_per_s"] * seconds)
