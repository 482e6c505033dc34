"""Share of the HBM roofline the ragged XOR decode kernel reaches on the
GET path: the XOR decodes' algorithm bytes (codec_bytes: t sources read,
one block written, per lost block) over the device time of the kernel's
jitted module, against the chip's HBM bandwidth (peaks.py)."""

MODULES = ("ragged_xor_tiles",)


def read(r):
    if r.trace is None:
        return None
    seconds = r.trace.module_seconds(MODULES)
    work = r.work.get("read.xor", 0)
    if seconds <= 0 or not work:
        return None
    return 100.0 * work / (r.peaks["hbm_bytes_per_s"] * seconds)
