"""Share of the HBM roofline the ragged GF(256) decode kernel reaches on
the GET path: the row decodes' algorithm bytes (codec_bytes: k sources
read, the lost blocks written) over the device time of the kernel's
jitted module, against the chip's HBM bandwidth (peaks.py). GF(256) has
no published vector-unit peak, so this is a share of the HBM roofline
only."""

MODULES = ("ragged_gf256_tiles",)


def read(r):
    if r.trace is None:
        return None
    seconds = r.trace.module_seconds(MODULES)
    work = r.work.get("read.gf256", 0)
    if seconds <= 0 or not work:
        return None
    return 100.0 * work / (r.peaks["hbm_bytes_per_s"] * seconds)
