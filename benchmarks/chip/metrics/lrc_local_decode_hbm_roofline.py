"""Share of the HBM roofline the ragged XOR decode kernel reaches on an
LRC cell's GET path, where every GET rebuilds its one lost data block by
XOR of a local group's other ``locality`` members: the local decodes'
algorithm bytes, (locality + 1) blocks per GET, which is the window's
payload bytes times (locality + 1) / k (k and ``locality`` from the
cell's configuration file), over the device time of the kernel's jitted
module, against the chip's HBM bandwidth (peaks.py)."""

MODULES = ("ragged_xor_tiles",)


def read(r):
    import harness

    if r.trace is None:
        return None
    seconds = r.trace.module_seconds(MODULES)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    config = harness.cell_spec(bench, r.cell["name"])[1]
    if seconds <= 0 or "locality" not in config or not r.counters["payload_bytes"]:
        return None
    work = r.counters["payload_bytes"] * (config["locality"] + 1) / config["k"]
    return 100.0 * work / (r.peaks["hbm_bytes_per_s"] * seconds)
