"""Source blocks the window's repairs read per block they rebuilt
(RepairReport.blocks_fetched over blocks_repaired): t for a CORE column,
k for an RS stripe (the paper's Table 1)."""


def read(r):
    rebuilt = r.counters["repair_blocks_repaired"]
    return r.counters["repair_blocks_fetched"] / rebuilt if rebuilt else None
